"""What device the process got, and its peak numbers for utilization.

`device_summary()` is the identity every entry point prints at start and
`/healthz` carries: platform, exact `device_kind`, device count — so a run
that did not get the chip says so in its first lines.

`peak_tflops` is a table keyed by the EXACT `device_kind` JAX reports. A
TPU whose kind is not in the table raises: a utilization against a guessed
peak is worse than none. Non-TPU platforms have no datasheet peak (None).

`resolve_peak` adds a measured matmul-rate stand-in for non-TPU platforms
only (the CPU smoke lanes), labeled `measured` so it can never be read as
a fraction of a datasheet peak. It is never taken on a TPU.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

# bf16 peak TFLOP/s per chip. Numbers: Google Cloud TPU documentation, the
# "System architecture" page of each generation ("TPU v5e": 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s). Kind strings: as jax spells them in
# jax/_src/pallas/mosaic/tpu_info.py; "TPU v5 lite" is what the v5e chip
# prints. Only kinds whose JAX device is one whole chip are listed.
_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}

_MEASURED: dict = {}  # device_kind -> measured peak (once per process)


def device_summary() -> dict:
    """{"platform", "kind", "count"} of the devices JAX found."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peak_tflops(device) -> Optional[float]:
    """bf16 peak TFLOP/s for one chip; None off-TPU; raises on a TPU kind
    the table does not hold."""
    if device.platform != "tpu":
        return None
    try:
        return _PEAK_TFLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak on record for TPU device_kind "
            f"{device.device_kind!r}; add it to utils/hw._PEAK_TFLOPS with "
            "its source") from None


def measured_peak_tflops(device, n: int = 512, reps: int = 3,
                         min_probe_s: float = 0.01, max_n: int = 4096,
                         ) -> Optional[float]:
    """Best-of-`reps` f32 `n`x`n` matmul rate on a non-TPU `device`,
    TFLOP/s — the measured stand-in where no datasheet peak exists.
    Cached per device kind (one short calibration per process). None when
    the probe itself fails (no backend, OOM) — callers then report no MFU.

    The probe size ADAPTS: `n` doubles (to `max_n`) until one timed run
    takes at least `min_probe_s`, so the measurement is compute-bound
    rather than a dispatch latency."""
    if device.platform == "tpu":
        raise ValueError("a TPU's peak comes from the datasheet table, "
                         "never from a measured matmul rate")
    key = (device.platform, device.device_kind)
    if key in _MEASURED:
        return _MEASURED[key]
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        mm = jax.jit(lambda x, y: x @ y)

        def one_run(size: int, i: int) -> float:
            a = jax.device_put(jnp.ones((size, size), jnp.float32), device)
            np.asarray(mm(a, a))  # compile + warm for this size
            b = a * float(i + 1)  # fresh operand: no result caching
            t0 = time.perf_counter()
            np.asarray(mm(b, b))  # value-fetch sync (bench discipline)
            return time.perf_counter() - t0

        dt = one_run(n, 0)
        while dt < min_probe_s and n < max_n:
            n *= 2
            dt = one_run(n, 0)
        best = 2.0 * n * n * n / max(dt, 1e-9) / 1e12
        for i in range(1, reps):
            dt = one_run(n, i)
            tf = 2.0 * n * n * n / max(dt, 1e-9) / 1e12
            best = max(best, tf)
        _MEASURED[key] = best
    except Exception:
        _MEASURED[key] = None
    return _MEASURED[key]


def resolve_peak(device) -> Tuple[Optional[float], str]:
    """(peak TFLOP/s, source): on a TPU the datasheet number
    ("datasheet") or an error; elsewhere a per-process measured matmul
    calibration ("measured"), else (None, "none"). MFU consumers must
    carry the source label — a measured-peak MFU is a utilization proxy,
    not a fraction of silicon peak, and must never be compared against
    one."""
    if device.platform == "tpu":
        return peak_tflops(device), "datasheet"
    peak = measured_peak_tflops(device)
    if peak:
        return peak, "measured"
    return None, "none"

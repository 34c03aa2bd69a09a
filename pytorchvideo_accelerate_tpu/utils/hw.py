"""What device the process got.

`device_summary()` is the identity every entry point prints at start and
`/healthz` carries: platform, exact `device_kind`, device count — so a run
that did not get the chip says so in its first lines. The chip's peak
numbers live with the benchmark (`benchmarks/peaks.json`).
"""

from __future__ import annotations


def device_summary() -> dict:
    """{"platform", "kind", "count"} of the devices JAX found."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}

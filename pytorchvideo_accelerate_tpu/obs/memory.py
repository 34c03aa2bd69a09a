"""Device-memory ledger: per-component HBM byte accounting (pva-tpu-hbm).

Every byte figure the control plane used to act on was a *declared
estimate* (`ring_bytes(geom)`, `footprint_mb` at model registration).
The ledger makes device memory an observed truth: the real allocation
sites — trainer state (params/opt/EMA), the guard LKG ring, the device
prefetch ring, serving weight pins + compiled-bucket caches, streaming
ring pools — register their actual array bytes here, and the ledger
cross-checks the attributed sum live against the backend's
`device.memory_stats()` (`bytes_in_use` / `peak_bytes_in_use`) where the
platform exposes it (TPU/GPU; the CPU backend does not, and the ledger
NEVER fakes device bytes — `source` stays "estimate").

The residual discipline is PR-3's `obs/unattributed_s` applied to bytes:
`unattributed_bytes = bytes_in_use - sum(components)` is published
explicitly instead of silently absorbed, so a growing residual is a
visible accounting bug, not a hidden leak. Declared-vs-measured drift
past `drift_tol` is itself a gauge (`pva_hbm_drift_frac{component=}`):
when an estimate lies, the lie is a metric.

Exported surface (docs/OBSERVABILITY.md § memory ledger):

- gauges `pva_hbm_bytes{component=}` (+ the explicit ``unattributed``
  component), `pva_hbm_bytes_in_use`, `pva_hbm_peak_bytes`,
  `pva_hbm_attributed_frac`, `pva_hbm_drift_frac{component=}`;
- watermark warnings into the flight ring when `bytes_in_use` crosses
  `watermark_frac` of the backend's `bytes_limit` (edge-triggered — one
  warning per excursion, re-armed on recovery);
- `measured_bytes(component)` / `source()` for admission paths
  (`SessionTable`, `ModelBudget`): *measured* ledger bytes on device,
  declared estimates as the documented CPU/test fallback.

Arming discipline (`utils/sync.py`): the module-level `register()` /
`release()` hooks at the allocation sites are ONE module-global read +
`None` check while disarmed — no dict, no lock, no jax. `configure()`
arms the process-default ledger; tests construct private instances.

Stdlib-only at import time: jax is imported lazily inside
`default_device_stats()` and only when a caller actually asks the
backend (obs/ must stay importable from worker threads without jax).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

from pytorchvideo_accelerate_tpu.utils.sync import make_lock, shared_state

# The armed process-default ledger or None. Module-global by design (the
# utils/sync.py `_runtime` pattern): the disarmed hot path at every
# allocation site is one load + a None check.
_DEFAULT: Optional["MemoryLedger"] = None


def default_device_stats() -> Optional[Dict[str, int]]:
    """`memory_stats()` of device 0, or None when the backend does not
    expose it (CPU) or jax is absent entirely. Never raises: a dying
    probe must not take an allocation site down with it."""
    try:
        import jax

        dev = jax.devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats or "bytes_in_use" not in stats:
        return None
    return {k: int(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


def tree_nbytes(tree) -> int:
    """Total `.nbytes` over a pytree of arrays — jax.tree_util when
    available, a stdlib container walk otherwise (tests without jax)."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        leaves = _walk_leaves(tree)
    return sum(int(getattr(leaf, "nbytes", 0)) for leaf in leaves)


def _walk_leaves(tree):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _walk_leaves(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _walk_leaves(v)
    else:
        yield tree


@shared_state("_bytes", "_declared", "_peak_attributed", "_over_watermark")
class MemoryLedger:
    """Per-component device-byte accounting with a live backend
    cross-check. Thread-safe: allocation sites (streaming pool builds,
    serving weight pins) race scrape ticks and the doctor's snapshot."""

    def __init__(self, registry=None, recorder=None, *,
                 watermark_frac: float = 0.92,
                 drift_tol: float = 0.25,
                 stats_fn: Optional[Callable[[], Optional[Dict[str, int]]]]
                 = None):
        from pytorchvideo_accelerate_tpu.obs.registry import get_registry

        self._lock = make_lock("obs.MemoryLedger._lock")
        self._bytes: Dict[str, int] = {}
        self._declared: Dict[str, int] = {}
        self._peak_attributed = 0
        self._over_watermark = False  # edge trigger for the watermark warn
        self.watermark_frac = float(watermark_frac)
        self.drift_tol = float(drift_tol)
        self.registry = registry if registry is not None else get_registry()
        self.recorder = recorder
        self._stats_fn = stats_fn if stats_fn is not None \
            else default_device_stats
        self._g_bytes = self.registry.gauge(
            "pva_hbm_bytes", "attributed device bytes per component "
            "(component=unattributed is the residual vs bytes_in_use)",
            labelnames=("component",))
        self._g_drift = self.registry.gauge(
            "pva_hbm_drift_frac", "relative declared-vs-measured drift "
            "per component (0 when the estimate is honest)",
            labelnames=("component",))
        self._g_in_use = self.registry.gauge(
            "pva_hbm_bytes_in_use", "backend bytes_in_use (0 = backend "
            "exposes no memory_stats; see pva_hbm_attributed_frac)")
        self._g_peak = self.registry.gauge(
            "pva_hbm_peak_bytes", "backend peak_bytes_in_use, or the peak "
            "attributed sum when the backend exposes no memory_stats")
        self._g_frac = self.registry.gauge(
            "pva_hbm_attributed_frac",
            "attributed / bytes_in_use (1.0 when no backend stats: the "
            "ledger is then the only accounting there is)")
        # live reads: the scrape sees current stats without a tick cycle
        self._g_in_use.set_function(lambda: (self.device_stats() or {})
                                    .get("bytes_in_use", 0))
        self._g_peak.set_function(lambda: self.peak_bytes())
        self._g_frac.set_function(lambda: self.attributed_frac())
        self._g_bytes.set_function(lambda: self.unattributed_bytes(),
                                   component="unattributed")

    # --- accounting ---------------------------------------------------------

    def register(self, component: str, nbytes: int,
                 declared: Optional[int] = None) -> None:
        """Add `nbytes` of live device allocation to `component`;
        `declared` is the estimate the caller would have used before this
        ledger existed (drives the drift gauge)."""
        n = int(nbytes)
        with self._lock:
            self._bytes[component] = self._bytes.get(component, 0) + n
            if declared is not None:
                self._declared[component] = (
                    self._declared.get(component, 0) + int(declared))
            total = sum(self._bytes.values())
            if total > self._peak_attributed:
                self._peak_attributed = total
            cur = self._bytes[component]
            dec = self._declared.get(component)
        self._g_bytes.set(cur, component=component)
        if dec:
            self._g_drift.set(abs(cur - dec) / dec, component=component)
        self._check_watermark()

    def release(self, component: str, nbytes: Optional[int] = None,
                declared: Optional[int] = None) -> None:
        """Return bytes to the pool; `nbytes=None` clears the component.
        Clamped at zero — a double release is an accounting bug, not a
        negative gauge."""
        with self._lock:
            if nbytes is None:
                self._bytes.pop(component, None)
                self._declared.pop(component, None)
                cur, dec = 0, None
            else:
                cur = max(0, self._bytes.get(component, 0) - int(nbytes))
                self._bytes[component] = cur
                if declared is not None:
                    dec = max(0,
                              self._declared.get(component, 0)
                              - int(declared))
                    self._declared[component] = dec
                else:
                    dec = self._declared.get(component)
        self._g_bytes.set(cur, component=component)
        if dec:
            self._g_drift.set(abs(cur - dec) / dec, component=component)

    def component_bytes(self, component: str) -> int:
        with self._lock:
            return self._bytes.get(component, 0)

    def attributed_bytes(self) -> int:
        with self._lock:
            return sum(self._bytes.values())

    # --- backend cross-check ------------------------------------------------

    def device_stats(self) -> Optional[Dict[str, int]]:
        try:
            return self._stats_fn()
        except Exception:
            return None

    def source(self) -> str:
        """"measured" when the backend exposes memory_stats, else
        "estimate" — the label every report that carries ledger bytes
        must carry too (never fake device bytes on a CPU host)."""
        return "measured" if self.device_stats() is not None else "estimate"

    def measured_bytes(self, component: str) -> Optional[int]:
        """Ledger bytes for `component` IF this host measures device
        memory; None on estimate-only hosts (admission falls back to the
        caller's declared figure — the documented CPU/test path)."""
        if self.device_stats() is None:
            return None
        return self.component_bytes(component)

    def peak_bytes(self) -> int:
        stats = self.device_stats()
        if stats is not None and "peak_bytes_in_use" in stats:
            return stats["peak_bytes_in_use"]
        with self._lock:
            return self._peak_attributed

    def unattributed_bytes(self) -> int:
        stats = self.device_stats()
        if stats is None:
            return 0
        return max(0, stats.get("bytes_in_use", 0) - self.attributed_bytes())

    def attributed_frac(self) -> float:
        stats = self.device_stats()
        if stats is None or not stats.get("bytes_in_use"):
            return 1.0
        return min(1.0, self.attributed_bytes() / stats["bytes_in_use"])

    def _check_watermark(self) -> None:
        stats = self.device_stats()
        limit = (stats or {}).get("bytes_limit")
        if not limit:
            return
        over = stats.get("bytes_in_use", 0) >= self.watermark_frac * limit
        with self._lock:
            fire = over and not self._over_watermark
            self._over_watermark = over
        if fire and self.recorder is not None:
            self.recorder.warn(
                "hbm watermark crossed",
                bytes_in_use=stats.get("bytes_in_use", 0),
                bytes_limit=limit, watermark_frac=self.watermark_frac)

    # --- snapshots ----------------------------------------------------------

    def drift(self) -> Dict[str, float]:
        """component -> |measured - declared| / declared, for components
        that declared an estimate."""
        with self._lock:
            return {c: abs(self._bytes.get(c, 0) - d) / d
                    for c, d in self._declared.items() if d}

    def snapshot(self) -> Dict:
        """The doctor-facing view: per-component bytes, the residual,
        drift offenders, and the provenance label."""
        stats = self.device_stats()
        with self._lock:
            components = dict(self._bytes)
            peak_att = self._peak_attributed
        drift = self.drift()
        out = {
            "source": "measured" if stats is not None else "estimate",
            "components": components,
            "attributed_bytes": sum(components.values()),
            "unattributed_bytes": self.unattributed_bytes(),
            "attributed_frac": self.attributed_frac(),
            "peak_bytes": (stats or {}).get("peak_bytes_in_use", peak_att),
            "drift": drift,
            "drift_over_tol": sorted(c for c, d in drift.items()
                                     if d > self.drift_tol),
        }
        if stats is not None:
            out["bytes_in_use"] = stats.get("bytes_in_use", 0)
            if "bytes_limit" in stats:
                out["bytes_limit"] = stats["bytes_limit"]
        return out


# --- module-level arming ----------------------------------------------------

def get_ledger() -> Optional[MemoryLedger]:
    return _DEFAULT


def configure(enabled: bool = True, **kwargs) -> Optional[MemoryLedger]:
    """Arm (or disarm, with enabled=False) the process-default ledger.
    kwargs pass through to `MemoryLedger` (tests inject `stats_fn`)."""
    global _DEFAULT
    _DEFAULT = MemoryLedger(**kwargs) if enabled else None
    return _DEFAULT


def register(component: str, nbytes: int,
             declared: Optional[int] = None) -> None:
    """Allocation-site hook; disarmed this is one global read + return."""
    led = _DEFAULT
    if led is None:
        return
    led.register(component, nbytes, declared=declared)


def release(component: str, nbytes: Optional[int] = None,
            declared: Optional[int] = None) -> None:
    led = _DEFAULT
    if led is None:
        return
    led.release(component, nbytes, declared=declared)

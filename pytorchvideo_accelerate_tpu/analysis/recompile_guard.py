"""Runtime counterpart of the `recompile` lint rule.

The static rule catches the *syntax* of recompile hazards; this guard
catches the *fact*: after warmup, the train step's jit cache must stop
growing. Every steady-state cache miss is a multi-second XLA compile
stall in the middle of training — the failure mode the pjit-at-scale
writeups (arXiv:2204.06514) spend a section on eliminating.

Mechanics: `jax.jit` wrappers expose `_cache_size()` (the number of
compiled executables behind the callable). `arm()` records the size
after the first real step (the legitimate compile); `sample()` reports
growth since then and mirrors it into the `pva_train_recompiles` gauge
of the obs metric registry. Trainer.fit() arms after step one, samples
at every `log_every` drain and epoch end, and surfaces the total in its
perf dict as `train_recompiles` — which the benchmark reads as
`recompiles` and requires to be 0 (`benchmarks/jobs/train_fit.py`).

`_cache_size` is a private-but-stable jax API (present in 0.9.0); if a
future jax drops it the guard degrades to inert (reports None) rather than lying
with a zero, and the static rule keeps standing watch.
"""

from __future__ import annotations

from typing import Any, Optional

GAUGE_NAME = "pva_train_recompiles"


def cache_size(fn: Any) -> Optional[int]:
    """Compiled-executable count behind a jitted callable; None when the
    wrapper doesn't expose one (non-jit callable, future jax)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:  # a broken probe must never break the step loop
        return None


class RecompileGuard:
    """Steady-state jit-cache-growth monitor for one compiled callable."""

    def __init__(self, fn: Any, registry: Any = None,
                 gauge_name: str = GAUGE_NAME):
        self.fn = fn
        self._baseline: Optional[int] = None
        if registry is None:
            from pytorchvideo_accelerate_tpu.obs import get_registry

            registry = get_registry()
        self._gauge = registry.gauge(
            gauge_name,
            "jit cache entries compiled after warmup (steady state == 0)")
        self._gauge.set(0.0)

    @property
    def armed(self) -> bool:
        return self._baseline is not None

    @property
    def supported(self) -> bool:
        return cache_size(self.fn) is not None

    def arm(self) -> None:
        """Take the post-warmup baseline (call after the first step has
        returned — its compile is the legitimate one)."""
        self._baseline = cache_size(self.fn)

    def sample(self) -> Optional[int]:
        """Cache growth since `arm()` (0 is the healthy reading); updates
        the gauge. None when unarmed or the probe is unavailable."""
        if self._baseline is None:
            return None
        size = cache_size(self.fn)
        if size is None:
            return None
        recompiles = max(0, size - self._baseline)
        self._gauge.set(float(recompiles))
        return recompiles

"""Compile seconds and persistent-cache traffic, from `jax.monitoring`
(copied from `chip_smoke.py`'s CompileCounters; the original stays there).
JAX counts a cache "miss" when it writes an entry."""

from __future__ import annotations


class CompileCounters:
    def __init__(self):
        import jax

        self.compiles = []  # (seconds, jitted function), cache reads included
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((secs, fun_name))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        """(compile seconds so far, number of compiles, hits, writes)."""
        return {"compile_s": sum(s for s, _ in self.compiles),
                "compiles": len(self.compiles), "cache_hits": self.hits,
                "cache_written": self.misses}

    def slowest(self, n=5):
        return [[name, secs] for secs, name in sorted(self.compiles, reverse=True)[:n]]

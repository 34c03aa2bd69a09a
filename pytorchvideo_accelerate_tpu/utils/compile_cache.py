"""The one rule for JAX's persistent compilation cache.

Every entry point that compiles a model (`run.main`, `serving.server.main`,
`benchmarks/run.py`, `chip_smoke.py`) calls
`enable_compile_cache()` before its first compile:

- `JAX_COMPILATION_CACHE_DIR` set: JAX has already read it into its own
  config, so nothing is set in code — whoever launched the process placed
  the cache;
- otherwise `<checkout>/.jax_cache` (listed in `.gitignore`). The path is
  part of the cache key, so it is a fixed function of where the code
  lives — never a temp dir, a pid or a timestamp, which would never hit.

Tier-1 runs with `JAX_ENABLE_COMPILATION_CACHE=false` in the environment
(tests/conftest.py), which wins over anything set here.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache lives in."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        return cache_dir
    import jax

    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def cache_entries(cache_dir: str) -> int:
    """Executables in the cache directory (JAX keeps one `<key>-cache`
    file per entry beside its `-atime` bookkeeping file)."""
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0

"""`compile_s` (layer: entry). Seconds JAX spent in backend compiles during
set-up and the run, persistent-cache reads included, from `jax.monitoring`
(`/jax/core/compile/backend_compile_duration`). Moves `setup_s`."""


def read(results):
    return results["compile"]["compile_s"]

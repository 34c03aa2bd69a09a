"""`health_gauges_ms_per_step` (layer: in-graph health gauges: trainer/steps.py
health_metrics). Device milliseconds a step of every op of the compiled step
under `health/`: the global norms of the new parameters and of the update, and
the non-finite flag, which the step computes where `obs.enabled` is on
(lib/scoped.py; a fusion that spans two scopes counts under both). What the
program's own instrumentation costs on the device. None where the program has
no such scope."""

from benchmarks.lib import scoped

SCOPE = r"/health/"


def read(results):
    return scoped.scope_ms_per_step(results, SCOPE)

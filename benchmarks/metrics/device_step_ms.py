"""`device_step_ms` (layer: step). Median device duration of one execution of
the step program, from the `XLA Modules` line of the first chip."""

from benchmarks.lib import xtrace


def read(results):
    trace = results["trace"]
    if not trace or not trace["step_ms"]:
        return None
    return xtrace.median(trace["step_ms"])

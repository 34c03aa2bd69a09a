"""`step_gap_p95_ms` (layer: train loop). 95th percentile of the gaps between
one execution of the step program on the device and the next, from the
`XLA Modules` line of the first chip. Needs a few traced steps."""

from benchmarks.lib import xtrace


def read(results):
    trace = results["trace"]
    if not trace or len(trace["step_gap_ms"]) < 3:
        return None
    return xtrace.percentile(trace["step_gap_ms"], 95.0)

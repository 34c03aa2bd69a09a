"""A conv class's share of its roofline, from the work and the trace."""

from benchmarks.lib import xtrace


def class_share(results, work_class, scope_pattern):
    """least seconds for the class's work in one step (from the reference's
    shapes) / device seconds per step under the class's scopes, in percent.
    None where the trace has no op under those scopes or no whole step."""
    trace, work = results["trace"], results["work"]
    if not trace or not work or not trace["traced_steps"]:
        return None
    least_s = work["by_class"][work_class]["least_s"] / results["chips"]
    if least_s <= 0:
        return None
    seconds = xtrace.scope_seconds(trace["ops"], scope_pattern)
    if seconds <= 0:
        return None
    return 100.0 * least_s / (seconds / trace["traced_steps"])

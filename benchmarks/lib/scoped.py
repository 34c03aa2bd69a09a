"""What the token cell's readers share: a class's roofline share that may not
pass 100%, and the device milliseconds a step under a scope."""

from benchmarks.lib import roofline, xtrace


def checked_class_share(results, work_class, scope_pattern, name):
    """`roofline.class_share`; a share over 100% raises (the work is counted
    too high, or the scope misses part of the time) and nothing is reported."""
    share = roofline.class_share(results, work_class, scope_pattern)
    if share is not None and share > 100.0:
        raise ValueError(
            f"{name} reads {share:.1f}%: the least time of class "
            f"{work_class!r} is longer than the device time under "
            f"{scope_pattern!r}")
    return share


def scope_ms_per_step(results, scope_pattern):
    """Device milliseconds a traced step of the ops whose scope matches (self
    times, so a loop's body counts once). None where the trace has no whole
    step or nothing under the scope (a program without such layers)."""
    trace = results["trace"]
    if not trace or not trace["traced_steps"]:
        return None
    seconds = xtrace.scope_seconds(trace["ops"], scope_pattern)
    if seconds <= 0:
        return None
    return 1e3 * seconds / trace["traced_steps"]

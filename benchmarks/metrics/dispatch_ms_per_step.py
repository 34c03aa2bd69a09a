"""`dispatch_ms_per_step` (layer: train loop). `fit()`'s own `step` span (the
call that dispatches the jitted step; it absorbs device time only when the
dispatch queue pushes back), summed over the log windows inside the measured
window, per step."""


def read(results):
    step_s = results["spans"].get("step")
    if step_s is None or not results["steps"]:
        return None
    return 1000.0 * step_s / results["steps"]

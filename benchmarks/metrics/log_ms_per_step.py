"""`log_ms_per_step` (layer: train loop). `fit()`'s `log` span, the deferred
fetch of an earlier step's metrics: the one place where the steady loop may
block on the device. Summed over the log windows inside the measured window,
per step."""


def read(results):
    log_s = results["spans"].get("log")
    if log_s is None or not results["steps"]:
        return None
    return 1000.0 * log_s / results["steps"]

"""`device_idle_share` (layer: device). 1 - (union of the intervals in which
an op ran on the chip) / (traced window), averaged over the chips used."""


def read(results):
    trace = results["trace"]
    if not trace or not trace["busy_s"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

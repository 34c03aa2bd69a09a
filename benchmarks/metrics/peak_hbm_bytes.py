"""`peak_hbm_bytes` (layer: device). What the fullest chip held at once
(`jobs/train_fit.py` `memory_peak_bytes`): the larger of
`memory_stats()["peak_bytes_in_use"]` (live arrays) and the live arrays at the
window's two ends plus `["peak_bytes_reserved"]` (the running program's
scratch: PERF.md section 4), read after the window and before the reference
runs. Not the sum of the two peaks: they need not fall together."""


def read(results):
    return results["memory_peak_bytes"] or None

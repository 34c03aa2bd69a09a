"""`peak_hbm_bytes` (layer: device). The fullest chip's
`memory_stats()["peak_bytes_in_use"]` (live arrays) plus
`["peak_bytes_reserved"]` (the running program's scratch: PERF.md, Findings),
read after the window and before the reference runs."""


def read(results):
    return results["memory_peak_bytes"] or None

"""`gdn_scan_roofline` (layer: kernels: Gated DeltaNet chunked scan). Least time for
the class `gdn_scan` of one step's work (benchmarks/lib/work_qwen3_next.py:
forward and backward, recompute not counted, each part at
max(flops/peak, bytes/bandwidth)) over the device time a step of the ops under
the scope `gdn/scan` of the compiled step. The scope selects the time, whatever
lowers the layer under it. None where the trace or the program has nothing
there; a share over 100% raises and reports nothing (lib/scoped.py)."""

from benchmarks.lib import scoped

SCOPE = r"/gdn/scan/"


def read(results):
    return scoped.checked_class_share(results, "gdn_scan", SCOPE, "gdn_scan_roofline")

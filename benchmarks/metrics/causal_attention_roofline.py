"""`causal_attention_roofline` (layer: kernels: causal grouped-query attention). Least time for
the class `attn_core` of one step's work (benchmarks/lib/work_qwen3_next.py:
forward and backward, recompute not counted, each part at
max(flops/peak, bytes/bandwidth)) over the device time a step of the ops under
the scope `attn/core` of the compiled step. The scope selects the time, whatever
lowers the layer under it. None where the trace or the program has nothing
there; a share over 100% raises and reports nothing (lib/scoped.py)."""

from benchmarks.lib import scoped

SCOPE = r"/attn/core/"


def read(results):
    return scoped.checked_class_share(results, "attn_core", SCOPE, "causal_attention_roofline")

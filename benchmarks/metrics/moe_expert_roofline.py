"""`moe_expert_roofline` (layer: kernels: grouped expert products). Least time for
the class `moe_experts` of one step's work (benchmarks/lib/work_qwen3_next.py:
forward and backward, recompute not counted, each part at
max(flops/peak, bytes/bandwidth)) over the device time a step of the ops under
the scope `moe/.../experts` of the compiled step. The scope selects the time, whatever
lowers the layer under it. None where the trace or the program has nothing
there; a share over 100% raises and reports nothing (lib/scoped.py)."""

from benchmarks.lib import scoped

# under `moe/` whatever lies between (the layer chooses its row buffers in a
# `cond`, whose branch names join the path)
SCOPE = r"/moe/(?:[^| ]+/)?experts/"


def read(results):
    return scoped.checked_class_share(results, "moe_experts", SCOPE, "moe_expert_roofline")

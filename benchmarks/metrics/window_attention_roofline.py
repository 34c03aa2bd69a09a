"""`window_attention_roofline` (layer: kernels: causal sliding-window attention). Least time
for the class `attn_window` of one step's work (benchmarks/lib/work_smallthinker.py:
forward and backward of the windowed layers' q k^T and p v, the pairs counted
under the band, recompute not counted, each part at max(flops/peak,
bytes/bandwidth)) over the device time a step of the ops under the scope
`swa/core` of the compiled step. The scope selects the time, whatever lowers
the layer under it; pairs a lowering computes outside the band earn nothing.
None where the trace, the work or the program has nothing there (a program
without windowed layers, a work count without the class); a share over 100%
raises and reports nothing (lib/scoped.py)."""

from benchmarks.lib import scoped

SCOPE = r"/swa/core/"


def read(results):
    work = results.get("work")
    if not work or "attn_window" not in work["by_class"]:
        return None
    return scoped.checked_class_share(results, "attn_window", SCOPE,
                                      "window_attention_roofline")

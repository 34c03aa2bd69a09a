"""`mlp_roofline` (layer: kernels: gated MLP products). Least time for the
class `mlp` of one step's work (benchmarks/lib/work_ouro.py: the gate, up and
down products of every layer execution, forward and backward, recompute not
counted, each at max(flops/peak, bytes/bandwidth)) over the device time a step
of the ops under the scope `mlp/` of the compiled step (products, the SiLU and
the multiply, forward, rematerialised forward and backward). The scope selects
the time, whatever lowers the layer under it. None where the trace, the work
or the program has nothing there (a work count without the class, a program
without the scope); a share over 100% raises and reports nothing
(lib/scoped.py)."""

from benchmarks.lib import scoped

SCOPE = r"/mlp/"


def read(results):
    work = results.get("work")
    if not work or "mlp" not in work["by_class"]:
        return None
    return scoped.checked_class_share(results, "mlp", SCOPE, "mlp_roofline")

"""`ssd_scan_roofline` (layer: kernels: SSD chunked scan). Least time for the
class `ssd_scan` of one step's work (benchmarks/lib/work_nemotron_h.py: the
per-token recurrence's 5 P N a head a token forward and twice that backward,
least bytes x, B, C, dt and y, each part at max(flops/peak, bytes/bandwidth))
over the device time a step of the ops under the scope `mamba/scan` of the
compiled step. The scope selects the time, whatever lowers the scan under it;
the chunked form's extra products earn nothing. None where the trace, the
work or the program has nothing there (a program without Mamba-2 mixers, a
work count without the class); a share over 100% raises and reports nothing
(lib/scoped.py)."""

from benchmarks.lib import scoped

SCOPE = r"/mamba/scan/"


def read(results):
    work = results.get("work")
    if not work or "ssd_scan" not in work["by_class"]:
        return None
    return scoped.checked_class_share(results, "ssd_scan", SCOPE,
                                      "ssd_scan_roofline")

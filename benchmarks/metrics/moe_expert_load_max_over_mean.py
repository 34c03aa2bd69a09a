"""`moe_expert_load_max_over_mean` (layer: mixture). The fullest held
expert's rows over the mean held expert's, in the layer where that ratio is
worst: the program's own step counter (`obs/moe_expert_load_max_over_mean`,
sampled at each log boundary), averaged over the log windows inside the
measured window. 1.0 is a perfect balance; the grouped products' time follows
the fullest expert's tile count. None where the program logs no such counter."""


def read(results):
    values = (results.get("counters") or {}).get("moe_expert_load_max_over_mean")
    if not values:
        return None
    return sum(values) / len(values)

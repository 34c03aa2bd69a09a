"""`step_mfu` (layer: step). Algorithmic FLOPs of one clip's forward and
backward pass (convs and matmuls of the plain reference's jaxpr; recompute
and padding are not work) times the clips the traced window's step executions
trained, over the window's length on the device's clock (from the start of
the first counted execution to the end of the last, idle gaps included), over
the chip's bf16 peak."""


def read(results):
    trace = results["trace"]
    if not results["work"] or not results["peaks"] or not trace:
        return None
    if not trace["traced_steps"] or not trace["window_s"]:
        return None
    flops_per_clip = results["work"]["flops_per_step"] / results["global_batch"]
    clips_per_s_per_chip = (trace["traced_steps"] * results["global_batch"]
                            / trace["window_s"] / results["chips"])
    return 100.0 * flops_per_clip * clips_per_s_per_chip / results["peaks"]["bf16_flops_per_s"]

"""`exit_loss_ms_per_step` (layer: looped head: per-pass head, loss, exit gate
and distribution). Device milliseconds a step of every op of the compiled step
under `lm_head/`, `loss/` or `exit/` (self times, an op under two of them
counted once): what a looped model pays after every pass for its logits, its
cross-entropy, its gate and its exit distribution, forward, rematerialised
forward and backward (lib/scoped.py). None where the program has no `exit/`
scope (a model without a loop), whatever it has under the other two."""

from benchmarks.lib import scoped

EXIT = r"/exit/"
SCOPE = r"/(?:lm_head|loss|exit)/"


def read(results):
    if scoped.scope_ms_per_step(results, EXIT) is None:
        return None
    return scoped.scope_ms_per_step(results, SCOPE)

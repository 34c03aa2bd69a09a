"""`optimizer_ms_per_step` (layer: optimizer: trainer/steps.py
_make_update_step, trainer/optim.py). Device milliseconds a step of every op
of the compiled step under `optim/`: the optimizer's transformation (the
clip's global norm, AdamW or SGD with momentum and weight decay), the update's
application, the EMA and the guard's keep where there are any, and the step's
`grad_norm` (lib/scoped.py; a fusion that spans two scopes counts under
both). None where the program has no such scope."""

from benchmarks.lib import scoped

SCOPE = r"/optim/"


def read(results):
    return scoped.scope_ms_per_step(results, SCOPE)

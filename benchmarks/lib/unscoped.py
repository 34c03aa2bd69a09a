"""Device time that no scope of the program names.

An op's scope (`xtrace.scope_of`) is the `op_name`s of the instructions it
holds, joined by " | ". One `op_name` names nothing of the program where,
with its last component (the primitive) and JAX's own wrappers (`_wrapper`)
taken off, nothing is left: `jit(step)/jvp(SlowFast)/reduce_window_max` (a
max-pool called in the model's own body), a bare name such as
`ragged-dot-none` or `copy-done` (what the trace carries where the compiled
text has no instruction of that name), an argument's name, or nothing at
all. An op is unscoped where every part of its scope names nothing: a fusion
that holds any scoped instruction counts under that scope (`lib/scoped.py`),
not here.
"""

import re

# a transformation or a call: jit(step), jvp(SlowFast), transpose(jvp(X3D))
_CALL = re.compile(r"^[\w.<>\-]+\(.*\)$")
_BRANCH = re.compile(r"^branch_\d+_fun$")
WRAPPERS = frozenset({"checkpoint", "remat", "rematted_computation",
                      "closed_call", "cond", "while", "body", "scan", "pjit",
                      "shard_map"})


def _wrapper(component):
    """JAX's own: a transformation, a control-flow body, a remat, an einsum's
    subscripts (`bhrd,bkhd->bhrk`)."""
    return (component in WRAPPERS or "->" in component
            or bool(_CALL.match(component)) or bool(_BRANCH.match(component)))


def names_nothing(op_name):
    """True where one `op_name` holds no scope of the program."""
    parts = [p for p in op_name.strip().split("/") if p]
    return all(_wrapper(p) for p in parts[:-1])


def is_unscoped(scope):
    """True where no part of an op's scope names a scope of the program."""
    return all(names_nothing(part) for part in str(scope).split(" | "))


def seconds(ops):
    """Self seconds of the unscoped ops of `xtrace.reduce`'s `ops`."""
    return sum(s for scope, _n, _c, s in ops if is_unscoped(scope))


def ms_per_step(results):
    """Device milliseconds a traced step of the unscoped ops. None where the
    trace has no whole step or every op is scoped."""
    trace = results["trace"]
    if not trace or not trace["traced_steps"]:
        return None
    found = seconds(trace["ops"])
    if found <= 0:
        return None
    return 1e3 * found / trace["traced_steps"]

"""`mamba_ms_per_step` (layer: mixer: Mamba-2).
Device milliseconds a step of every op of the compiled step under the Mamba-2
mixers' scope (input projection, conv, the state-space scan, the gated norm
and output projection), forward, rematerialised forward and backward
(lib/scoped.py). None where the program has no such layer."""

from benchmarks.lib import scoped

SCOPE = r"/mamba/"


def read(results):
    return scoped.scope_ms_per_step(results, SCOPE)

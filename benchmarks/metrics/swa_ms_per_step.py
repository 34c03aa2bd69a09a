"""`swa_ms_per_step` (layer: mixer: sliding-window attention).
Device milliseconds a step of every op of the compiled step under the
windowed layers' scope (projections, rotary, core, output), forward,
rematerialised forward and backward (lib/scoped.py). None where the program
has no such layer."""

from benchmarks.lib import scoped

SCOPE = r"/swa/"


def read(results):
    return scoped.scope_ms_per_step(results, SCOPE)

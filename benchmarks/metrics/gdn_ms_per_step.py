"""`gdn_ms_per_step` (layer: mixer: Gated DeltaNet).
Device milliseconds a step of every op of the compiled step under the layer's
scope, forward, rematerialised forward and backward (lib/scoped.py)."""

from benchmarks.lib import scoped

SCOPE = r"/gdn/"


def read(results):
    return scoped.scope_ms_per_step(results, SCOPE)

"""`unscoped_ms_per_step` (layer: device). Device milliseconds a step of the
ops of the compiled step that no scope of the program names (lib/unscoped.py):
what no scope reader sees. None where every op is scoped."""

from benchmarks.lib import unscoped


def read(results):
    return unscoped.ms_per_step(results)

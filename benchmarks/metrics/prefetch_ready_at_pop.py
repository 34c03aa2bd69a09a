"""`prefetch_ready_at_pop` (layer: input). Mean, over the measured window's
steps, of the device batches the prefetch ring held when the loop asked for
the next one (`DevicePrefetcher.ready()`, in `fit()`'s per-iteration
records): `device_prefetch_depth` (2) is a ring that is always full, 0 a loop
that always waits."""

from benchmarks.lib import step_records


def read(results):
    records = step_records.of_window(results)
    if not records:
        return None
    return sum(r["ready"] for r in records) / len(records)

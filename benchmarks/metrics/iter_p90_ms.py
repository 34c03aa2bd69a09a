"""`iter_p90_ms` (layer: train loop). 90th percentile of the `iter` span over
the measured window's steps, from `fit()`'s per-iteration records. A 90th
percentile needs ten samples beyond it: under `MIN_RECORDS` records the
reader returns `None`. The count it used goes to standard error."""

import sys

from benchmarks.lib import step_records, xtrace

MIN_RECORDS = 100


def read(results):
    records = step_records.of_window(results)
    if not records:
        return None
    print(f"iter_p90_ms: {len(records)} records", file=sys.stderr)
    if len(records) < MIN_RECORDS:
        return None
    return xtrace.percentile([1000.0 * r["iter"] for r in records], 90.0)

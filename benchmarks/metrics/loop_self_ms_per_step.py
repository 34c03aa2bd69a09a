"""`loop_self_ms_per_step` (layer: train loop). Self time of `fit()`'s `iter`
span (one span an iteration, from asking for the next batch to the bottom of
the loop body): its duration less what its children `input_wait`, `step`,
`log` cover, all four as `fit()`'s per-iteration records hold them. The loop's
own Python, and whatever took the interpreter from it.

The median over the measured window's records, not the window's
`obs/iter_self_s` sum over its steps: the harness stands inside `fit()`'s
`next()` (its prefetcher wrapper), so what it does there counts as the
iteration's own time, and in a traced run one iteration holds its whole
`stop_trace` (113 s against a millisecond a step; PERF.md, Findings, PR 25).
A program that keeps no records (before the span existed) reads nothing."""

from benchmarks.lib import step_records, xtrace


def read(results):
    records = step_records.of_window(results)
    if not records:
        return None
    return xtrace.median([1000.0 * (r["iter"] - r["input_wait"] - r["step"]
                                    - r["log"]) for r in records])

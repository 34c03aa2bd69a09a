"""`fit()`'s per-iteration records, for the readers that take them.

`Trainer.fit()` returns `step_records`: one dict an iteration (`gstep`,
`t0_ns`, the seconds of the `iter` span and of its children `input_wait`,
`step`, `log`, and `ready`, the prefetch ring's fill when the loop asked for
the batch). The measured window ends with `fit()`'s last step, so its records
are the last `results["steps"]` of them. A program that returns no records
(before they existed) gives an empty list, and the readers return `None`.
"""


def of_window(results):
    """The records of the measured window's steps, oldest first."""
    records = (results.get("fit") or {}).get("step_records") or []
    steps = results.get("steps") or 0
    return list(records[-steps:]) if steps else []

"""The readers of the loop's own spans and records (`loop_self_ms_per_step`,
`log_ms_per_step`, `iter_p90_ms`, `prefetch_ready_at_pop`) on hand-made
`results`, and their entries in `BENCHMARK.json`."""

import json
import os

import pytest

from benchmarks.lib.spec import Spec, metric_module, quantity

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LOOP = "train loop: trainer/loop.py Trainer.fit"
INPUT = "input: data/pipeline.py, data/device_prefetch.py"
NEW = {  # quantity: (unit, better, source, layer)
    "loop_self_ms_per_step": ("ms", "lower", "program_span", LOOP),
    "log_ms_per_step": ("ms", "lower", "program_span", LOOP),
    "prefetch_ready_at_pop": ("batches", "higher", "program_counter", INPUT),
}
# a reader with no entry yet: a traced window holds 13-23 records, under its
# 100-record rule, so the entries wait for a window that can feed it
UNLISTED = ("iter_p90_ms",)


def record(gstep, iter_s, ready=2):
    return {"gstep": gstep, "t0_ns": 10 ** 18 + gstep, "iter": iter_s,
            "input_wait": 0.001, "step": 0.02, "log": 0.03, "ready": ready}


def results(steps, records, **spans):
    return {"steps": steps, "spans": spans,
            "fit": {"steps": len(records), "step_records": records}}


def test_log_reader_divides_the_windows_sum_by_its_steps():
    res = results(50, [], iter=5.5, iter_self=0.25, log=4.0, step=1.0)
    assert metric_module("log_ms_per_step.device_paced").read(res) == 80.0


def test_loop_self_is_the_median_iterations_own_time():
    # 51 ms of children in every record; the loop's own 2 ms, but for the
    # iteration in which the harness stopped its profiler (113 s)
    records = [record(g, 0.053) for g in range(40)] + [record(40, 113.0)]
    res = results(21, records, iter_self=113.1, log=0.6)
    assert metric_module("loop_self_ms_per_step.host_paced").read(res) \
        == pytest.approx(2.0)
    # the records are the span's own durations: no window sum is needed
    assert metric_module("loop_self_ms_per_step").read(
        results(21, records)) == pytest.approx(2.0)


@pytest.mark.parametrize("res", [
    results(50, [], step=1.0, input_wait=0.2),  # a program without the spans
    results(0, [], iter_self=0.25, log=4.0),    # a window of no step
    results(0, [record(g, 0.2) for g in range(9)], step=1.0),  # the same
    results(5, [], iter_self=0.25),             # the span, but no record
])
def test_span_readers_return_nothing_where_there_is_no_span(res):
    assert metric_module("loop_self_ms_per_step").read(res) is None
    if "log" not in res["spans"] or not res["steps"]:
        assert metric_module("log_ms_per_step").read(res) is None


def test_record_readers_take_the_windows_last_steps():
    # 30 warm-up iterations of 9 s each, then a window of 120 steps whose
    # iterations take 1..120 ms; the ring was full but for every sixth ask
    warm = [record(g, 9.0, ready=0) for g in range(30)]
    window = [record(30 + i, (i + 1) / 1000.0, ready=0 if i % 6 == 0 else 2)
              for i in range(120)]
    res = results(120, warm + window)
    # linear between the ranks: position 119 * 0.9 = 107.1 -> 108.1 ms
    assert metric_module("iter_p90_ms.host_paced").read(res) == pytest.approx(108.1)
    assert metric_module("prefetch_ready_at_pop.host_paced").read(res) \
        == pytest.approx(2.0 * 100 / 120)


def test_iter_p90_needs_a_hundred_records_and_says_how_many_it_had(capsys):
    reader = metric_module("iter_p90_ms.device_paced")
    few = results(75, [record(g, 0.2) for g in range(105)])
    assert reader.read(few) is None
    assert "iter_p90_ms: 75 records" in capsys.readouterr().err
    enough = results(100, [record(g, 0.2) for g in range(105)])
    assert reader.read(enough) == pytest.approx(200.0)
    assert "iter_p90_ms: 100 records" in capsys.readouterr().err
    # the mean needs no such count
    assert metric_module("prefetch_ready_at_pop").read(few) == 2.0


@pytest.mark.parametrize("res", [
    {"steps": 120, "spans": {}, "fit": {"steps": 150}},  # no records yet
    {"steps": 120, "spans": {}, "fit": {"step_records": []}},
    {"steps": 120, "spans": {}, "fit": None},
    results(0, [record(0, 0.2)]),
])
def test_record_readers_return_nothing_where_there_is_no_record(res, capsys):
    assert metric_module("iter_p90_ms").read(res) is None
    assert metric_module("prefetch_ready_at_pop").read(res) is None
    assert capsys.readouterr().err == ""


def test_new_entries_have_reader_workloads_and_moves():
    """Each of PR 25's quantities has a reader and, in every regime, an entry
    with the unit, direction, source, layer and `moves` it names, listing
    that regime's cells. Which cells a regime has is `BENCHMARK.json`'s own
    `clips_per_s_per_chip.<regime>` list: a cell moves when a `benchmark` PR
    finds that its spread needs the other bound, and entries are appended."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    layers = {m["layer"] for m in doc["per_layer"]
              if quantity(m["name"]) not in NEW}
    regimes = {m["name"].split(".", 1)[1]: m["workloads"]
               for m in doc["end_to_end"]
               if quantity(m["name"]) == "clips_per_s_per_chip"}
    assert set(regimes) == {"device_paced", "host_paced"}
    spec = Spec(ROOT)
    for q, (unit, better, source, layer) in NEW.items():
        assert callable(metric_module(q).read)
        assert layer in layers  # a layer the benchmark already names
        for regime, cells in regimes.items():
            m = dict(by_name[f"{q}.{regime}"])
            listed = m.pop("workloads")
            assert m == {"name": f"{q}.{regime}", "unit": unit,
                         "better": better, "source": source, "layer": layer,
                         "moves": f"clips_per_s_per_chip.{regime}"}
            # cells of its regime, and only those; a cell that gives the
            # reader nothing to read (`prefetch_ready_at_pop` where nothing
            # waits on the prefetcher) is left out
            assert listed and set(listed) <= set(cells)
            for cell in listed:
                assert m["name"] in spec.metric_names("per_layer", cell)
        # every cell whose loop runs is read by the loop's two quantities
        if layer == LOOP:
            assert all(set(by_name[f"{q}.{r}"]["workloads"]) == set(cells)
                       for r, cells in regimes.items())
    names = [m["name"] for m in doc["per_layer"]]
    for q in UNLISTED:
        assert callable(metric_module(q).read)
        assert q not in {quantity(n) for n in names}

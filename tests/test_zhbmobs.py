"""pva-tpu-hbm observability tests: the device-memory ledger (register/
release parity, the unattributed residual, estimate-vs-measured
provenance, the watermark edge trigger, measured-bytes budget admission),
the bounded metrics history (ring eviction, label-summed series, rate/
ratio/ewma reads), multi-window SLO burn-rate alerts (truth table +
no-flap hysteresis), the on-demand profiler capture's atomic publish,
the `ledger-discipline` lint rule, the doctor snapshots, and the
two-family canary comparison.

Late-alphabet name on purpose: tier-1 is timeout-bound and these run
after the cheap early families (the test_zobs/test_zcontrol rationale).
Everything host-side: fake `stats_fn`s stand in for device memory_stats,
synthetic clocks drive the alert windows, and the only jax use is the
monkeypatched profiler seam.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.analysis import lint_source
from pytorchvideo_accelerate_tpu.fleet.control import ModelBudget
from pytorchvideo_accelerate_tpu.obs import alerts as obs_alerts
from pytorchvideo_accelerate_tpu.obs import history as obs_history
from pytorchvideo_accelerate_tpu.obs import memory as obs_memory
from pytorchvideo_accelerate_tpu.obs import profiler as obs_profiler
from pytorchvideo_accelerate_tpu.obs.registry import Registry

STREAM_HOT = "pytorchvideo_accelerate_tpu/streaming/engine.py"
COLD = "pytorchvideo_accelerate_tpu/data/manifest.py"


def _stats(in_use=0, peak=0, limit=10**9):
    return {"bytes_in_use": int(in_use), "peak_bytes_in_use": int(peak),
            "bytes_limit": int(limit)}


class _Recorder:
    def __init__(self):
        self.warns = []
        self.records = []

    def warn(self, msg, **kw):
        self.warns.append((msg, kw))

    def record(self, *a, **kw):
        self.records.append((a, kw))


@pytest.fixture(autouse=True)
def _disarm_module_defaults():
    """Every test leaves the process-default singletons disarmed — the
    arming discipline other suites rely on."""
    yield
    obs_memory.configure(enabled=False)
    obs_history.configure(enabled=False)
    obs_alerts.configure(enabled=False)
    obs_profiler.configure(enabled=False)


# --- memory ledger ----------------------------------------------------------

def test_ledger_register_release_parity_with_array_bytes():
    led = obs_memory.MemoryLedger(registry=Registry(),
                                  stats_fn=lambda: None)
    a = np.zeros((4, 16, 16, 3), np.float32)
    b = np.zeros((8, 64), np.int8)
    led.register("pool", a.nbytes)
    led.register("pool", b.nbytes)  # accumulates, not replaces
    assert led.component_bytes("pool") == a.nbytes + b.nbytes
    assert led.attributed_bytes() == a.nbytes + b.nbytes
    led.release("pool", b.nbytes)
    assert led.component_bytes("pool") == a.nbytes
    led.release("pool")  # nbytes=None clears the component
    assert led.component_bytes("pool") == 0
    # a double release is an accounting bug, not a negative gauge
    led.register("x", 100)
    led.release("x", 300)
    assert led.component_bytes("x") == 0
    # tree_nbytes walks nested containers of arrays
    tree = {"params": {"w": a, "b": b}, "opt": [a]}
    assert obs_memory.tree_nbytes(tree) == 2 * a.nbytes + b.nbytes


def test_ledger_residual_and_provenance_on_a_measured_host():
    led = obs_memory.MemoryLedger(
        registry=Registry(),
        stats_fn=lambda: _stats(in_use=100 * 10**6, peak=120 * 10**6))
    led.register("train_state", 60 * 10**6)
    assert led.source() == "measured"
    assert led.measured_bytes("train_state") == 60 * 10**6
    # a zero-byte "measurement" is an unregistered component, not None
    assert led.measured_bytes("never_registered") == 0
    assert led.unattributed_bytes() == 40 * 10**6
    assert led.attributed_frac() == pytest.approx(0.6)
    assert led.peak_bytes() == 120 * 10**6  # the backend's own peak
    snap = led.snapshot()
    assert snap["source"] == "measured"
    assert snap["bytes_in_use"] == 100 * 10**6
    assert snap["unattributed_bytes"] == 40 * 10**6


def test_ledger_estimate_host_never_fakes_device_bytes():
    led = obs_memory.MemoryLedger(registry=Registry(),
                                  stats_fn=lambda: None)
    led.register("rings", 50 * 10**6)
    assert led.source() == "estimate"
    # admission paths get None and must fall back to declared figures
    assert led.measured_bytes("rings") is None
    # no backend truth to diff against: the residual/frac read clean
    assert led.unattributed_bytes() == 0
    assert led.attributed_frac() == 1.0
    # peak on an estimate host is the peak ATTRIBUTED sum, held across
    # a release (a high-water mark, not the current level)
    led.register("rings", 30 * 10**6)
    led.release("rings", 60 * 10**6)
    assert led.peak_bytes() == 80 * 10**6
    assert led.snapshot()["source"] == "estimate"


def test_ledger_drift_is_a_metric_not_a_shrug():
    led = obs_memory.MemoryLedger(registry=Registry(),
                                  stats_fn=lambda: None, drift_tol=0.25)
    # padding/dtype promotion: measured 130 vs declared 100 -> 30% drift
    led.register("stream_rings:eng", 130 * 10**6, declared=100 * 10**6)
    led.register("honest", 101, declared=100)
    drift = led.drift()
    assert drift["stream_rings:eng"] == pytest.approx(0.30)
    assert drift["honest"] == pytest.approx(0.01)
    assert led.snapshot()["drift_over_tol"] == ["stream_rings:eng"]


def test_ledger_watermark_warns_edge_triggered():
    stats = _stats(in_use=10, peak=10, limit=100)
    rec = _Recorder()
    led = obs_memory.MemoryLedger(registry=Registry(), recorder=rec,
                                  watermark_frac=0.9,
                                  stats_fn=lambda: dict(stats))
    led.register("c", 10)
    assert rec.warns == []
    stats["bytes_in_use"] = 95  # cross the watermark
    led.register("c", 10)
    assert len(rec.warns) == 1 and "watermark" in rec.warns[0][0]
    led.register("c", 10)  # still over: edge trigger stays quiet
    assert len(rec.warns) == 1
    stats["bytes_in_use"] = 50  # recover...
    led.register("c", 10)
    stats["bytes_in_use"] = 96  # ...and cross again: re-armed
    led.register("c", 10)
    assert len(rec.warns) == 2


def test_model_budget_measured_bytes_flip_declared_admission():
    """The budget-lies probe: a
    family that under-declares is admitted on declared figures, refused
    the moment the ledger can measure its real bytes."""
    obs_memory.configure(
        registry=Registry(),
        stats_fn=lambda: _stats(in_use=200 * 10**6, peak=220 * 10**6))
    budget = ModelBudget(100.0)
    budget.register("honest", 60.0)
    budget.register("liar", 10.0)  # declares 10 MB -> 70 < 100: admitted
    assert budget.over_budget() == []
    # honest never registered engine bytes: the zero-byte trap must keep
    # it on the declared figure, not admit it for free
    assert budget.footprint_mb("honest") == 60.0
    assert budget.footprint_source("honest") == "declared"
    # the liar's engine actually pins 90 MB on device
    obs_memory.register("model_weights:liar", 90 * 10**6,
                        declared=10 * 10**6)
    assert budget.footprint_mb("liar") == pytest.approx(90.0)
    assert budget.footprint_source("liar") == "measured"
    assert budget.over_budget() == ["liar"]  # 60 + 90 > 100
    # the lie itself is a gauge
    led = obs_memory.get_ledger()
    assert led.drift()["model_weights:liar"] == pytest.approx(8.0)


def test_module_level_ledger_disarmed_is_a_noop():
    obs_memory.configure(enabled=False)
    assert obs_memory.get_ledger() is None
    # allocation-site hooks: one global read, no effect, no raise
    obs_memory.register("anything", 123)
    obs_memory.release("anything")
    led = obs_memory.configure(registry=Registry(), stats_fn=lambda: None)
    obs_memory.register("c", 7)
    assert led.component_bytes("c") == 7


# --- metrics history --------------------------------------------------------

def test_history_ring_evicts_oldest_past_capacity():
    reg = Registry()
    g = reg.gauge("pva_probe", "t")
    hist = obs_history.MetricsHistory(registry=reg, capacity=4)
    for i in range(7):
        g.set(float(i))
        hist.tick(now=1000.0 + i)
    assert hist.occupancy() == 4
    assert hist.total_ticks() == 7
    pts = hist.series("pva_probe")
    # oldest-first, the first three ticks evicted
    assert [v for _, v in pts] == [3.0, 4.0, 5.0, 6.0]
    assert [ts for ts, _ in pts] == [1003.0, 1004.0, 1005.0, 1006.0]
    assert hist.latest("pva_probe") == 6.0
    # trailing-window restriction
    assert [v for _, v in hist.series("pva_probe", window_s=2.0,
                                      now=1006.0)] == [4.0, 5.0, 6.0]
    with pytest.raises(ValueError):
        obs_history.MetricsHistory(registry=reg, capacity=1)


def test_history_bare_key_sums_label_variants():
    reg = Registry()
    c = reg.counter("pva_serving_shed_total", "t", labelnames=("state",))
    hist = obs_history.MetricsHistory(registry=reg, capacity=16)
    c.inc(state="degraded")
    hist.tick(now=1.0)
    c.inc(state="draining")
    c.inc(state="degraded")
    hist.tick(now=2.0)
    # a rule over the bare name sees every shed cause summed per tick
    assert [v for _, v in hist.series("pva_serving_shed_total")] \
        == [1.0, 3.0]


def test_history_rate_ratio_and_ewma_reads():
    reg = Registry()
    num = reg.counter("pva_errs_total", "t")
    den = reg.counter("pva_reqs_total", "t")
    hist = obs_history.MetricsHistory(registry=reg, capacity=32)
    for i in range(5):
        den.inc(10)
        if i >= 3:
            num.inc(2)
        hist.tick(now=100.0 + i)
    # 40 requests over 4s between first and last tick
    assert hist.rate("pva_reqs_total", window_s=60.0,
                     now=104.0) == pytest.approx(10.0)
    # an untouched counter emits no sample, so the errs series starts at
    # its first increment (2): delta(errs)/delta(reqs) = 2/40
    assert hist.ratio("pva_errs_total", "pva_reqs_total", window_s=60.0,
                      now=104.0) == pytest.approx(0.05)
    assert hist.ewma("pva_reqs_total", halflife_s=1.0) is not None
    # a single point yields no rate; an absent key yields None
    assert hist.rate("pva_reqs_total", window_s=0.5, now=104.0) is None
    assert hist.window_mean("pva_missing", 60.0, now=104.0) is None


def test_history_to_json_is_the_get_history_payload():
    reg = Registry()
    g = reg.gauge("pva_probe", "t")
    hist = obs_history.MetricsHistory(registry=reg, capacity=8)
    for i in range(3):
        g.set(float(i))
        hist.tick(now=10.0 + i)
    out = hist.to_json(keys=["pva_probe"])
    assert out["occupancy"] == 3 and out["capacity"] == 8
    assert out["series"]["pva_probe"] == [[10.0, 0.0], [11.0, 1.0],
                                          [12.0, 2.0]]
    json.dumps(out)  # the HTTP handler serializes it verbatim


# --- burn-rate alerts -------------------------------------------------------

def _gauge_engine(slo=100.0, **rule_kw):
    reg = Registry()
    g = reg.gauge("pva_probe_p99_ms", "t")
    rule = obs_alerts.AlertRule(
        name="p99_burn", kind="gauge", key="pva_probe_p99_ms",
        objective=slo, fast_s=2.0, slow_s=8.0, **rule_kw)
    eng = obs_alerts.AlertEngine(
        obs_history.MetricsHistory(registry=reg, capacity=64),
        [rule], registry=reg)
    return reg, g, eng


def test_alert_rule_validation():
    with pytest.raises(ValueError, match="kind"):
        obs_alerts.AlertRule(name="r", kind="nope", key="k", objective=1.0)
    with pytest.raises(ValueError, match="fast"):
        obs_alerts.AlertRule(name="r", key="k", objective=1.0,
                             fast_s=60.0, slow_s=60.0)
    with pytest.raises(ValueError, match="flap"):
        obs_alerts.AlertRule(name="r", key="k", objective=1.0,
                             burn=1.0, clear_burn=1.1)
    with pytest.raises(ValueError, match="objective"):
        obs_alerts.AlertRule(name="r", key="k", objective=0.0)
    with pytest.raises(ValueError, match="duplicate"):
        reg = Registry()
        rules = [obs_alerts.AlertRule(name="r", key="k", objective=1.0)] * 2
        obs_alerts.AlertEngine(
            obs_history.MetricsHistory(registry=reg, capacity=8),
            rules, registry=reg)


def test_alert_fires_only_when_fast_and_slow_both_burn():
    reg, g, eng = _gauge_engine(slo=100.0)
    t = 1000.0
    g.set(25.0)
    for _ in range(10):
        eng.tick(now=t)
        t += 1.0
    assert eng.active() == [] and eng.fires("p99_burn") == 0
    g.set(400.0)
    eng.tick(now=t)
    t += 1.0
    # the fast window burns immediately; the slow window still holds the
    # calm ticks — a blip must NOT page
    st = eng.snapshot()["rules"]["p99_burn"]
    assert st["last_burn"]["fast"] >= 1.0
    assert st["last_burn"]["slow"] < 1.0
    assert eng.active() == []
    for _ in range(8):  # sustain the burn: the slow window fills
        eng.tick(now=t)
        t += 1.0
    assert eng.active() == ["p99_burn"]
    assert eng.fires("p99_burn") == 1
    # staying burning is ONE fire, however long it lasts
    for _ in range(5):
        eng.tick(now=t)
        t += 1.0
    assert eng.fires("p99_burn") == 1
    assert reg.scrape("pva_alert")['pva_alert_active{rule="p99_burn"}'] \
        == 1.0


def test_alert_clears_with_hysteresis_not_flap():
    reg, g, eng = _gauge_engine(slo=100.0, hold_clear=2)
    t = 1000.0
    g.set(400.0)
    for _ in range(10):
        eng.tick(now=t)
        t += 1.0
    assert eng.active() == ["p99_burn"]
    g.set(25.0)
    eng.tick(now=t)
    # one calm tick is not a clear: the slow window still burns and the
    # clear must hold for hold_clear consecutive ticks
    assert eng.active() == ["p99_burn"]
    for _ in range(12):
        t += 1.0
        eng.tick(now=t)
    assert eng.active() == []
    assert eng.fires("p99_burn") == 1  # fire/clear is one cycle, no flap
    snap = eng.snapshot()["rules"]["p99_burn"]
    assert snap["active"] is False and snap["cleared_at"] is not None
    scr = reg.scrape("pva_alert")
    assert scr['pva_alert_active{rule="p99_burn"}'] == 0.0
    assert scr['pva_alert_transitions_total{rule="p99_burn",'
               'to="firing"}'] == 1.0
    assert scr['pva_alert_transitions_total{rule="p99_burn",'
               'to="clear"}'] == 1.0


def test_alert_ratio_rule_reads_counter_pairs():
    reg = Registry()
    errs = reg.counter("pva_serving_errors_total", "t")
    reqs = reg.counter("pva_serving_requests_total", "t")
    rule = obs_alerts.AlertRule(
        name="error_burn", kind="ratio",
        num="pva_serving_errors_total", den="pva_serving_requests_total",
        objective=0.01, fast_s=2.0, slow_s=8.0)
    eng = obs_alerts.AlertEngine(
        obs_history.MetricsHistory(registry=reg, capacity=64),
        [rule], registry=reg)
    t = 0.0
    for _ in range(12):  # healthy: 0 errors
        reqs.inc(100)
        eng.tick(now=t)
        t += 1.0
    assert eng.active() == []
    for _ in range(10):  # 5% errors against a 1% objective
        reqs.inc(100)
        errs.inc(5)
        eng.tick(now=t)
        t += 1.0
    assert eng.active() == ["error_burn"]


def test_default_rules_cover_the_serving_slo_triple():
    rules = {r.name: r for r in obs_alerts.default_rules()}
    assert set(rules) == {"serve_latency_burn", "shed_burn", "error_burn"}
    for r in rules.values():
        assert r.kind == "ratio"
        assert r.num.startswith("pva_serving_")
        assert r.den.startswith("pva_serving_")
        assert r.fast_s < r.slow_s


# --- profiler capture -------------------------------------------------------

def test_profiler_parse_steps():
    assert obs_profiler.parse_steps("") is None
    assert obs_profiler.parse_steps("5..10") == (5, 10)
    for bad in ("5", "10..5", "-1..4", "3..3", "a..b"):
        with pytest.raises(ValueError):
            obs_profiler.parse_steps(bad)


@pytest.fixture()
def fake_jax_profiler(monkeypatch, tmp_path):
    """Stub the jax.profiler seam: start writes a marker file into the
    trace dir, stop is recorded — the atomic-publish logic under test is
    the module's, not XLA's."""
    import jax

    state = {"dir": None, "stops": 0}

    def start_trace(d, profiler_options=None):
        # the one capture path arms a trace that can be read: host
        # annotations only, no Python tracer, no HLO proto
        assert profiler_options.python_tracer_level == 0
        assert profiler_options.host_tracer_level == 1
        assert profiler_options.enable_hlo_proto is False
        state["dir"] = d
        with open(os.path.join(d, "trace.marker"), "w") as f:
            f.write("x")

    def stop_trace():
        state["stops"] += 1

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    return state


def test_profiler_atomic_publish_and_singleton(fake_jax_profiler, tmp_path):
    prof = obs_profiler.ProfilerCapture(str(tmp_path), recorder=_Recorder())
    assert prof.start(tag="t1") is True
    assert prof.busy
    # mid-capture: only the dot-prefixed temp dir exists — a reader can
    # never mistake a partial trace for a complete one
    assert os.path.isdir(tmp_path / ".profile_tmp_t1")
    assert not os.path.isdir(tmp_path / "profile_t1")
    assert prof.start(tag="t2") is False  # one window at a time
    final = prof.stop()
    assert final == str(tmp_path / "profile_t1")
    assert os.path.isfile(tmp_path / "profile_t1" / "trace.marker")
    assert not os.path.isdir(tmp_path / ".profile_tmp_t1")
    assert prof.snapshot()["captures"] == 1
    assert prof.stop() is None  # nothing open


def test_profiler_capture_for_background_stop(fake_jax_profiler, tmp_path):
    prof = obs_profiler.ProfilerCapture(str(tmp_path))
    tag = prof.capture_for(0.05, tag="bg")
    assert tag == "bg"
    assert prof.capture_for(0.05) is None  # busy
    prof.join(timeout=10.0)
    assert os.path.isdir(tmp_path / "profile_bg")
    assert not prof.busy


def test_profiler_backend_refusal_is_recorded_not_raised(monkeypatch,
                                                         tmp_path):
    import jax

    def boom(d, profiler_options=None):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    rec = _Recorder()
    prof = obs_profiler.ProfilerCapture(str(tmp_path), recorder=rec)
    assert prof.start(tag="x") is False
    assert not prof.busy
    assert any("refused" in m for m, _ in rec.warns)
    assert not os.path.isdir(tmp_path / ".profile_tmp_x")


# --- doctor snapshots -------------------------------------------------------

def test_doctor_memory_and_alerts_snapshots():
    from pytorchvideo_accelerate_tpu.utils.device_doctor import (
        alerts_snapshot,
        memory_snapshot,
    )

    obs_memory.configure(enabled=False)
    obs_alerts.configure(enabled=False)
    obs_history.configure(enabled=False)
    assert memory_snapshot()["armed"] is False
    assert alerts_snapshot()["armed"] is False

    obs_memory.configure(registry=Registry(), stats_fn=lambda: None)
    obs_memory.register("train_state", 42)
    m = memory_snapshot()
    assert m["armed"] is True
    assert m["components"] == {"train_state": 42}
    assert m["source"] == "estimate"

    reg = Registry()
    hist = obs_history.configure(registry=reg, capacity=16)
    obs_alerts.configure(history=hist,
                         rules=obs_alerts.default_rules(), registry=reg)
    obs_alerts.get_engine().tick(now=1.0)
    a = alerts_snapshot()
    assert a["armed"] is True
    assert set(a["rules"]) == {"serve_latency_burn", "shed_burn",
                               "error_burn"}
    assert a["active"] == []
    assert a["history"]["occupancy"] == 1


# --- the ledger-discipline lint rule ----------------------------------------

def test_ledger_discipline_fires_on_offledger_allocation():
    src = ("import jax\n"
           "import jax.numpy as jnp\n"
           "def build_pool(self, shape):\n"
           "    ring = jnp.zeros(shape)\n"
           "    return jax.device_put(ring, None)\n")
    found = lint_source(src, STREAM_HOT)
    assert [f.rule for f in found] == ["ledger-discipline"] * 2
    assert [f.line for f in found] == [4, 5]
    # cold modules allocate freely — the rule patrols the ledger's
    # documented hot modules only
    assert lint_source(src, COLD) == []


def test_ledger_discipline_quiet_with_register_in_scope():
    src = ("import jax.numpy as jnp\n"
           "from pytorchvideo_accelerate_tpu.obs import memory\n"
           "def build_pool(self, shape):\n"
           "    ring = jnp.zeros(shape)\n"
           "    memory.register('stream_rings:x', ring.nbytes)\n"
           "    return ring\n")
    assert lint_source(src, STREAM_HOT) == []
    # an injected ledger object satisfies the rule too
    src2 = ("import jax.numpy as jnp\n"
            "def build(self, shape):\n"
            "    ring = jnp.zeros(shape)\n"
            "    self._ledger.register('c', ring.nbytes)\n"
            "    return ring\n")
    assert lint_source(src2, STREAM_HOT) == []


def test_ledger_discipline_is_alias_proof():
    src = ("from jax import device_put as dp\n"
           "import jax.numpy as weird\n"
           "def move(self, arr):\n"
           "    a = dp(arr)\n"
           "    b = weird.empty((4,))\n"
           "    return a, b\n")
    found = lint_source(src, STREAM_HOT)
    assert [f.rule for f in found] == ["ledger-discipline"] * 2
    # numpy.zeros is host memory, never flagged; jax.numpy tails need a
    # jax head (a local zeros() helper stays quiet)
    quiet = ("import numpy as np\n"
             "def host_side(self, shape):\n"
             "    return np.zeros(shape)\n")
    assert lint_source(quiet, STREAM_HOT) == []


def test_ledger_discipline_suppression_carries_a_reason():
    src = ("import jax\n"
           "def _replicated(self, arr):\n"
           "    return jax.device_put(arr)  "
           "# pva: disable=ledger-discipline -- transient H2D helper\n")
    assert lint_source(src, STREAM_HOT) == []


# --- two-family canary comparison (pva-tpu-hbm satellite) -------------------

def test_canary_compares_per_family_and_strikes_only_the_regressor():
    """A regression that lives in ONE family must strike tagged with that
    family — and the clean family's windows must not dilute it (nor may
    a traffic-mix shift fake one). Single-family pools keep the original
    pool-level verdict shape (test_zcontrol covers that path)."""
    from pytorchvideo_accelerate_tpu.fleet.control import CanaryController
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.serving.stub import StubEngine

    def mk(name, model, forward_s):
        stats = ServingStats(window=128, registry=Registry())
        sched = Scheduler(StubEngine(tag=0.0, forward_s=forward_s),
                          stats=stats, max_queue=64, batch_max_wait_ms=1.0,
                          name=name)
        return LocalReplica(name, sched, stats=stats, model=model)

    # interleaved so fraction=0.5 canaries one replica of EACH family.
    # Forwards of 20 ms: at 2 ms the host's own scheduling jitter under a
    # loaded test run (a few ms) moved the clean family's 12-sample p50 or
    # p99 past the 50% threshold in about 2% of runs
    replicas = [mk("x3-0", "x3d_s", 0.02), mk("vm-0", "videomae_t", 0.02),
                mk("x3-1", "x3d_s", 0.02), mk("vm-1", "videomae_t", 0.02)]
    reg = Registry()
    pool = ReplicaPool(replicas, health_interval_s=0.05, registry=reg)
    router = Router(pool, registry=reg)
    try:
        cc = CanaryController(router, fraction=0.5, threshold=0.5,
                              rollback_after=2, prewarm=False)
        # the green is only slow for the videomae family
        entry = cc.start_rollout(
            lambda r: StubEngine(
                tag=9.0,
                forward_s=0.2 if r.model == "videomae_t" else 0.02),
            label="mixed")
        assert sorted(entry["canaries"]) == ["vm-0", "x3-0"]
        clip = {"video": np.zeros((2, 4, 4, 3), np.float32)}
        for fut in [router.submit(clip, model=m)
                    for m in ("x3d_s", "videomae_t") for _ in range(24)]:
            fut.result(timeout=30)
        verdict = cc.evaluate()
        fams = verdict["families"]
        assert set(fams) == {"x3d_s", "videomae_t"}
        assert fams["x3d_s"]["regressions"] == []
        assert any(k.startswith("serve_p")
                   for k in fams["videomae_t"]["regressions"])
        # pool-level strikes carry the family tag
        assert all(k.startswith("videomae_t:")
                   for k in verdict["regressions"])
        assert verdict["strikes"] == 1
        cc.rollback()
        assert all(r.scheduler.current_engine().tag == 0.0
                   for r in replicas)
    finally:
        router.close()


# --- HTTP round-trips (real socket: the test_zserving_http convention) ------

@pytest.mark.slow
def test_history_and_profile_http_round_trip(fake_jax_profiler, tmp_path):
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.serving.server import InferenceServer
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.serving.stub import StubEngine

    reg = Registry()
    stats = ServingStats(window=64, registry=reg)
    sched = Scheduler(StubEngine(), stats=stats, max_queue=32, name="hbm-t")
    hist = obs_history.configure(registry=reg, capacity=32)
    obs_alerts.configure(history=hist,
                         rules=obs_alerts.default_rules(), registry=reg)
    obs_profiler.configure(output_dir=str(tmp_path))
    srv = InferenceServer(StubEngine(), sched, stats, host="127.0.0.1",
                          port=0).start()
    try:
        host, port = srv.address
        base = f"http://{host}:{port}"
        obs_alerts.get_engine().tick()  # seed one scrape tick
        with urllib.request.urlopen(f"{base}/history?window_s=60",
                                    timeout=10) as r:
            body = json.loads(r.read())
        assert r.status == 200
        assert body["occupancy"] >= 1
        assert "series" in body
        assert body["alerts_active"] == []
        assert set(body["alerts"]) == {"serve_latency_burn", "shed_burn",
                                       "error_burn"}
        # profile: 202 pending, 409 while one is in flight
        req = urllib.request.Request(f"{base}/profile?seconds=30",
                                     data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            out = json.loads(r.read())
        assert r.status == 202 and out["capturing"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(f"{base}/profile?seconds=1",
                                       data=b"", method="POST"), timeout=10)
        assert ei.value.code == 409
        final = obs_profiler.get_profiler().stop()  # publish now
        assert final and os.path.isdir(final)
        # bad query is a 400, not a crash
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(f"{base}/profile?seconds=0",
                                       data=b"", method="POST"), timeout=10)
        assert ei.value.code == 400
        # disarmed surfaces say so: 503, distinguishable from "empty"
        obs_history.configure(enabled=False)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/history", timeout=10)
        assert ei.value.code == 503
        obs_profiler.configure(enabled=False)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(f"{base}/profile?seconds=1",
                                       data=b"", method="POST"), timeout=10)
        assert ei.value.code == 503
    finally:
        srv.close()

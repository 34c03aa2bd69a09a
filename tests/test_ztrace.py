"""Distributed tracing (obs/trace.py + obs/tracetool.py + the
trace-propagation lint rule): context propagation across thread / queue /
HTTP hops, seeded sampling determinism, the disarmed structural
zero-overhead contract, Chrome/Perfetto export round-trips, histogram
exemplar parity, and the multi-process merge.

Late-alphabet name on purpose: tier-1 is timeout-bound and these tests
must run after the cheap early families (same rationale as
test_zobs/test_zfleet)."""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.obs import trace, tracetool
from pytorchvideo_accelerate_tpu.obs.registry import (
    DEFAULT_BUCKETS,
    Registry,
    set_family_buckets,
)
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
from pytorchvideo_accelerate_tpu.serving.stub import StubEngine
from pytorchvideo_accelerate_tpu.utils.sync import make_thread

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tracer():
    t = trace.configure_tracing(1.0, seed=0, capacity=1024)
    yield t
    trace.disable_tracing()


# --- sampling ---------------------------------------------------------------

def test_sampling_deterministic_under_seed():
    a = trace.Tracer(sample_rate=0.5, seed=123)
    b = trace.Tracer(sample_rate=0.5, seed=123)
    da = [a.start("r") is not None for _ in range(64)]
    db = [b.start("r") is not None for _ in range(64)]
    assert da == db, "same seed must make identical sampling decisions"
    assert 0 < sum(da) < 64, "rate 0.5 should sample some, not all"
    # forced starts (debug probes) must NOT consume the decision stream
    c = trace.Tracer(sample_rate=0.5, seed=123)
    dc = []
    for _ in range(64):
        assert c.start("probe", force=True) is not None
        dc.append(c.start("r") is not None)
    assert dc == da
    stats = a.stats()
    assert stats["started"] == 64
    assert stats["sampled"] == sum(da)
    assert stats["sampled_frac"] == pytest.approx(sum(da) / 64, abs=1e-4)


def test_sampling_rate_one_and_bounds():
    t = trace.Tracer(sample_rate=1.0, seed=9)
    assert all(t.start("r") is not None for _ in range(8))
    with pytest.raises(ValueError):
        trace.Tracer(sample_rate=1.5)


# --- disarmed = structurally zero overhead ----------------------------------

def test_disarmed_structural_zero_overhead():
    trace.disable_tracing()
    assert trace.get_tracer() is None
    # every hot-path helper returns the SHARED no-op / None — no
    # allocation, no id generation, no lock
    assert trace.root("x", k=1) is trace.NOOP
    assert trace.span("x") is trace.NOOP
    assert trace.attach(None) is trace.NOOP
    assert trace.capture() is None
    assert trace.current_traceparent() is None
    assert trace.dump() is None
    assert trace.snapshot() == {"enabled": False}
    # the obs.span integration allocates no trace token while disarmed
    from pytorchvideo_accelerate_tpu import obs

    with obs.span("ztrace_unit") as s:
        assert s._trace is None
    obs.get_collector().drain()  # leave no residue for other tests


def test_configure_zero_rate_disarms():
    assert trace.configure_tracing(0.0) is None
    assert trace.get_tracer() is None


# --- traceparent ------------------------------------------------------------

def test_traceparent_roundtrip_and_garbage():
    ctx = trace.TraceContext("ab" * 16, "cd" * 8)
    hdr = trace.format_traceparent(ctx)
    back = trace.parse_traceparent(hdr)
    assert back is not None
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    unsampled = f"00-{'ab' * 16}-{'cd' * 8}-00"  # flag 00: head said no
    for bad in ("", "junk", "00-zz-xx-01", unsampled,
                "00-short-cdcdcdcdcdcdcdcd-01", None):
        assert trace.parse_traceparent(bad) is None


# --- propagation: thread hop ------------------------------------------------

def test_thread_handoff_capture_attach(tracer):
    h = tracer.start("root", force=True)
    with h:
        ctx = trace.capture()
        assert ctx is h.ctx

        def worker():
            with trace.attach(ctx):
                with trace.span("child_work"):
                    pass

        t = make_thread(target=worker, name="ztrace-worker", daemon=True)
        t.start()
        t.join(timeout=5.0)
    events = tracer.export()["traceEvents"]
    child = [e for e in events if e["name"] == "child_work"]
    assert child, f"worker span missing from {events}"
    assert child[0]["args"]["trace_id"] == h.ctx.trace_id
    assert child[0]["args"]["parent_id"] == h.ctx.span_id
    root = [e for e in events if e["name"] == "root"]
    assert root and "parent_id" not in root[0]["args"]


def test_obs_span_joins_active_trace(tracer):
    from pytorchvideo_accelerate_tpu import obs

    with tracer.start("step_root", force=True, gstep=7) as h:
        with obs.span("ztrace_step"):
            pass
    obs.get_collector().drain()
    events = tracer.export()["traceEvents"]
    spans = [e for e in events if e["name"] == "ztrace_step"]
    assert spans and spans[0]["args"]["trace_id"] == h.ctx.trace_id
    assert spans[0]["args"]["parent_id"] == h.ctx.span_id
    roots = [e for e in events if e["name"] == "step_root"]
    assert roots and roots[0]["args"]["gstep"] == 7


# --- propagation: queue hop (scheduler) + exemplar parity -------------------

def test_queue_handoff_through_scheduler(tracer):
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler

    stats = ServingStats(window=64)
    sched = Scheduler(StubEngine(forward_s=0.001, num_classes=4),
                      stats=stats, max_queue=64, name="ztrace")
    clip = {"video": np.zeros((2, 4, 4, 3), np.float32)}
    try:
        h = tracer.start("request", force=True)
        with h:
            fut = sched.submit(clip)
        fut.result(timeout=10.0)
    finally:
        sched.close()
    events = tracer.export()["traceEvents"]
    by_name = {e["name"]: e for e in events}
    # the context crossed the pending queue: the flush thread recorded the
    # scheduler wait AND the engine dispatch under the request's trace
    assert by_name["sched_wait"]["args"]["trace_id"] == h.ctx.trace_id
    assert by_name["device_dispatch"]["args"]["trace_id"] == h.ctx.trace_id
    # exemplar parity: the latency histogram's occupied bucket names this
    # very trace, and /stats' slowest list agrees
    exemplars = stats._h_latency.exemplars()
    assert exemplars, "traced completion must pin an exemplar"
    assert any(ex[0] == h.ctx.trace_id for ex in exemplars.values())
    slowest = stats.slowest_traces()
    assert slowest and slowest[0]["trace_id"] == h.ctx.trace_id


def test_exemplar_lands_in_top_bucket_and_render_flag():
    stats = ServingStats(window=32)
    stats.observe_batch(1, 2, [0.004], trace_ids=["slow-trace"])
    stats.observe_batch(1, 2, [0.0005], trace_ids=["fast-trace"])
    stats.observe_batch(1, 2, [0.0004], trace_ids=[None])  # untraced: no pin
    exemplars = stats._h_latency.exemplars()
    # 0.004 lands in le=0.005 (the highest OCCUPIED bucket here)
    assert exemplars["0.005"][0] == "slow-trace"
    assert exemplars["0.005"][1] == pytest.approx(0.004)
    top_occupied = max(exemplars, key=lambda le: float(le)
                       if le != "+Inf" else float("inf"))
    assert exemplars[top_occupied][0] == "slow-trace"
    assert stats.slowest_traces()[0]["trace_id"] == "slow-trace"
    # rendering: exemplars appear ONLY behind the flag; the default text
    # stays plain Prometheus v0.0.4 (parseable by the existing tests)
    flagged = stats.registry.render(exemplars=True)
    assert '# {trace_id="slow-trace"}' in flagged
    plain = stats.registry.render()
    assert "trace_id=" not in plain
    for line in plain.splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP", "# TYPE"))
        elif line:
            assert "#" not in line  # sample lines carry no exemplar suffix


def test_family_buckets_configurable():
    from pytorchvideo_accelerate_tpu.obs import registry as reg_mod

    set_family_buckets("ztrace_family_", (0.5, 1.0, 2.0))
    try:
        reg = Registry()
        h = reg.histogram("ztrace_family_latency")
        assert h.buckets == (0.5, 1.0, 2.0)
        other = reg.histogram("ztrace_other")
        assert other.buckets == DEFAULT_BUCKETS
        # explicit buckets always win over the family default
        explicit = reg.histogram("ztrace_family_explicit", buckets=(9.0,))
        assert explicit.buckets == (9.0,)
        # ServingStats picks up a family override for the serving latency
        set_family_buckets("pva_serving_request_latency_seconds",
                          (0.1, 0.2))
        st = ServingStats(window=8)
        assert st._h_latency.buckets == (0.1, 0.2)
        # ...and the explicit constructor arg beats it
        st2 = ServingStats(window=8, latency_buckets=(0.3, 0.6))
        assert st2._h_latency.buckets == (0.3, 0.6)
    finally:
        reg_mod._FAMILY_BUCKETS.pop("ztrace_family_", None)
        reg_mod._FAMILY_BUCKETS.pop("pva_serving_request_latency_seconds",
                                    None)


# --- propagation: HTTP hop --------------------------------------------------

def test_http_hop_traceparent_continuation_and_echo(tracer):
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.serving.server import InferenceServer

    engine = StubEngine(forward_s=0.001, num_classes=4)
    engine.model_name = "ztrace-stub"
    stats = ServingStats(window=64)
    sched = Scheduler(engine, stats=stats, max_queue=64, name="ztrace-http")
    srv = InferenceServer(engine, sched, stats, host="127.0.0.1", port=0,
                          request_timeout_s=10.0).start()
    host, port = srv.address
    url = f"http://{host}:{port}"
    body = json.dumps(
        {"video": np.zeros((2, 4, 4, 3), np.float32).tolist()}).encode()
    try:
        # hop 1: incoming traceparent is CONTINUED (head already sampled)
        ctx = trace.TraceContext(trace._new_trace_id(),
                                 trace._new_span_id())
        req = urllib.request.Request(
            url + "/predict", data=body,
            headers={"Content-Type": "application/json",
                     "traceparent": trace.format_traceparent(ctx)})
        with urllib.request.urlopen(req, timeout=10.0) as r:
            assert r.status == 200
            assert r.headers["x-pva-trace-id"] == ctx.trace_id
        # hop 2: no header -> a fresh head-sampled trace, id still echoed
        req2 = urllib.request.Request(
            url + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req2, timeout=10.0) as r2:
            fresh_id = r2.headers["x-pva-trace-id"]
            assert fresh_id and fresh_id != ctx.trace_id
        # /stats carries the slowest traced completions
        with urllib.request.urlopen(url + "/stats", timeout=10.0) as r3:
            snap = json.loads(r3.read())
        assert {s["trace_id"] for s in snap["slowest_traces"]} >= {
            ctx.trace_id, fresh_id}
    finally:
        srv.close()
    events = tracer.export()["traceEvents"]
    server_side = [e for e in events if e["name"] == "http_predict"
                   and e["args"]["trace_id"] == ctx.trace_id]
    assert server_side, "continued trace must record server-side"
    # the continued span parents onto the REMOTE caller's span id
    assert server_side[0]["args"]["parent_id"] == ctx.span_id
    dispatch = [e for e in events if e["name"] == "device_dispatch"
                and e["args"]["trace_id"] == ctx.trace_id]
    assert dispatch, "engine dispatch must join the continued trace"


# --- export / merge ---------------------------------------------------------

def test_perfetto_schema_roundtrip_and_dump(tracer, tmp_path):
    with tracer.start("outer", force=True, tag="v"):
        with trace.span("inner"):
            pass
    export = tracer.export()
    blob = json.dumps(export)  # must be JSON-serializable as-is
    parsed = json.loads(blob)
    assert parsed["displayTimeUnit"] == "ms"
    assert parsed["otherData"]["pid"] == os.getpid()
    for evt in parsed["traceEvents"]:
        assert evt["ph"] == "X"
        assert isinstance(evt["ts"], float) and evt["ts"] > 0
        assert isinstance(evt["dur"], float) and evt["dur"] >= 0
        assert isinstance(evt["pid"], int) and isinstance(evt["tid"], int)
        assert "trace_id" in evt["args"] and "span_id" in evt["args"]
    # child precedes root in the ring (finishes first) and ts orders them
    names = [e["name"] for e in parsed["traceEvents"]]
    assert names == ["inner", "outer"]
    path = tracer.dump(str(tmp_path / "ring.json"))
    assert path and os.path.exists(path)
    assert tracer.stats()["last_export"] == path
    # the merge tool accepts its own dumps verbatim
    merged = tracetool.merge_paths([path])
    assert len(merged["traceEvents"]) == 2
    summary = tracetool.summarize(merged)
    assert summary["events"] == 2 and summary["traces"] == 1
    assert summary["slowest"][0]["name"] == "outer"


def test_merge_includes_flight_record(tracer, tmp_path):
    from pytorchvideo_accelerate_tpu.obs.flight_recorder import FlightRecorder

    with tracer.start("r", force=True):
        pass
    rec = FlightRecorder(capacity=32)
    rec.record("watchdog", "stall", stalled=["train"])
    # a span of the flight ring carries its start: an interval in the merge
    rec.record("span", "iter", t0=1700000000.25, dur_s=0.5, step=7)
    flight = tmp_path / "flight_record.json"
    rec.install(str(tmp_path))
    assert rec.dump() == str(flight)
    ring = tmp_path / "ring.json"
    tracer.dump(str(ring))
    merged = tracetool.merge_paths([str(ring), str(flight)])
    phases = {e["ph"] for e in merged["traceEvents"]}
    assert phases == {"X", "i"}  # spans + instants on one timeline
    ts = [e["ts"] for e in merged["traceEvents"]]
    assert ts == sorted(ts)
    span = [e for e in merged["traceEvents"] if e["name"] == "span:iter"]
    assert [(e["ph"], e["ts"], e["dur"], e["args"]["step"]) for e in span] \
        == [("X", 1700000000.25e6, 0.5e6, 7)]


def test_multiprocess_merge_two_forced_children(tmp_path):
    """Two forced-host children each dump a trace ring; the merge puts
    both on one timeline with distinct pids (the SERVE_FLEET merge path,
    minus the HTTP fabric). Children import only obs.trace (stdlib), so
    this stays cheap."""
    from pytorchvideo_accelerate_tpu.utils.forcehost import forced_host_env

    child = """
import json, sys
sys.path.insert(0, {root!r})
from pytorchvideo_accelerate_tpu.obs import trace
t = trace.configure_tracing(1.0, seed={seed}, capacity=64)
with t.start("child_root", force=True, host={seed}):
    with trace.span("child_work"):
        pass
path = t.dump({path!r})
print(json.dumps({{"path": path}}))
"""
    paths = []
    for i in (0, 1):
        out = str(tmp_path / f"ring_{i}.json")
        code = child.format(root=ROOT, seed=i, path=out)
        proc = subprocess.run([sys.executable, "-c", code],
                              env=forced_host_env(2), timeout=120,
                              capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert json.loads(proc.stdout.strip().splitlines()[-1])["path"] == out
        paths.append(out)
    merged = tracetool.merge_paths(paths)
    summary = tracetool.summarize(merged)
    assert summary["events"] == 4
    assert len(summary["pids"]) == 2, "two processes must both appear"
    assert summary["traces"] == 2
    ts = [e["ts"] for e in merged["traceEvents"]]
    assert ts == sorted(ts)
    # each child's root->work parentage survived the merge
    for tid, rec in (
            (e["args"]["trace_id"], e) for e in merged["traceEvents"]):
        assert tid


# --- doctor + stats ---------------------------------------------------------

def test_doctor_trace_snapshot(tracer):
    from pytorchvideo_accelerate_tpu.utils.device_doctor import trace_snapshot

    with tracer.start("slow_root", force=True):
        pass
    snap = trace_snapshot()
    assert snap["enabled"] is True
    assert snap["ring_occupancy"] == 1
    assert snap["ring_capacity"] == 1024
    assert snap["sampled"] >= 1
    assert snap["overhead_s"] >= 0.0
    assert snap["slowest_traces"][0]["name"] == "slow_root"
    trace.disable_tracing()
    assert trace_snapshot()["enabled"] is False


def test_ring_bounded_and_eviction_counted():
    t = trace.Tracer(sample_rate=1.0, seed=0, capacity=16)
    for i in range(40):
        with t.start("r", force=True, seq=i):
            pass
    stats = t.stats()
    assert stats["ring_occupancy"] == 16
    assert stats["events_recorded"] == 40
    assert stats["events_evicted"] == 24


# --- the trace-propagation lint rule ----------------------------------------

_FIX_PATH = "pytorchvideo_accelerate_tpu/fleet/scheduler.py"


def _trace_findings(source, path=_FIX_PATH):
    from pytorchvideo_accelerate_tpu.analysis.core import lint_source

    return [f for f in lint_source(source, path=path)
            if f.rule == "trace-propagation"]


def test_rule_flags_thread_handoff_without_capture():
    src = (
        "from pytorchvideo_accelerate_tpu.utils.sync import make_thread\n"
        "def go(fn):\n"
        "    t = make_thread(target=fn, daemon=True)\n"
        "    t.start()\n")
    findings = _trace_findings(src)
    assert len(findings) == 1
    assert "truncated" in findings[0].message


def test_rule_flags_factory_queue_put():
    src = (
        "from pytorchvideo_accelerate_tpu.utils.sync import make_queue\n"
        "def go(item):\n"
        "    q = make_queue()\n"
        "    q.put(item)\n"
        "    q.put_nowait(item)\n")
    assert len(_trace_findings(src)) == 2


def test_rule_clean_when_module_propagates():
    src = (
        "from pytorchvideo_accelerate_tpu.obs import trace\n"
        "from pytorchvideo_accelerate_tpu.utils.sync import make_thread\n"
        "def go(fn):\n"
        "    ctx = trace.capture()\n"
        "    t = make_thread(target=fn, args=(ctx,), daemon=True)\n"
        "    t.start()\n")
    assert _trace_findings(src) == []


def test_rule_alias_proof():
    # a sync-module alias cannot launder the handoff...
    src = (
        "import pytorchvideo_accelerate_tpu.utils.sync as s\n"
        "def go(fn):\n"
        "    t = s.make_thread(target=fn, daemon=True)\n"
        "    t.start()\n")
    assert len(_trace_findings(src)) == 1
    # ...and a from-import as-name of the helper still counts as wired
    src_ok = (
        "import pytorchvideo_accelerate_tpu.utils.sync as s\n"
        "from pytorchvideo_accelerate_tpu.obs.trace import capture as grab\n"
        "def go(fn):\n"
        "    ctx = grab()\n"
        "    t = s.make_thread(target=fn, args=(ctx,), daemon=True)\n"
        "    t.start()\n")
    assert _trace_findings(src_ok) == []


def test_rule_scoped_to_traced_modules_and_suppressible():
    src = (
        "from pytorchvideo_accelerate_tpu.utils.sync import make_thread\n"
        "def go(fn):\n"
        "    t = make_thread(target=fn, daemon=True)\n"
        "    t.start()\n")
    # a cold module is out of scope
    assert _trace_findings(
        src, path="pytorchvideo_accelerate_tpu/models/slowfast.py") == []
    # the house suppression syntax works (context-free handoffs)
    suppressed = (
        "from pytorchvideo_accelerate_tpu.utils.sync import make_thread\n"
        "def go(fn):\n"
        "    t = make_thread(target=fn, daemon=True)  "
        "# pva: disable=trace-propagation -- health poller carries no "
        "request context\n"
        "    t.start()\n")
    assert _trace_findings(suppressed) == []


def test_rule_clean_on_the_real_tree():
    """The shipped tree must be clean under the new rule (scoped here to
    the traced modules so the failure message names the culprit)."""
    from pytorchvideo_accelerate_tpu.analysis.core import lint_source
    from pytorchvideo_accelerate_tpu.analysis.rules_trace import (
        TRACE_HANDOFF_MODULES,
    )

    pkg = os.path.join(ROOT, "pytorchvideo_accelerate_tpu")
    for suffix in TRACE_HANDOFF_MODULES:
        path = os.path.join(pkg, *suffix.split("/")[-2:]) \
            if os.path.exists(os.path.join(pkg, *suffix.split("/")[-2:])) \
            else None
        if path is None:
            continue
        with open(path, encoding="utf-8") as f:
            source = f.read()
        findings = [x for x in lint_source(source, path=suffix)
                    if x.rule == "trace-propagation"]
        assert findings == [], (suffix, [x.format() for x in findings])


def test_rule_flags_wire_send_in_dataplane_module():
    """The data plane's cross-PROCESS put site: `send_frame(...)` in a
    dataplane module that never touches the trace helpers truncates every
    trace at the process boundary."""
    src = (
        "from pytorchvideo_accelerate_tpu.dataplane.wire import send_frame\n"
        "def ship(sock, batch):\n"
        "    send_frame(sock, 'batch', arrays=batch)\n")
    findings = _trace_findings(
        src, path="pytorchvideo_accelerate_tpu/dataplane/feed.py")
    assert len(findings) == 1
    assert "process boundary" in findings[0].message
    # a dotted spelling is the same site
    src_dotted = (
        "from pytorchvideo_accelerate_tpu.dataplane import wire\n"
        "def ship(sock, batch):\n"
        "    wire.send_frame(sock, 'batch', arrays=batch)\n")
    assert len(_trace_findings(
        src_dotted,
        path="pytorchvideo_accelerate_tpu/dataplane/worker.py")) == 1


def test_rule_wire_send_clean_when_module_continues_traces():
    """continue_trace on a Tracer INSTANCE (the worker's shape:
    `get_tracer().continue_trace(header, ...)`) counts as propagation —
    the cross-process helpers are distinctive enough to recognize on any
    receiver."""
    src = (
        "from pytorchvideo_accelerate_tpu.dataplane.wire import send_frame\n"
        "from pytorchvideo_accelerate_tpu.obs import trace\n"
        "def ship(sock, batch, header):\n"
        "    t = trace.get_tracer()\n"
        "    if t is not None:\n"
        "        h = t.continue_trace(header, 'remote_decode')\n"
        "    send_frame(sock, 'batch', arrays=batch)\n")
    assert _trace_findings(
        src, path="pytorchvideo_accelerate_tpu/dataplane/worker.py") == []


def test_rule_send_frame_out_of_scope_in_cold_modules():
    src = (
        "from pytorchvideo_accelerate_tpu.dataplane.wire import send_frame\n"
        "def ship(sock, batch):\n"
        "    send_frame(sock, 'batch', arrays=batch)\n")
    assert _trace_findings(
        src, path="pytorchvideo_accelerate_tpu/models/x3d.py") == []

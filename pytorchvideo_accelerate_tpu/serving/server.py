"""Stdlib HTTP front + the `pva-tpu-serve` CLI.

Endpoints:
  POST /predict  — body {"video": nested-list clip} (or {"slow":…,"fast":…}
                   for SlowFast), clip shaped (T,H,W,C) or (V,T,H,W,C);
                   responds {"logits": […], "top1": k, "latency_ms": x}.
  GET  /healthz  — liveness + model identity (load balancers poll this).
  GET  /stats    — ServingStats.snapshot(): p50/p95/p99 latency, queue
                   depth, batch-fill ratio, throughput, compile count,
                   uptime, rejected split by cause (400/503/504).
  GET  /metrics  — Prometheus text exposition of the same registry the
                   /stats counters read from (obs/registry.py): request/
                   batch/rejection counters, latency histogram, queue
                   depth + uptime gauges. Point a scraper here.
  POST /drain    — controller endpoint (fleet/control/): flip admission
                   to DRAINING (healthz 503, new work sheds, in-flight
                   flushes) WITHOUT tearing the process down — the fleet
                   autoscaler re-homes sessions then reaps separately.

Deliberately stdlib (`http.server.ThreadingHTTPServer`): zero new
dependencies, and the concurrency story is honest — handler threads only
parse JSON and block on a batcher future; all accelerator work is
serialized behind a single flush thread (the continuous-batching
`fleet/scheduler.Scheduler` by default — deadlines, priority classes,
EDF launches; `--serve.scheduler micro` restores the MicroBatcher
policy). Error mapping: bad request -> 400, shed/queue full -> 503
(+ Retry-After; deadline sheds resolve the future the same way), request
budget exceeded -> 504 (+ Retry-After).

Degradation (serving/admission.py, docs/RELIABILITY.md): a
healthy/degraded/draining state machine sits in front of the batcher —
queue depth past the high-water mark sheds with `503 + Retry-After`
BEFORE latency collapses, `/healthz` reports the state (and goes 503
while draining so load balancers stop routing), and SIGTERM on the CLI
path drains: stop admitting, flush in-flight futures, exit 0.
"""

from __future__ import annotations

import json
import signal
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional, Sequence
from urllib.parse import parse_qs, urlparse

import numpy as np

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.obs import alerts as obs_alerts
from pytorchvideo_accelerate_tpu.obs import history as obs_history
from pytorchvideo_accelerate_tpu.obs import memory as obs_memory
from pytorchvideo_accelerate_tpu.obs import profiler as obs_profiler
from pytorchvideo_accelerate_tpu.obs import trace
from pytorchvideo_accelerate_tpu.serving.admission import (
    DRAINING,
    AdmissionController,
)
from pytorchvideo_accelerate_tpu.serving.batcher import MicroBatcher, QueueFullError
from pytorchvideo_accelerate_tpu.serving.engine import CLIP_KEYS, InferenceEngine
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
from pytorchvideo_accelerate_tpu.utils.hw import device_summary
from pytorchvideo_accelerate_tpu.utils.logging import get_logger

logger = get_logger("pva_tpu")


class _Handler(BaseHTTPRequestHandler):
    server_version = "pva-tpu-serve/0.4"
    protocol_version = "HTTP/1.1"

    # route access logs to the package logger instead of stderr spam
    def log_message(self, fmt, *args):  # noqa: D102
        logger.debug("http: " + fmt, *args)

    def _reply(self, code: int, payload: dict,
               headers: Optional[Dict[str, str]] = None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        if self.close_connection:
            # say it explicitly (shed-before-body-read leaves the request
            # stream unread, so this connection cannot be reused): clients
            # must not wait on a keep-alive that will never come
            self.send_header("Connection", "close")
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _reject(self, code: int, message: str, retry_after_s: float,
                headers: Optional[Dict[str, str]] = None) -> None:
        """503/504 with Retry-After: the cheapest response the server can
        produce, and it tells a well-behaved client when to come back."""
        hdrs = {"Retry-After": str(max(int(round(retry_after_s)), 1))}
        if headers:
            hdrs.update(headers)
        self._reply(code, {"error": message, "retry_after_s": retry_after_s},
                    headers=hdrs)

    def do_GET(self):  # noqa: N802 - stdlib API
        srv: "InferenceServer" = self.server.owner
        if self.path == "/healthz":
            eng = srv.engine
            state = srv.admission.state()
            health = {
                # the state machine IS the health answer: "healthy",
                # "degraded" (shedding, still 200 — the replica works,
                # don't kill it), "draining" (503 — stop routing here)
                "status": state,
                "model": eng.model_name,
                "num_classes": eng.num_classes,
                "input_dtype": eng.input_dtype,
                "buckets": list(eng.buckets),
                # what JAX gave this process (utils/hw.device_summary)
                "platform": srv.device["platform"],
                "device_kind": srv.device["kind"],
                "device_count": srv.device["count"],
                "queue_depth": srv.batcher.queue_depth(),
                # streaming capability: whether /stream serves sessions
                # here (routers/load balancers may key affinity on it)
                "streaming": bool(getattr(srv.batcher,
                                          "supports_sessions", False)),
            }
            if srv.expected_spec is not None:  # per-request (T, H, W, C)
                health["clip_spec"] = {k: list(v[1:])
                                       for k, v in srv.expected_spec.items()}
            self._reply(503 if state == DRAINING else 200, health)
        elif self.path == "/stats":
            snap = srv.stats.snapshot()
            # slowest TRACED completions ride /stats (not the flat
            # snapshot dict — trackers keep their {str: float} surface):
            # a bad p99 here names trace ids to pull from the merged
            # timeline (docs/OBSERVABILITY.md § exemplar→trace)
            slowest = srv.stats.slowest_traces()
            if slowest:
                snap["slowest_traces"] = slowest
            self._reply(200, snap)
        elif self.path == "/metrics":
            body = srv.stats.registry.render().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path.split("?", 1)[0] == "/history":
            # pva-tpu-hbm: the scrape-tick ring as JSON series. Optional
            # query: ?window_s=SECONDS trims the trailing window, ?keys=a,b
            # restricts to named flat scrape keys. 503 while the history
            # ring is disarmed (obs.enabled=false / history_ticks=0) — a
            # scraper must be able to tell "off" from "empty".
            hist = obs_history.get_history()
            if hist is None:
                self._reply(503, {"error": "metrics history disarmed "
                                           "(obs.history_ticks=0?)"})
                return
            try:
                q = parse_qs(urlparse(self.path).query)
                window_s = (float(q["window_s"][0])
                            if "window_s" in q else None)
                keys = (sorted({k for tok in q["keys"]
                                for k in tok.split(",") if k})
                        if "keys" in q else None)
            except (ValueError, TypeError) as e:
                self._reply(400, {"error": f"bad query: {e}"})
                return
            payload = hist.to_json(keys=keys, window_s=window_s)
            engine = obs_alerts.get_engine()
            if engine is not None:
                payload["alerts"] = engine.snapshot()["rules"]
                payload["alerts_active"] = engine.active()
            self._reply(200, payload)
        else:
            self._reply(404, {"error": f"no route {self.path}"})

    def do_POST(self):  # noqa: N802 - stdlib API
        srv: "InferenceServer" = self.server.owner
        if self.path == "/stream":
            self._do_stream(srv)
            return
        if self.path == "/drain":
            # controller-initiated drain (fleet/control/autoscaler.py):
            # flip admission to DRAINING — /healthz goes 503 so pollers
            # route around, new work sheds, in-flight futures keep
            # flushing — but do NOT tear the server down: the controller
            # re-homes the replica's live sessions first and reaps the
            # process itself once outstanding work has flushed. Reading
            # the (empty) body keeps the keep-alive connection clean.
            length = int(self.headers.get("Content-Length", 0))
            if length:
                self.rfile.read(length)
            srv.admission.start_draining()
            obs.get_recorder().record("serving", "drain-requested")
            self._reply(200, {"draining": True,
                              "status": srv.admission.state(),
                              "queue_depth": srv.batcher.queue_depth()})
            return
        if self.path.split("?", 1)[0] == "/profile":
            self._do_profile(srv)
            return
        if self.path != "/predict":
            self._reply(404, {"error": f"no route {self.path}"})
            return
        # admission control BEFORE the body is even read (serving/
        # admission.py): a shed must be the cheapest response the server
        # can produce — under real overload, json.loads of a multi-MB clip
        # per shed request would saturate the host CPU anyway. The unread
        # body forces a connection close (can't reuse the stream).
        admitted, retry_after = srv.admission.admit(
            srv.batcher.queue_depth())
        if not admitted:
            state = srv.admission.state()
            srv.stats.observe_shed(state)
            self.close_connection = True
            self._reject(503, f"load shed (service {state}); retry later",
                         retry_after)
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            clip = {k: np.asarray(body[k], dtype=srv.engine.input_dtype)
                    for k in CLIP_KEYS if k in body}
            if not clip:
                raise ValueError(
                    "body needs 'video' (or 'slow'+'fast') nested lists")
            srv.check_geometry(clip)
            # per-request scheduling hints (fleet/scheduler.py): forwarded
            # only to deadline-aware fronts — a plain MicroBatcher treats
            # every request the same by design, so the keys are ignored
            kwargs = {}
            if getattr(srv.batcher, "supports_priority", False):
                if "priority" in body:
                    kwargs["priority"] = str(body["priority"])
                if "deadline_ms" in body:
                    kwargs["deadline_ms"] = float(body["deadline_ms"])
        except (ValueError, TypeError, KeyError) as e:
            srv.stats.observe_rejected("400")
            self._reply(400, {"error": f"bad request: {e}"})
            return
        # distributed tracing (obs/trace.py): continue an incoming
        # `traceparent` (the head already sampled it) or start a fresh
        # head-sampled trace; the submit below captures the context into
        # the request, so the scheduler/batcher spans join this trace.
        # Sheds above stay untraced on purpose — a shed must remain the
        # cheapest response the server can produce.
        rt = trace.get_tracer()
        handle = None
        if rt is not None:
            tp = self.headers.get("traceparent")
            handle = rt.continue_trace(tp, "http_predict") if tp else None
            if handle is None:
                # absent OR malformed/unsampled header: fall back to the
                # local head-sampling decision (a corrupt header must
                # degrade to "normal sampling", never disable tracing)
                handle = rt.start("http_predict")
        tid = handle.ctx.trace_id if handle is not None else None
        # sampled responses echo the id so clients/log pipelines can join
        # their records to the server-side trace
        echo = {"x-pva-trace-id": tid} if tid else None
        with (handle if handle is not None else trace.NOOP):
            try:
                future = srv.batcher.submit(clip, **kwargs)
            except QueueFullError as e:
                # the batcher already counted this one (cause "503")
                self._reject(503, str(e), e.retry_after_s, headers=echo)
                return
            except ValueError as e:
                srv.stats.observe_rejected("400")
                self._reply(400, {"error": f"bad request: {e}"},
                            headers=echo)
                return
            t0 = time.monotonic()
            try:
                logits = future.result(timeout=srv.request_timeout_s)
            except FutureTimeout:
                if future.cancel():
                    # shed before the engine touched it: a true rejection
                    srv.stats.observe_rejected("504")
                else:
                    # lost the cancel race: the flush thread already claimed
                    # the request and will count it as completed — counting a
                    # 504 too would double-book it across the requests/
                    # rejected partition. Record the budget miss separately.
                    obs.get_recorder().warn(
                        "504 after engine claim (request completed but "
                        "client timed out)", budget_s=srv.request_timeout_s)
                # traced rejections echo the id too: a 504 is exactly the
                # tail-latency failure whose server-side trace an operator
                # needs to find
                self._reject(
                    504, f"request exceeded {srv.request_timeout_s}s budget",
                    srv.admission.retry_after_s, headers=echo)
                return
            except QueueFullError as e:
                # shed AFTER admission: the continuous-batching scheduler's
                # shed-before-deadline-miss (fleet/scheduler.ShedError) or a
                # fleet router with no routable capacity resolves the FUTURE
                # with the shed — same 503 + Retry-After contract as a
                # submit-time shed, never a 500 and never a burned 504 budget
                self._reject(503, str(e), e.retry_after_s, headers=echo)
                return
            except Exception as e:  # noqa: BLE001 - batch failure surfaced per-request
                srv.stats.observe_error()
                self._reply(500, {"error": f"inference failed: {e}"},
                            headers=echo)
                return
            self._reply(200, {
                "logits": np.asarray(logits, np.float32).tolist(),
                "top1": int(np.argmax(logits)),
                "latency_ms": round((time.monotonic() - t0) * 1e3, 3),
            }, headers=echo)

    def _do_profile(self, srv: "InferenceServer") -> None:
        """POST /profile?seconds=S — pva-tpu-hbm on-demand capture: start a
        jax.profiler trace window on the LIVE server, stopped by a
        background timer and published atomically as
        <output_dir>/profile_<tag>/ (obs/profiler.py). 202 with the
        pending tag when the capture starts, 409 while one is already in
        flight (one capture at a time — traces are expensive), 503 when
        the profiler is disarmed."""
        length = int(self.headers.get("Content-Length", 0))
        if length:  # keep the keep-alive stream clean
            self.rfile.read(length)
        prof = obs_profiler.get_profiler()
        if prof is None:
            self._reply(503, {"error": "profiler disarmed (obs.enabled "
                                       "off or no output_dir)"})
            return
        try:
            q = parse_qs(urlparse(self.path).query)
            seconds = float(q.get("seconds", ["3"])[0])
            if not 0 < seconds <= 120:
                raise ValueError("seconds must be in (0, 120]")
        except (ValueError, TypeError) as e:
            self._reply(400, {"error": f"bad query: {e}"})
            return
        tag = prof.capture_for(seconds)
        if tag is None:
            self._reply(409, {"error": "a profile capture is already "
                                       "running", "busy": True})
            return
        obs.get_recorder().record("profile", "capture-requested",
                                  seconds=seconds, tag=tag)
        self._reply(202, {"capturing": True, "seconds": seconds,
                          "tag": tag})

    def _do_stream(self, srv: "InferenceServer") -> None:
        """POST /stream — one incremental session advance (docs/SERVING.md
        § streaming). Body: ``{"session": id, "frames": [s new frames],
        "window": optional resendable (T,H,W,C), "stride": int,
        "end": bool, "priority"/"deadline_ms": as /predict}``. Responds
        with the logits over the session's rolling window. Error map:
        admission/budget shed -> 503 + Retry-After (like /predict),
        malformed -> 400, session unknown with no window -> 409 (resend
        the window), budget miss -> 504."""
        from pytorchvideo_accelerate_tpu.streaming.session import (
            SessionUnknownError,
        )

        # same shed-before-body-read admission as /predict: a shed must
        # stay the cheapest response under overload
        admitted, retry_after = srv.admission.admit(
            srv.batcher.queue_depth())
        if not admitted:
            state = srv.admission.state()
            srv.stats.observe_shed(state)
            self.close_connection = True
            self._reject(503, f"load shed (service {state}); retry later",
                         retry_after)
            return
        if not getattr(srv.batcher, "supports_sessions", False):
            srv.stats.observe_rejected("400")
            self._reply(400, {"error": "this replica serves no streaming "
                                       "sessions (serve.streaming off)"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            sid = str(body.get("session") or "")
            if not sid:
                raise ValueError("body needs a 'session' id")
            clip = {}
            if body.get("frames") is not None:
                clip["video"] = np.asarray(body["frames"],
                                           dtype=srv.engine.input_dtype)
            session = {"sid": sid, "end": bool(body.get("end"))}
            if body.get("window") is not None:
                session["window"] = np.asarray(
                    body["window"], dtype=srv.engine.input_dtype)
            if body.get("stride") is not None:
                session["stride"] = int(body["stride"])
            kwargs: dict = {"session": session}
            if "priority" in body:
                kwargs["priority"] = str(body["priority"])
            if "deadline_ms" in body:
                kwargs["deadline_ms"] = float(body["deadline_ms"])
        except (ValueError, TypeError, KeyError) as e:
            srv.stats.observe_rejected("400")
            self._reply(400, {"error": f"bad request: {e}"})
            return
        rt = trace.get_tracer()
        handle = None
        if rt is not None:
            tp = self.headers.get("traceparent")
            handle = rt.continue_trace(tp, "http_stream") if tp else None
            if handle is None:
                handle = rt.start("http_stream")
        tid = handle.ctx.trace_id if handle is not None else None
        echo = {"x-pva-trace-id": tid} if tid else None
        with (handle if handle is not None else trace.NOOP):
            try:
                future = srv.batcher.submit(clip, **kwargs)
            except QueueFullError as e:
                self._reject(503, str(e), e.retry_after_s, headers=echo)
                return
            except ValueError as e:
                srv.stats.observe_rejected("400")
                self._reply(400, {"error": f"bad request: {e}"},
                            headers=echo)
                return
            t0 = time.monotonic()
            try:
                logits = future.result(timeout=srv.request_timeout_s)
            except FutureTimeout:
                if future.cancel():
                    srv.stats.observe_rejected("504")
                else:
                    obs.get_recorder().warn(
                        "504 after engine claim (stream advance completed "
                        "but client timed out)",
                        budget_s=srv.request_timeout_s)
                self._reject(
                    504, f"request exceeded {srv.request_timeout_s}s budget",
                    srv.admission.retry_after_s, headers=echo)
                return
            except QueueFullError as e:
                self._reject(503, str(e), e.retry_after_s, headers=echo)
                return
            except SessionUnknownError as e:
                # not a bad request: the client holds the stream and can
                # re-establish — 409 tells it to resend its window
                self._reply(409, {"error": str(e)}, headers=echo)
                return
            except ValueError as e:
                srv.stats.observe_rejected("400")
                self._reply(400, {"error": f"bad request: {e}"},
                            headers=echo)
                return
            except Exception as e:  # noqa: BLE001 - per-request failure
                srv.stats.observe_error()
                self._reply(500, {"error": f"inference failed: {e}"},
                            headers=echo)
                return
            self._reply(200, {
                "logits": np.asarray(logits, np.float32).tolist(),
                "top1": int(np.argmax(logits)),
                "session": sid,
                "latency_ms": round((time.monotonic() - t0) * 1e3, 3),
            }, headers=echo)


class InferenceServer:
    """ThreadingHTTPServer wrapper owning engine + batcher + stats."""

    def __init__(self, engine: InferenceEngine, batcher: MicroBatcher,
                 stats: ServingStats, host: str = "127.0.0.1", port: int = 0,
                 request_timeout_s: float = 30.0,
                 expected_spec: Optional[dict] = None,
                 watchdog=None, admission: Optional[AdmissionController] = None,
                 drain_grace_s: float = 10.0):
        self.engine = engine
        self.batcher = batcher
        self.stats = stats
        self.watchdog = watchdog  # obs.Watchdog over the flush thread
        self.request_timeout_s = request_timeout_s
        self.drain_grace_s = drain_grace_s
        if admission is None:  # direct construction (tests, embedding)
            q = getattr(batcher, "_q", None)
            admission = AdmissionController(
                max_queue=getattr(q, "maxsize", 0) or 256)
        if admission.queue_depth_fn is None:
            # idle degraded->healthy recovery on /healthz reads
            admission.queue_depth_fn = batcher.queue_depth
        self.admission = admission
        # clip-name -> (1, T, H, W, C) from the artifact's config (None =
        # accept any geometry; direct/bench construction)
        self.expected_spec = expected_spec
        self.device = device_summary()
        logger.info("device: %s", json.dumps(self.device))
        self.httpd = ThreadingHTTPServer((host, port), _Handler)
        self.httpd.daemon_threads = True
        self.httpd.owner = self  # handler back-reference
        self._thread = None
        # pva-tpu-hbm: the burn-rate alert engine needs a control cadence;
        # a ticker thread starts with the server when one is armed (each
        # tick also appends a scrape to the /history ring). 0 disables.
        self.alert_tick_s = 1.0
        self._tick_stop: Optional[threading.Event] = None
        self._tick_thread = None

    def _start_alert_ticker(self) -> None:
        engine = obs_alerts.get_engine()
        if engine is None or self.alert_tick_s <= 0 \
                or self._tick_thread is not None:
            return
        from pytorchvideo_accelerate_tpu.utils.sync import make_thread

        stop = threading.Event()

        def _loop():
            while not stop.wait(self.alert_tick_s):
                try:
                    engine.tick()
                except Exception:  # noqa: BLE001 - ticker must survive
                    logger.exception("alert tick failed")

        self._tick_stop = stop
        self._tick_thread = make_thread(target=_loop,
                                        name="pva-serve-alerts",
                                        daemon=True)
        self._tick_thread.start()

    @property
    def address(self) -> tuple:
        """Actual (host, port) bound — port 0 resolves here."""
        return self.httpd.server_address[:2]

    def check_geometry(self, clip: dict) -> None:
        """400-guard: requests must carry the serving geometry. Every new
        shape the engine sees costs a synchronous compile on the batch
        thread (and a cached executable forever), so when the artifact
        declared its clip spec, off-spec requests are rejected up front —
        only the view count (leading axis of a rank-5 clip) is free."""
        if self.expected_spec is None:
            return
        if sorted(clip) != sorted(self.expected_spec):
            raise ValueError(
                f"request clips {sorted(clip)} != served model's "
                f"{sorted(self.expected_spec)}")
        for k, v in clip.items():
            want = tuple(self.expected_spec[k][1:])  # (T, H, W, C)
            got = tuple(v.shape[-4:]) if v.ndim == 5 else tuple(v.shape)
            if got != want:
                raise ValueError(
                    f"clip {k!r} geometry {tuple(v.shape)} does not match "
                    f"the served model's (T,H,W,C)={want} "
                    "(an optional leading view axis is allowed)")

    def start(self) -> "InferenceServer":
        """Serve on a background thread (tests / embedding)."""
        from pytorchvideo_accelerate_tpu.utils.sync import make_thread

        self._thread = make_thread(
            target=self.httpd.serve_forever, name="pva-serve-http",
            daemon=True)
        self._thread.start()
        self._start_alert_ticker()
        return self

    def drain(self, grace_s: Optional[float] = None) -> None:
        """Graceful shutdown: stop admitting (every /predict sheds with
        503 + Retry-After, /healthz goes 503 so LBs stop routing), flush
        the in-flight futures within the grace budget, then close."""
        self.admission.start_draining()
        drained = self.batcher.drain(
            self.drain_grace_s if grace_s is None else grace_s)
        if not drained:
            logger.warning("drain: queue not empty at grace deadline; "
                           "remaining requests will be failed by close()")
        self.close()

    def _install_drain_handler(self) -> None:
        """SIGTERM -> drain (CLI path only). This REPLACES the recorder's
        dump-only SIGTERM hook, so the PR 3 evidence is written here
        explicitly: record the signal, dump the flight ring, then drain."""

        def on_term(signum, frame):
            logger.info("SIGTERM: draining (stop admitting, flush "
                        "in-flight, exit 0)")
            obs.get_recorder().record("signal", "SIGTERM-drain")
            obs.get_recorder().dump()  # flight_record.json still lands
            trace.dump()  # the trace ring too (no-op when disarmed)
            # httpd.shutdown() must run off the serve_forever thread
            from pytorchvideo_accelerate_tpu.utils.sync import make_thread

            make_thread(target=self.drain, name="pva-serve-drain",
                        daemon=True).start()

        try:
            signal.signal(signal.SIGTERM, on_term)
        except (ValueError, OSError):  # not the main thread: no drain hook
            pass

    def serve_forever(self, drain_on_sigterm: bool = True) -> None:
        """Serve on the calling thread (the CLI path)."""
        if drain_on_sigterm and self.drain_grace_s > 0:
            self._install_drain_handler()
        self._start_alert_ticker()
        try:
            self.httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.close()

    def close(self) -> None:
        # idempotent: the drain path closes, then serve_forever's finally
        # closes again on its way out
        if getattr(self, "_closed", False):
            return
        self._closed = True
        if self._tick_stop is not None:
            self._tick_stop.set()
            if self._tick_thread is not None:
                self._tick_thread.join(timeout=5.0)
            self._tick_thread = self._tick_stop = None
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        self.batcher.close()
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None


def build_server(cfg) -> InferenceServer:
    """serve.* config block -> a ready (not yet started) InferenceServer."""
    import jax

    from pytorchvideo_accelerate_tpu import obs

    s = cfg.serve
    if not s.checkpoint:
        raise SystemExit(
            "serving needs --serve.checkpoint pointing at an "
            "export_inference artifact (see docs/SERVING.md)")
    if cfg.cpu:
        jax.config.update("jax_platforms", "cpu")
    # telemetry spine: same config block as training (obs.*); the watchdog
    # covers the single flush thread — a wedged compile or stuck H2D there
    # stalls EVERY request, and without a heartbeat it stalls silently
    obs.configure(enabled=cfg.obs.enabled,
                  capacity=cfg.obs.flight_recorder_events)
    if cfg.obs.enabled and cfg.obs.trace_sample_rate > 0:
        # distributed tracing: head-sample this fraction of /predict
        # requests (incoming traceparent headers are always continued);
        # the ring dumps to <output_dir>/trace_ring.json on SIGTERM-drain
        trace.configure_tracing(cfg.obs.trace_sample_rate, seed=cfg.seed,
                                capacity=cfg.obs.trace_ring_events,
                                output_dir=cfg.checkpoint.output_dir)
    watchdog = None
    if cfg.obs.enabled:
        # flight-record destination + SIGTERM/excepthook dump hooks for the
        # serving process too (checkpoint.output_dir defaults to "."): a
        # killed or wedged server leaves the same evidence file a training
        # run does (pva-tpu-doctor --obs-dir reads it)
        obs.get_recorder().install(cfg.checkpoint.output_dir)
        if cfg.obs.watchdog_timeout_s > 0:
            watchdog = obs.Watchdog(
                cfg.obs.watchdog_timeout_s,
                output_dir=cfg.checkpoint.output_dir,
                recorder=obs.get_recorder(),
                collector=obs.get_collector()).start()
    # pva-tpu-hbm: on-demand profiler capture for the serving process
    # (POST /profile); ledger + history/alerts arm just after stats is
    # built below — their gauges belong in the ServingStats registry so
    # /metrics and /history carry them (it is per-instance by design).
    if cfg.obs.enabled:
        obs_profiler.configure(output_dir=cfg.checkpoint.output_dir,
                               recorder=obs.get_recorder())
    latency_buckets = None
    if s.latency_buckets_ms:
        try:
            latency_buckets = sorted(
                float(b) / 1e3 for b in s.latency_buckets_ms.split(",") if b)
        except ValueError:
            raise SystemExit(
                f"--serve.latency_buckets_ms {s.latency_buckets_ms!r}: "
                "expected comma-separated millisecond bounds, e.g. "
                "'5,10,25,50,100,250,1000'")
    stats = ServingStats(window=s.stats_window,
                         latency_buckets=latency_buckets)
    if cfg.obs.enabled and cfg.obs.memory_ledger:
        # the engines' weight pins / compiled caches / session rings
        # register through the module hooks into this singleton; its
        # pva_hbm_* gauges ride the serving /metrics + /history
        obs_memory.configure(recorder=obs.get_recorder(),
                             registry=stats.registry)
    if cfg.obs.enabled and cfg.obs.history_ticks > 0:
        # burn-rate SLO rules over the serving series (obs/alerts.py
        # default_rules); the server's ticker thread drives the cadence
        hist = obs_history.configure(capacity=cfg.obs.history_ticks,
                                     registry=stats.registry)
        obs_alerts.configure(history=hist, registry=stats.registry,
                             recorder=obs.get_recorder())
    engine = InferenceEngine.from_artifact(
        s.checkpoint, max_batch_size=s.max_batch_size, stats=stats,
        quantization=s.quantization if s.quantization != "off" else None)
    spec = None
    if engine.artifact_config is not None:
        # pre-compile every bucket for the training run's clip geometry so
        # the first requests never pay a compile (multi-view variants of
        # the same geometry still compile on first arrival); the same spec
        # then 400-guards /predict against off-geometry requests
        from pytorchvideo_accelerate_tpu.models import model_input_spec

        spec = model_input_spec(engine.artifact_config.model,
                                engine.artifact_config.data)
        sample = {k: np.zeros(shape[1:], engine.input_dtype)
                  for k, shape in spec.items()}
        logger.info("warmup: compiling buckets %s for %s",
                    engine.buckets, {k: v.shape for k, v in sample.items()})
        engine.warmup(sample)
    front_engine = engine
    if s.streaming:
        # stateful streaming mode (streaming/engine.py): /stream advances
        # run incrementally against device-resident session rings; the
        # scheduler batches them across sessions. /predict still serves
        # stateless one-shot requests through the same wrapped engine.
        from pytorchvideo_accelerate_tpu.streaming import StreamingEngine

        front_engine = StreamingEngine(
            engine, session_budget_mb=s.stream_session_budget_mb,
            session_ttl_s=s.stream_session_ttl_s,
            retry_after_s=s.retry_after_s,
            trunk=s.stream_trunk)
        if s.scheduler != "edf":
            raise SystemExit(
                "--serve.streaming needs the continuous-batching "
                "scheduler (--serve.scheduler edf); the MicroBatcher has "
                "no session launch path")
        if spec is not None and "video" in spec:
            # pre-compile establish+advance at every (stride, bucket) for
            # the served geometry: a first advance compiling on the flush
            # thread would stall the launch AND poison the service-time
            # EWMA into transient deadline sheds (serve.stream_strides)
            _, t, h, w, c = spec["video"]
            for tok in s.stream_strides.split(","):
                if not tok.strip():
                    continue
                try:
                    n = front_engine.warmup_stream(t, h, w, c,
                                                   int(tok))
                    logger.info("stream warmup: stride %s -> %d "
                                "compiled steps", tok.strip(), n)
                except Exception as e:  # noqa: BLE001 - invalid stride for this model
                    logger.warning("stream warmup skipped stride %s: %s",
                                   tok.strip(), e)
    heartbeat = watchdog.beat_fn("serve_batcher") if watchdog else None
    if s.scheduler == "edf":
        # the continuous-batching scheduler (fleet/scheduler.py) is the
        # default hot path: deadlines + priority classes + EDF launches +
        # shed-before-deadline-miss; serve.max_wait_ms becomes the
        # batch-class coalescing dial (realtime is work-conserving)
        from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler

        batcher = Scheduler(
            front_engine, max_queue=s.max_queue, stats=stats,
            realtime_deadline_ms=s.realtime_deadline_ms,
            batch_deadline_ms=s.batch_deadline_ms,
            batch_max_wait_ms=s.max_wait_ms,
            retry_after_s=s.retry_after_s, heartbeat=heartbeat)
    elif s.scheduler == "micro":
        batcher = MicroBatcher(
            engine, max_wait_ms=s.max_wait_ms, max_queue=s.max_queue,
            stats=stats, retry_after_s=s.retry_after_s,
            heartbeat=heartbeat)
    else:
        raise SystemExit(
            f"unknown --serve.scheduler {s.scheduler!r} (edf | micro)")
    stats.queue_depth_fn = batcher.queue_depth
    admission = AdmissionController(
        max_queue=s.max_queue, shed_frac=s.shed_queue_frac,
        recover_frac=s.recover_queue_frac, retry_after_s=s.retry_after_s,
        on_state_change=lambda old, new: (
            logger.warning("serving state %s -> %s", old, new),
            obs.get_recorder().record("serving", "state-change",
                                      old=old, new=new)))
    return InferenceServer(engine, batcher, stats, host=s.host, port=s.port,
                           request_timeout_s=s.request_timeout_s,
                           expected_spec=spec, watchdog=watchdog,
                           admission=admission,
                           drain_grace_s=s.drain_grace_s)


def main(argv: Optional[Sequence[str]] = None) -> None:
    """`pva-tpu-serve --serve.checkpoint PATH [--serve.port N ...]`."""
    from pytorchvideo_accelerate_tpu.config import parse_cli
    from pytorchvideo_accelerate_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cfg = parse_cli(argv)
    enable_compile_cache()
    server = build_server(cfg)
    host, port = server.address
    logger.info("serving %s on http://%s:%d (/predict /healthz /stats)",
                server.engine.model_name, host, port)
    print(f"pva-tpu-serve: http://{host}:{port}  model="
          f"{server.engine.model_name} buckets={server.engine.buckets} "
          f"device={json.dumps(server.device)}", flush=True)
    server.serve_forever()


if __name__ == "__main__":
    main()

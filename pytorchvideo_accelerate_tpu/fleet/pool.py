"""Replica pool: N serving replicas behind health-gated membership.

A replica is one `InferenceEngine` behind one `Scheduler` — in this
process (`LocalReplica`, engines pinned to disjoint device meshes; the
multi-device CI story runs them on a forced-host slice via
`utils/forcehost.py`) or in another process behind HTTP (`HttpReplica`,
the production shape: one `pva-tpu-serve` per host/slice). The pool owns
MEMBERSHIP: a poller thread re-checks every replica's health on
`health_interval_s` — driven by the replica's existing `/healthz`
admission state, so a replica that is merely shedding (`degraded`) stays
routable while a `draining` or dead one leaves the rotation — and the
router reports observed deaths (`mark_down`) for immediate route-around
without waiting out a poll interval. A down replica whose health probe
recovers rejoins automatically.

Process replicas are spawned by the operator (or `spawn_serving_process`
below for CI), never supervised here: restart policy belongs to the
platform (k8s, systemd); the pool's job is to keep traffic off a corpse
and notice a resurrection.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.obs import trace
from pytorchvideo_accelerate_tpu.serving.batcher import QueueFullError
from pytorchvideo_accelerate_tpu.utils.logging import get_logger
from pytorchvideo_accelerate_tpu.utils.sync import (
    make_lock,
    make_thread,
    shared_state,
)

logger = get_logger("pva_tpu")

# /healthz states that keep a replica in the routable set: "degraded" is a
# replica WORKING as designed (shedding at its own door, still serving) —
# pulling it would turn one replica's overload into fleet capacity loss
ROUTABLE_STATES = ("healthy", "degraded")


class ReplicaDeadError(RuntimeError):
    """The replica cannot take (or finish) this request at the transport
    level — closed scheduler, refused/reset connection. The router treats
    it as a route-around signal, never a client-visible failure."""


class LocalReplica:
    """In-process replica: one engine behind one `Scheduler`."""

    def __init__(self, name: str, scheduler, stats=None,
                 model: Optional[str] = None):
        self.name = name
        self.scheduler = scheduler
        self.stats = stats if stats is not None else scheduler.stats
        # model family served here (multi-model routing key); defaults to
        # the engine's own identity when it declares one
        if model is None:
            try:
                model = getattr(scheduler.current_engine(), "model_name",
                                None)
            except Exception:
                model = None
        self.model = model
        self._draining = False

    def submit(self, clip, **kwargs) -> Future:
        try:
            inner = self.scheduler.submit(clip, **kwargs)
        except (QueueFullError, ValueError):
            raise  # shed (503) and bad-request (400) are not death
        except RuntimeError as e:  # closed scheduler = dead replica
            raise ReplicaDeadError(f"{self.name}: {e}") from e
        # a replica that dies AFTER accepting (close() fails its pending
        # futures) must surface as ReplicaDeadError so the router
        # re-dispatches instead of failing the client. Death is classified
        # by the SCHEDULER's closed latch, never by exception-message
        # sniffing — an engine bug whose text happens to contain "closed"
        # (jax buffer / file errors) must propagate untranslated.
        outer: Future = Future()

        def done(f, name=self.name):
            err = f.exception()
            try:
                if err is None:
                    outer.set_result(f.result())
                elif (isinstance(err, RuntimeError)
                      and not isinstance(err, QueueFullError)
                      and self.scheduler._closed.is_set()):
                    outer.set_exception(
                        ReplicaDeadError(f"{name}: {err}"))
                else:
                    outer.set_exception(err)
            except Exception:  # outer cancelled by the caller
                pass

        inner.add_done_callback(done)
        return outer

    def health(self) -> str:
        if self.scheduler._closed.is_set():
            return "dead"
        return "draining" if self._draining else "healthy"

    def drain(self) -> bool:
        """Scale-down actuator: report `draining` from here on, so the
        pool's poller removes this replica from the rotation within one
        health interval (the admission state machine's terminal state,
        mirrored for the in-process shape). Idempotent."""
        self._draining = True
        return True

    def queue_depth(self) -> int:
        try:
            return self.scheduler.queue_depth()
        except Exception:
            return 0

    def snapshot(self) -> Dict[str, float]:
        return self.stats.snapshot() if self.stats is not None else {}

    def close(self) -> None:
        self.scheduler.close()


class HttpReplica:
    """Process replica behind a `pva-tpu-serve`-style HTTP endpoint.

    `submit` returns a Future resolved by a small worker pool posting
    `/predict`; HTTP 503 resolves to `QueueFullError` (the shed contract,
    Retry-After honored), connection-level failures to `ReplicaDeadError`
    so the router can route around a SIGKILLed process."""

    def __init__(self, name: str, url: str, *, pid: Optional[int] = None,
                 timeout_s: float = 30.0, health_timeout_s: float = 2.0,
                 workers: int = 8, model: Optional[str] = None):
        self.name = name
        self.url = url.rstrip("/")
        self.pid = pid
        self.timeout_s = float(timeout_s)
        self.health_timeout_s = float(health_timeout_s)
        self.model = model  # multi-model routing key (None = unlabeled)
        self._pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix=f"pva-http-{name}")

    def _predict(self, clip, kwargs, ctx=None) -> np.ndarray:
        # the worker thread re-attaches the submitter's trace context (the
        # thread-pool hop would otherwise drop it) and wraps the whole
        # round trip in an `http_hop` span; the outgoing `traceparent`
        # header carries that span's id, so the server's `http_predict`
        # trace parents onto THIS hop in the merged cross-process timeline
        with trace.attach(ctx), trace.span("http_hop", replica=self.name):
            body = {k: np.asarray(v).tolist() for k, v in clip.items()}
            if kwargs.get("priority") is not None:
                body["priority"] = kwargs["priority"]
            if kwargs.get("deadline_ms") is not None:
                body["deadline_ms"] = float(kwargs["deadline_ms"])
            path = "/predict"
            session = kwargs.get("session")
            if session is not None:
                # streaming advance -> the replica's /stream endpoint;
                # the "video" clip carries the s new frames, the session
                # envelope the id (+ resendable window when the caller
                # chose to ship it — the re-establish-anywhere tradeoff,
                # docs/SERVING.md § streaming)
                path = "/stream"
                body["session"] = str(session.get("sid"))
                if session.get("window") is not None:
                    body["window"] = np.asarray(session["window"]).tolist()
                if session.get("stride"):
                    body["stride"] = int(session["stride"])
                if session.get("end"):
                    body["end"] = True
                body["frames"] = body.pop("video", None)
            headers = {"Content-Type": "application/json"}
            tp = trace.current_traceparent()
            if tp:
                headers["traceparent"] = tp
            req = urllib.request.Request(
                self.url + path, data=json.dumps(body).encode(),
                headers=headers)
            try:
                with urllib.request.urlopen(req,
                                            timeout=self.timeout_s) as r:
                    out = json.loads(r.read())
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    retry_after = float(e.headers.get("Retry-After", 1) or 1)
                    raise QueueFullError(f"{self.name}: shed (503)",
                                         retry_after_s=retry_after) from e
                if e.code == 400:
                    raise ValueError(f"{self.name}: bad request: "
                                     f"{e.read()[:200]!r}") from e
                if e.code == 409:
                    # streaming session unknown on this replica and no
                    # window rode along: the caller must resend its window
                    from pytorchvideo_accelerate_tpu.streaming.session import (
                        SessionUnknownError,
                    )

                    raise SessionUnknownError(
                        f"{self.name}: session unknown (409); resend "
                        "window") from e
                raise RuntimeError(f"{self.name}: HTTP {e.code}") from e
            except (urllib.error.URLError, ConnectionError, OSError) as e:
                raise ReplicaDeadError(f"{self.name}: {e}") from e
            return np.asarray(out["logits"], np.float32)

    def submit(self, clip, **kwargs) -> Future:
        # trace context captured HERE (the caller's thread) and shipped to
        # the worker with the payload — the capture/attach handoff pattern
        return self._pool.submit(self._predict, dict(clip), kwargs,
                                 trace.capture())

    def health(self) -> str:
        try:
            with urllib.request.urlopen(self.url + "/healthz",
                                        timeout=self.health_timeout_s) as r:
                return str(json.loads(r.read()).get("status", "healthy"))
        except urllib.error.HTTPError as e:
            if e.code == 503:  # draining replies 503 with a status body
                try:
                    return str(json.loads(e.read()).get("status", "draining"))
                except Exception:
                    return "draining"
            return "dead"
        except Exception:
            return "dead"

    def queue_depth(self) -> int:
        try:
            with urllib.request.urlopen(self.url + "/healthz",
                                        timeout=self.health_timeout_s) as r:
                return int(json.loads(r.read()).get("queue_depth", 0))
        except Exception:
            return 0

    def snapshot(self) -> Dict[str, float]:
        try:
            with urllib.request.urlopen(self.url + "/stats",
                                        timeout=self.health_timeout_s) as r:
                return {k: float(v) for k, v in json.loads(r.read()).items()
                        if isinstance(v, (int, float))}
        except Exception:
            return {}

    def drain(self) -> bool:
        """Scale-down actuator: flip the remote admission state machine to
        DRAINING via the server's POST /drain controller endpoint; the
        replica then 503s /healthz and the poller pulls it from the
        rotation. Returns False (never raises) on an unreachable replica —
        a dead victim needs no drain."""
        req = urllib.request.Request(
            self.url + "/drain", data=b"{}", method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(
                    req, timeout=self.health_timeout_s) as r:
                return bool(json.loads(r.read()).get("draining", True))
        except Exception:
            return False

    def close(self) -> None:
        self._pool.shutdown(wait=False)


@shared_state("_down", benign={
    "_closed": "monotonic shutdown latch; poller polls it, a torn read of "
               "a bool is impossible and the worst case is one extra poll"})
class ReplicaPool:
    """Health-gated replica membership + the poller that maintains it."""

    def __init__(self, replicas: Sequence, *, health_interval_s: float = 0.5,
                 registry=None, name: str = "fleet",
                 on_change: Optional[Callable[[str, bool], None]] = None):
        if not replicas:
            raise ValueError("a replica pool needs at least one replica")
        self.replicas: List = list(replicas)
        self.name = name
        self.health_interval_s = max(float(health_interval_s), 0.01)
        self.on_change = on_change
        self._lock = make_lock("ReplicaPool._lock")
        self._down: frozenset = frozenset()
        self._closed = False
        reg = registry if registry is not None else obs.get_registry()
        # labeled per pool: two pools on one registry (a test harness
        # plus an app fleet) must not fight over one callback slot. close()
        # deregisters THIS pool's label — otherwise the registry closure
        # would pin a closed pool alive and scrape stale membership forever
        self._g_healthy = reg.gauge(
            "pva_fleet_healthy_replicas",
            "replicas currently in the routable set, by pool",
            labelnames=("pool",))
        self._g_healthy.set_function(
            lambda: float(len(self.routable())), pool=self.name)
        self._poller = make_thread(target=self._poll_loop,
                                   name="pva-fleet-health", daemon=True)
        self._poller.start()

    # --- membership -------------------------------------------------------

    def routable(self) -> List:
        with self._lock:
            down = self._down
        return [r for r in self.replicas if r.name not in down]

    def add_replica(self, replica) -> None:
        """Controller actuator (autoscaler scale-up): join the rotation.
        The new member is routable immediately — a fresh spawn already
        passed its bind-line handshake; the poller takes over from here."""
        with self._lock:
            if any(r.name == replica.name for r in self.replicas):
                raise ValueError(f"replica {replica.name!r} already pooled")
            # rebind a copy: the poller and routable() iterate snapshots,
            # so membership flips atomically under the lock
            self.replicas = self.replicas + [replica]
            self._down = frozenset(self._down - {replica.name})
        logger.info("fleet: replica %s joined the pool", replica.name)
        obs.get_recorder().record("fleet", "membership",
                                  replica=replica.name, joined=True)

    def remove_replica(self, replica, *, close: bool = True) -> None:
        """Controller actuator (autoscaler reap): leave the pool for good.
        Unlike `mark_down` this is not a health verdict the poller can
        revert — the replica is gone from membership entirely."""
        with self._lock:
            self.replicas = [r for r in self.replicas
                             if r.name != replica.name]
            self._down = frozenset(self._down - {replica.name})
        logger.info("fleet: replica %s removed from the pool", replica.name)
        obs.get_recorder().record("fleet", "membership",
                                  replica=replica.name, removed=True)
        if close:
            try:
                replica.close()
            except Exception:
                logger.exception("fleet: closing replica %s failed",
                                 replica.name)

    def mark_down(self, replica) -> None:
        """Router-observed death: leave the rotation NOW (the poller would
        take up to one interval to notice); the poller restores membership
        if the replica's health probe recovers."""
        self._set_down(replica.name, True)

    def _set_down(self, name: str, down: bool) -> None:
        changed = False
        with self._lock:
            new = (self._down | {name}) if down else (self._down - {name})
            if new != self._down:
                self._down = frozenset(new)
                changed = True
        if changed:
            logger.warning("fleet: replica %s %s", name,
                           "left the routable set" if down else "rejoined")
            obs.get_recorder().record(
                "fleet", "membership", replica=name,
                routable=not down)
            if self.on_change is not None:
                try:
                    self.on_change(name, not down)
                except Exception:  # observer must not break routing
                    pass

    def _poll_loop(self) -> None:
        while not self._closed:
            for replica in list(self.replicas):
                if self._closed:
                    return
                try:
                    state = replica.health()
                except Exception:  # a broken probe reads as dead
                    state = "dead"
                self._set_down(replica.name, state not in ROUTABLE_STATES)
            time.sleep(self.health_interval_s)

    def close(self) -> None:
        self._closed = True
        self._poller.join(timeout=5.0)
        # drop the registry's closure over this pool: a closed pool has
        # zero routable replicas and must not be kept alive by /metrics
        self._g_healthy.set_function(None, pool=self.name)
        self._g_healthy.set(0.0, pool=self.name)
        for replica in self.replicas:
            try:
                replica.close()
            except Exception:
                logger.exception("fleet: closing replica %s failed",
                                 replica.name)


def read_line_with_deadline(proc, timeout_s: float, *,
                            match: Optional[str] = None,
                            name: str = "pva-proc-read"):
    """First stdout line of a child process — the first containing `match`
    when given — within a deadline, via a daemon reader thread.

    `readline()` blocks forever, so a child that wedges BEFORE printing
    its bind/URL line would otherwise hang the caller past any timeout.
    One implementation for every spawn site (`spawn_serving_process`, the
    chaos replica_kill leg) so the
    wedge-safe protocol cannot drift between them. Returns `(line, eof)`:
    line None on deadline or EOF, eof True when the child's stdout closed
    without the wanted line (a died-or-redirected child, NOT a timeout —
    callers must report the two differently). The CALLER owns the error
    message and the kill."""
    box: dict = {}

    def read():
        for raw in proc.stdout:
            if match is None or match in raw:
                box["line"] = raw
                return
        box["eof"] = True

    reader = make_thread(target=read, name=name, daemon=True)
    reader.start()
    reader.join(timeout=timeout_s)
    return box.get("line"), bool(box.get("eof"))


def spawn_serving_process(artifact: str, *, port: int = 0,
                          n_devices: Optional[int] = None,
                          extra_args: Sequence[str] = (),
                          startup_timeout_s: float = 120.0):
    """Spawn one `pva-tpu-serve` process for `artifact` and return
    `(subprocess.Popen, HttpReplica)` once it reports its bound address.

    `n_devices` forces a CPU slice via `utils/forcehost.py` — the CI path
    for exercising process replicas on one host. Without it the child
    inherits the environment as it is and takes whatever backend JAX
    finds there (its first stdout line and `/healthz` say which). The
    CALLER owns the process (terminate/kill + reap); the pool only routes
    around it."""
    from pytorchvideo_accelerate_tpu.utils.forcehost import forced_host_env

    env = forced_host_env(n_devices) if n_devices else None
    cmd = [sys.executable, "-m", "pytorchvideo_accelerate_tpu.serving.server",
           "--serve.checkpoint", artifact, "--serve.port", str(port),
           *extra_args]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)
    line, eof = read_line_with_deadline(proc, startup_timeout_s,
                                        match="pva-tpu-serve: http://",
                                        name="pva-fleet-spawn-read")
    if line is None:
        code = proc.poll()
        proc.kill()
        raise RuntimeError(
            f"serving process exited {code} before binding"
            if eof or code is not None
            else f"serving process did not bind within {startup_timeout_s}s")
    url = line.split()[1]
    return proc, HttpReplica(f"proc-{proc.pid}", url, pid=proc.pid)

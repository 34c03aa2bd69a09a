"""RemoteClipFeed: the trainer-side half of the disaggregated data plane.

Slots in exactly where `ClipLoader`'s local iterator feeds the
`DevicePrefetcher` today: same `epoch_items()` contract ((batch,
LoaderState) pairs, post-CONSUMPTION state, a final (None, rollover)
marker), same `state` surface — but the decode+transform work happens in N
remote worker processes (dataplane/worker.py) instead of this host's
thread pool.

Determinism stays centralized (the Podracer split: decoupled actors, one
learner-owned curriculum): THIS side computes `_epoch_indices` (shuffle +
quarantine substitution) through the wrapped ClipLoader and leases each
batch's explicit index chunk to a worker; workers never sample. A batch is
therefore byte-identical no matter which worker decodes it — or whether
the local loader does — so checkpoints keep recording the consumed
position and mid-epoch resume works unchanged.

Credit-based back-pressure, two composed bounds (`_pump_locked`): each
worker holds at most `credits` UNRECEIVED leases (worker memory — a credit
frees when the batch lands back here), and leases are only granted inside
the window `[next_yield, next_yield + credits x workers)` (the trainer-side
reorder buffer — the window only advances when the trainer consumes). A
slow trainer therefore idles the whole plane at a hard `credits x workers`
bound and can never balloon worker memory (asserted non-vacuously in
tests/test_zdataplane.py); anchoring the window at the batch the consumer
is waiting for is also what makes worker death deadlock-free — the
re-leased head span is always grantable.

Worker death re-leases: a reader thread that loses its socket returns the
worker's un-received spans to the front of the lease queue and surviving
workers pick them up — zero duplicate, zero missing batches (chaos leg 13
SIGKILLs a worker mid-epoch and diffs the stream). Already-received
batches are kept, not re-decoded. ``qreport`` frames land in the trainer's
persisted `Quarantine` sidecar — a remote decode failure quarantines
exactly like a local one.

Tracing: the consumer's context is captured at epoch start and every lease
carries its W3C traceparent, so worker-side decode spans join the trainer's
trace across the process boundary (the PR 10 propagation contract; the
trace-propagation lint rule covers this module's send sites).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from collections import deque
from typing import Dict, Iterator, List, Optional, Tuple

from pytorchvideo_accelerate_tpu.obs import trace
from pytorchvideo_accelerate_tpu.data.pipeline import ClipLoader, LoaderState
from pytorchvideo_accelerate_tpu.dataplane.wire import (
    WireError,
    recv_frame,
    send_frame,
)
from pytorchvideo_accelerate_tpu.utils.sync import (
    make_condition,
    make_lock,
    make_thread,
    shared_state,
)


class RemoteDecodeFailure(IOError):
    """A quarantine verdict reported over the wire (sidecar evidence)."""


class NoWorkersError(ConnectionError):
    """Every decode worker disconnected while batches were outstanding."""


class _Worker:
    """Feed-side record of one connected worker. All mutable fields are
    guarded by the feed's condition; `send_lock` alone serializes frame
    writes (leases vs stop) on the socket."""

    __slots__ = ("wid", "sock", "pid", "outstanding", "send_lock", "alive",
                 "thread")

    def __init__(self, wid: int, sock: socket.socket, pid: int):
        self.wid = wid
        self.sock = sock
        self.pid = pid
        self.outstanding: set = set()  # (gen, batch_index) leased, unreceived
        self.send_lock = make_lock("RemoteClipFeed._Worker.send_lock")
        self.alive = True
        self.thread = None


# every spawned worker process, for emergency reaping: a harness that
# abandons a wedged feed (a timeout) must be able to kill the
# orphans rather than let them burn CPU under whatever runs next
_SPAWNED: List[subprocess.Popen] = []
_SPAWNED_LOCK = make_lock("dataplane.feed._SPAWNED_LOCK")


def spawn_worker(address: Tuple[str, int],
                 decode_threads: int = 2) -> subprocess.Popen:
    """Launch one `pva-tpu-dataworker` process pointed at `address`.
    stderr inherits (worker warnings are operator evidence); the worker
    never touches jax, but JAX_PLATFORMS=cpu rides along so a transitive
    import in a future transform can't grab the accelerator."""
    host, port = address
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pytorchvideo_accelerate_tpu.dataplane.worker",
         "--connect", f"{host}:{port}", "--threads", str(decode_threads)],
        env=env, stdin=subprocess.DEVNULL)
    with _SPAWNED_LOCK:
        _SPAWNED.append(proc)
    return proc


def reap_spawned_workers() -> int:
    """SIGKILL every still-running spawned worker process; returns how
    many were killed. For harnesses that gave up on a feed from OUTSIDE
    (a lane timeout) — a normal `RemoteClipFeed.close()` already waits
    for its own processes."""
    with _SPAWNED_LOCK:
        procs, _SPAWNED[:] = list(_SPAWNED), []
    killed = 0
    for p in procs:
        if p.poll() is None:
            p.kill()
            try:
                p.wait(timeout=5.0)
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass
            killed += 1
    return killed


@shared_state("_workers", "_unleased", "_pending", "_done", "_next_yield",
              "_error", "_gen", "_epoch", "_indices", "_spy", "_closing",
              "_lease_traceparent", "consumed", "received",
              "releases", "workers_lost", "qreports")
class RemoteClipFeed:
    """Lease coordinator + reorder buffer over N remote decode workers.

    `loader` supplies geometry, epoch indices, and the checkpointable
    `LoaderState`; its thread pool never decodes while the feed is in
    charge. `spawn` launches that many local worker processes (the
    single-host CI shape); additional external `pva-tpu-dataworker`
    processes may connect to `address` at any time and join the rotation
    mid-epoch — that is the horizontal-scale path.
    """

    def __init__(self, loader: ClipLoader, source_spec: dict,
                 spawn: int = 0, listen: Tuple[str, int] = ("127.0.0.1", 0),
                 credits: int = 2, quarantine=None,
                 trace_config: Optional[dict] = None,
                 decode_threads: int = 2,
                 connect_timeout_s: float = 120.0,
                 batch_timeout_s: float = 300.0):
        if credits < 1:
            raise ValueError(f"credits must be >= 1, got {credits}")
        self.loader = loader
        self.source_spec = source_spec
        self.credits = int(credits)
        self.quarantine = quarantine
        self.trace_config = trace_config or {}
        self.decode_threads = int(decode_threads)
        self.batch_timeout_s = batch_timeout_s
        self.consumed = 0
        self.received = 0
        self.releases = 0     # spans re-leased after a worker death
        self.workers_lost = 0
        self.qreports: List[dict] = []
        self._cond = make_condition("RemoteClipFeed._cond")
        self._workers: Dict[int, _Worker] = {}
        self._next_wid = 0
        self._unleased: deque = deque()
        self._pending: Dict[int, _Worker] = {}   # batch index -> worker
        self._done: Dict[int, tuple] = {}        # batch index -> (batch, wid)
        self._next_yield = 0
        self._error: Optional[BaseException] = None
        self._gen = 0        # epoch-pass generation; stale frames dropped
        self._epoch = 0
        self._indices = None
        self._spy = 0
        self._lease_traceparent: Optional[str] = None
        self._closing = False

        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(listen)
        self._listener.listen(16)
        # accept() with a poll timeout: closing a listener's fd does NOT
        # wake a thread blocked in accept() on Linux, so a blocking accept
        # would pin close() for its full join timeout
        self._listener.settimeout(0.2)
        self.address: Tuple[str, int] = self._listener.getsockname()[:2]
        self._accept_thread = make_thread(
            target=self._accept_loop, name="dataplane-accept", daemon=True)
        self._accept_thread.start()
        self._procs: List[subprocess.Popen] = [
            spawn_worker(self.address, self.decode_threads)
            for _ in range(int(spawn))]
        if spawn:
            try:
                self.wait_for_workers(int(spawn), timeout=connect_timeout_s)
            except TimeoutError:
                self.close()  # no spawned orphans on a failed construction
                raise

    # --- ClipLoader surface (what DevicePrefetcher and the trainer use) ------

    @property
    def state(self) -> LoaderState:
        return self.loader.state

    @state.setter
    def state(self, value: LoaderState) -> None:
        self.loader.state = value

    @property
    def global_batch_size(self) -> int:
        return self.loader.global_batch_size

    @property
    def local_batch_size(self) -> int:
        return self.loader.local_batch_size

    @property
    def accum_steps(self) -> int:
        return self.loader.accum_steps

    @property
    def samples_per_yield(self) -> int:
        return self.loader.samples_per_yield

    def batches_per_epoch(self) -> int:
        return self.loader.batches_per_epoch()

    def steps_per_epoch(self) -> int:
        return self.loader.steps_per_epoch()

    # --- membership ----------------------------------------------------------

    def wait_for_workers(self, n: int, timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        with self._cond:
            while len(self._workers) < n:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"only {len(self._workers)}/{n} decode workers "
                        f"connected within {timeout}s")
                self._cond.wait(timeout=min(left, 0.2))

    def worker_count(self) -> int:
        with self._cond:
            return len(self._workers)

    def stats(self) -> dict:
        """Doctor/bench/chaos view of the credit machinery."""
        with self._cond:
            return {
                "workers": {w.wid: {"pid": w.pid,
                                    "outstanding": len(w.outstanding)}
                            for w in self._workers.values()},
                "consumed": self.consumed,
                "received": self.received,
                "releases": self.releases,
                "workers_lost": self.workers_lost,
                "unleased": len(self._unleased),
                "buffered": len(self._done),
                "credits": self.credits,
                "qreports": list(self.qreports),
            }

    def _accept_loop(self) -> None:
        while True:
            try:
                sock, _addr = self._listener.accept()
            except socket.timeout:
                with self._cond:
                    if self._closing:
                        return
                continue
            except OSError:
                return  # listener closed: feed shutting down
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.settimeout(30.0)
                hello = recv_frame(sock)
                if hello is None or hello.kind != "hello":
                    sock.close()
                    continue
                send_frame(sock, "config", {
                    "spec": self.source_spec,
                    "batch": {
                        "samples_per_yield": self.loader.samples_per_yield,
                        "local_batch_size": self.loader.local_batch_size,
                        "accum_steps": self.loader.accum_steps,
                    },
                    "trace": self.trace_config,
                })
                # blocking reads from here on: an idle inter-epoch gap (or
                # a long eval) must not time a healthy worker out; close()
                # unblocks the reader by closing the socket
                sock.settimeout(None)
            except (WireError, OSError):
                try:
                    sock.close()
                except OSError:
                    pass
                continue
            with self._cond:
                if self._closing:
                    sock.close()
                    return
                wid = self._next_wid
                self._next_wid += 1
                w = _Worker(wid, sock, int(hello.meta.get("pid", -1)))
                self._workers[wid] = w
                to_send = self._pump_locked()  # a late joiner starts leasing
                self._cond.notify_all()
            w.thread = make_thread(target=self._reader, args=(w,),
                                   name=f"dataplane-reader-{wid}",
                                   daemon=True)
            w.thread.start()
            self._send_leases(to_send)

    # --- reader threads ------------------------------------------------------

    def _reader(self, w: _Worker) -> None:
        try:
            while True:
                fr = recv_frame(w.sock, allow_eof=True)
                if fr is None:
                    break
                if fr.kind == "batch":
                    self._on_batch(w, fr)
                elif fr.kind == "qreport":
                    self._on_qreport(w, fr)
                elif fr.kind == "error":
                    with self._cond:
                        if fr.meta.get("gen") == self._gen:
                            self._error = IOError(
                                fr.meta.get("message", "remote decode error"))
                            self._cond.notify_all()
                # hello/unknown: ignore
        except (WireError, OSError, socket.timeout):
            pass
        except (KeyError, TypeError, ValueError):
            # a wire-VALID frame with malformed meta (version skew, a
            # hostile worker): same posture as protocol corruption — drop
            # the peer cleanly, never die with an unhandled traceback
            pass
        finally:
            self._on_worker_gone(w)

    def _on_batch(self, w: _Worker, fr) -> None:
        b = int(fr.meta["index"])
        with self._cond:
            if fr.meta.get("gen") != self._gen or (self._gen, b) not in \
                    w.outstanding:
                # stale: an aborted pass's leftovers, or a span that was
                # re-leased away — drop it (the slot was already returned
                # when the span left `outstanding`)
                return
            w.outstanding.discard((self._gen, b))
            # the frame's backing bytearray is exclusively this batch's
            # (one recv buffer per frame), so the array views are safely
            # owned by the reorder buffer — no defensive copy
            self._done[b] = (fr.arrays, w.wid)
            self._pending.pop(b, None)
            self.received += 1
            to_send = self._pump_locked()  # receipt freed a worker slot
            self._cond.notify_all()
        # tracing: record the cross-process hop under the lease's context
        tracer = trace.get_tracer()
        if tracer is not None and fr.traceparent:
            handle = tracer.continue_trace(fr.traceparent, "remote_batch",
                                           epoch=fr.meta.get("epoch"),
                                           batch=b, worker=w.wid)
            if handle is not None:
                handle.finish()
        self._send_leases(to_send)

    def _on_qreport(self, w: _Worker, fr) -> None:
        path = str(fr.meta.get("path", ""))
        err = str(fr.meta.get("error", ""))
        with self._cond:
            self.qreports.append({"worker": w.wid, "pid": w.pid,
                                  "path": path, "error": err})
        if self.quarantine is not None and path:
            # same persisted sidecar, same budget, same counter as a local
            # decode failure (data/manifest.Quarantine)
            self.quarantine.record(path, RemoteDecodeFailure(err))

    def _on_worker_gone(self, w: _Worker) -> None:
        to_send: list = []
        with self._cond:
            if not w.alive:
                return
            w.alive = False
            self._workers.pop(w.wid, None)
            returned = sorted(b for gen, b in w.outstanding
                              if gen == self._gen)
            w.outstanding.clear()
            if not self._closing:
                for b in returned:
                    self._pending.pop(b, None)
                # MERGE, don't prepend: after two deaths in a row the
                # returned spans can interleave with previously-returned
                # ones (A held {2,5}, B held {3,4}), and the pump's
                # head-of-deque window check requires `_unleased` to stay
                # ascending — a bare appendleft could bury the span the
                # consumer is waiting for behind a larger head and stall
                # the pass until timeout
                self._unleased = deque(
                    sorted(set(returned).union(self._unleased)))
                self.releases += len(returned)
                self.workers_lost += 1
                if not self._workers and (self._unleased or self._pending):
                    self._error = NoWorkersError(
                        "all decode workers disconnected with "
                        f"{len(self._unleased)} span(s) outstanding")
                to_send = self._pump_locked()
            self._cond.notify_all()
        try:
            w.sock.close()
        except OSError:
            pass
        self._send_leases(to_send)

    # --- leasing -------------------------------------------------------------

    def _pump_locked(self) -> List[tuple]:
        """Assign unleased spans to workers with free credits (least-loaded
        first), within the lease WINDOW. Called under the condition; returns
        (worker, frame-kwargs) pairs for the caller to SEND outside the lock
        — a slow socket must never stall the reader threads.

        Two bounds compose here, and their split is what makes the design
        deadlock-free:

        - per-worker `credits` caps UNRECEIVED leases — worker memory.
          A credit frees at RECEIPT (a live worker always delivers, so a
          slot always comes back without the trainer's help);
        - the window `[next_yield, next_yield + credits x workers)` caps
          leased-but-unconsumed spans — the trainer-side reorder buffer.
          It only advances when the consumer consumes, so a stalled
          trainer idles the whole plane at a hard bound.

        Because the window is anchored at `next_yield`, the batch the
        consumer is waiting for is ALWAYS leasable — a worker death can
        never strand it behind survivors saturated with later spans (the
        head-of-line deadlock a consumption-released credit would allow).
        """
        to_send: List[tuple] = []
        if self._indices is None or not self._workers:
            return to_send
        window_end = self._next_yield + self.credits * len(self._workers)
        # `_unleased` stays ascending (built ascending; death MERGES spans
        # back in sorted order), so the head is always the smallest — one
        # check bounds the whole deque
        while self._unleased and self._unleased[0] < window_end:
            candidates = [w for w in self._workers.values()
                          if w.alive and len(w.outstanding) < self.credits]
            if not candidates:
                break
            w = min(candidates, key=lambda x: len(x.outstanding))
            b = self._unleased.popleft()  # pva: disable=lock-discipline -- _pump_locked is only ever called with self._cond held (the _locked suffix contract)
            w.outstanding.add((self._gen, b))
            self._pending[b] = w  # pva: disable=lock-discipline -- _pump_locked is only ever called with self._cond held (the _locked suffix contract)
            chunk = self._indices[b * self._spy:(b + 1) * self._spy]
            to_send.append((w, {
                "kind": "lease",
                "meta": {"epoch": self._epoch, "index": b, "gen": self._gen,
                         "indices": [int(i) for i in chunk]},
                "traceparent": self._lease_traceparent,
            }))
        return to_send

    def _send_leases(self, to_send: List[tuple]) -> None:
        for w, kw in to_send:
            try:
                with w.send_lock:
                    send_frame(w.sock, kw["kind"], kw["meta"],
                               traceparent=kw.get("traceparent"))
            except OSError:
                # the reader thread will notice the dead socket and
                # re-lease; double handling here would race it
                pass

    # --- iteration -----------------------------------------------------------

    def epoch(self, epoch: Optional[int] = None,
              from_start: bool = False) -> Iterator[dict]:
        """ClipLoader.epoch() twin (host batches, state honored/updated)."""
        for batch, state in self.epoch_items(epoch, from_start):
            self.loader.state = state
            if batch is not None:
                yield batch

    def epoch_items(self, epoch: Optional[int] = None,
                    from_start: bool = False) -> Iterator[tuple]:
        """The DevicePrefetcher contract, verbatim from ClipLoader: (batch,
        post-consumption LoaderState) pairs in exact batch order, a final
        (None, rollover) marker, `self.loader.state` never mutated here."""
        start_state = self.loader._start_state(epoch, from_start)
        epoch = start_state.epoch
        indices = self.loader._epoch_indices(epoch)
        n_batches = self.loader.batches_per_epoch()
        start = start_state.position
        # capture the consumer's trace context ONCE per pass: every lease
        # ships it as a traceparent so remote decode spans join the trace
        ctx = trace.capture()
        with self._cond:
            self._lease_traceparent = (
                trace.format_traceparent(ctx) if ctx is not None else None)
            self._gen += 1
            gen = self._gen
            self._epoch = epoch
            self._indices = indices
            self._spy = self.loader.samples_per_yield
            self._unleased = deque(range(start, n_batches))
            self._done.clear()
            self._pending.clear()
            self._next_yield = start
            self._error = None
            for w in self._workers.values():
                # an aborted previous pass's leases are stale: the readers
                # drop their frames by generation, and the slots free here
                w.outstanding = {o for o in w.outstanding if o[0] == gen}
            to_send = self._pump_locked()
        self._send_leases(to_send)
        try:
            b = start
            while b < n_batches:
                deadline = time.monotonic() + self.batch_timeout_s
                with self._cond:
                    while b not in self._done and self._error is None:
                        left = deadline - time.monotonic()
                        if left <= 0:
                            raise WireError(
                                f"no decode worker delivered batch {b} "
                                f"within {self.batch_timeout_s}s "
                                f"({len(self._workers)} worker(s) "
                                "connected)")
                        self._cond.wait(timeout=min(left, 0.2))
                    if self._error is not None:
                        raise self._error
                    batch, _wid = self._done.pop(b)
                    self._next_yield = b + 1
                    self.consumed += 1
                    to_send = self._pump_locked()  # the window advanced
                self._send_leases(to_send)
                yield batch, LoaderState(epoch=epoch, position=b + 1)
                b += 1
            yield None, LoaderState(epoch=epoch + 1, position=0)
        finally:
            # early exit (limit_train_batches break, an upstream
            # exception): invalidate the pass — readers drop stale batch
            # frames by generation, credits reset at the next pass start
            with self._cond:
                self._gen += 1
                self._unleased.clear()
                self._done.clear()
                self._pending.clear()

    # --- teardown ------------------------------------------------------------

    def close(self, timeout: float = 10.0) -> None:
        """Stop workers, close sockets, reap spawned processes. Idempotent;
        the wrapped loader stays usable (the trainer closes it itself)."""
        with self._cond:
            if self._closing:
                return
            self._closing = True
            if self._error is None:
                # release a consumer blocked mid-pass NOW: without this a
                # close() racing an active epoch (trainer crash teardown)
                # would leave the prefetcher thread waiting out the full
                # batch_timeout_s before noticing the world ended
                self._error = NoWorkersError("feed closed mid-pass")
            workers = list(self._workers.values())
            self._cond.notify_all()
        try:
            self._listener.close()
        except OSError:
            pass
        for w in workers:
            try:
                with w.send_lock:
                    send_frame(w.sock, "stop")
            except OSError:
                pass
            try:
                # shutdown (not just close): it is the call that actually
                # wakes a reader thread blocked in recv on this socket
                w.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                w.sock.close()
            except OSError:
                pass
        for w in workers:
            if w.thread is not None:
                w.thread.join(timeout=timeout)
        deadline = time.monotonic() + timeout
        for p in self._procs:
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5.0)
        self._accept_thread.join(timeout=timeout)

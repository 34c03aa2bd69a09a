"""Device-side batch prefetch: overlap host→HBM transfer with compute.

The reference stack hides host→device latency inside torch's pinned-memory
DataLoader + DDP machinery; the TPU-native rewrite owns that slice here. A
`DevicePrefetcher` sits between the host `ClipLoader` and the step loop: a
background thread advances `ClipLoader.epoch_items()`, places each numpy
batch on the mesh (`parallel.sharding.shard_batch` — cached `NamedSharding`,
`device_put` single-process / `make_array_from_process_local_data`
multi-host), and holds a bounded ring of at most `depth` on-device batches,
so the H2D copy of batch N+1 (tens of MB of video at reference geometry)
runs while the accelerator computes batch N. Without it, every step pays the
full PCIe/host-link transfer synchronously between dispatches — the
first-order throughput lever on TPU is simply never letting the chip wait on
the host (Podracer; "Scalable Training of LMs with pjit and TPUv4").

Contracts, in order of importance:

- **Exact batch order.** The queue is strictly FIFO from a single producer;
  the consumer sees precisely the sequence `ClipLoader.epoch()` would yield.
- **LoaderState resume semantics.** `epoch_items()` never mutates
  `loader.state`; each batch carries its post-consumption `LoaderState`, and
  the prefetcher assigns it back to the loader only when the trainer takes
  the batch. A mid-epoch checkpoint therefore records the *consumed*
  position, never a position several prefetched batches ahead (which would
  make resume silently skip data).
- **Bounded residency.** A counting semaphore caps placed-but-unconsumed
  batches at `depth`: HBM cost is `depth` extra batches, never "however far
  the host got ahead".
- **Deterministic shutdown.** Early `break` (limit_train_batches), an
  exception in the step loop, or generator close all reach the same
  `finally`: stop flag set, worker joined, source generator closed (which
  cancels the host loader's in-flight decode futures). Worker-side
  exceptions cross the queue and re-raise in the consumer.
- **Observability.** The consumer's blocking wait for the next device batch
  is the `input_wait` span (obs/spans.py: a start, a parent, the step id,
  on the profiler's clock); the same clock reads accumulate into `wait_s`,
  which `pop_wait()` drains. `ready()` is the ring's fill when asked.
  The trainer divides by the epoch's train-section wall time to report
  `input_wait_frac` (≪ 1 proves the overlap is real; → 1 means the input
  pipeline, not the model, bounds throughput).

`depth=0` degrades to synchronous inline placement (the pre-prefetch
behavior) while keeping the same interface and wait accounting — the A/B
lever, and the fallback if a backend misbehaves under threaded `device_put`.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Iterator, Optional

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.obs import memory as obs_memory
from pytorchvideo_accelerate_tpu.obs import trace
from pytorchvideo_accelerate_tpu.data.pipeline import ClipLoader
from pytorchvideo_accelerate_tpu.parallel.sharding import shard_batch
from pytorchvideo_accelerate_tpu.reliability.faults import fault_point
from pytorchvideo_accelerate_tpu.utils.sync import (
    make_lock,
    make_queue,
    make_thread,
    shared_state,
)

_SENTINEL_POLL_S = 0.05  # stop-flag poll cadence for blocking waits
_JOIN_TIMEOUT_S = 10.0


@shared_state("wait_s", "_resident", "max_resident")
class DevicePrefetcher:
    """Bounded background H2D pipeline over one `ClipLoader`.

    One instance per loader (train and val each get their own); `epoch()`
    mirrors `ClipLoader.epoch()`'s signature so the step loop swaps in
    without other changes, but yields mesh-placed device batches.
    """

    def __init__(
        self,
        loader: ClipLoader,
        mesh: Any,
        depth: int = 2,
        micro_dim: bool = False,
        watchdog: Optional[Any] = None,
        watchdog_name: str = "prefetch",
        wait_name: str = "input_wait",
        h2d_name: str = "h2d",
    ):
        if depth < 0:
            raise ValueError(f"device prefetch depth must be >= 0, got {depth}")
        self.loader = loader
        self.mesh = mesh
        self.depth = depth
        self.micro_dim = micro_dim
        self.wait_s = 0.0  # consumer time blocked on the next device batch
        # telemetry spine (obs/): the consumer wait is the `wait_name`
        # span ("input_wait" train / "eval_input_wait" val — the latter
        # nests inside the "eval" span); worker-side placement is the
        # `h2d_name` span ("h2d" train / "eval_h2d" val, kept apart so the
        # per-train-step obs_h2d_s never counts eval placements); the
        # worker pings the watchdog per placed batch and deregisters when
        # the epoch generator closes (idle != stalled).
        self.watchdog = watchdog
        self.watchdog_name = watchdog_name
        self.wait_name = wait_name
        self.h2d_name = h2d_name
        self._lock = make_lock("DevicePrefetcher._lock")
        self._resident = 0  # placed-but-unconsumed device batches
        self.max_resident = 0  # high-water mark (tests; monotonic per run)
        # pva-tpu-hbm: ledger component for the ring's HBM residency —
        # MEASURED placed-batch bytes (register on enqueue, release on
        # consumption/drain), never a depth×estimate. wait_name keys the
        # component so train/val prefetchers account separately.
        self._mem_component = f"prefetch_ring:{self.wait_name}"

    # --- observability ----------------------------------------------------

    def ready(self) -> int:
        """Device batches placed (or being placed) and not yet consumed:
        `depth` is a full ring, 0 a consumer that is about to wait."""
        with self._lock:
            return self._resident

    def pop_wait(self) -> float:
        """Accumulated input-wait seconds since the last call (epoch-scoped
        accounting in the trainer)."""
        w, self.wait_s = self.wait_s, 0.0
        return w

    # --- placement --------------------------------------------------------

    def _place(self, batch: dict) -> Any:
        # chaos hook: "delay" here IS the slow-worker scenario (a starved
        # host link); "raise" crosses the queue and re-raises in the
        # consumer like any real placement failure. Disarmed: one global
        # read (reliability/faults.py).
        fault_point("prefetch.h2d")
        with obs.span(self.h2d_name):
            return shard_batch(self.mesh, batch, micro_dim=self.micro_dim)

    # --- iteration --------------------------------------------------------

    def epoch(self, epoch: Optional[int] = None,
              from_start: bool = False) -> Iterator[Any]:
        """Yield device-placed batches for one epoch, prefetched `depth`
        ahead; `loader.state` tracks the consumed position exactly as the
        plain host iteration would."""
        if self.depth == 0:
            return self._epoch_sync(epoch, from_start)
        # trace handoff (obs/trace.py): capture the caller's context HERE,
        # when the epoch is asked for, so the worker's h2d spans join
        # whatever trace was active then (disarmed: one global read, ctx
        # stays None) — not inside the generator, whose body first runs
        # under the first iteration's own trace root
        return self._epoch_prefetched(epoch, from_start, trace.capture())

    def _epoch_prefetched(self, epoch: Optional[int], from_start: bool,
                          ctx) -> Iterator[Any]:
        q: "queue.Queue[tuple]" = make_queue()  # bounded by `slots`, not maxsize
        stop = threading.Event()
        slots = threading.Semaphore(self.depth)
        items = self.loader.epoch_items(epoch, from_start)
        worker = make_thread(
            target=self._worker,
            args=(items, q, stop, slots, ctx),
            name="device-prefetch", daemon=True,
        )
        worker.start()
        try:
            while True:
                with obs.timed_span(self.wait_name) as wait:
                    kind, payload, state = q.get()
                self.wait_s += wait.dur_s
                if kind == "batch":
                    with self._lock:
                        self._resident -= 1
                    slots.release()
                    # ownership transfers to the step loop: the ring's
                    # residency accounting drops the batch here
                    obs_memory.release(self._mem_component,
                                       obs_memory.tree_nbytes(payload))
                    self.loader.state = state
                    yield payload
                elif kind == "state":  # epoch rollover marker
                    self.loader.state = state
                elif kind == "error":
                    raise payload
                else:  # "done"
                    return
        finally:
            stop.set()
            worker.join(timeout=_JOIN_TIMEOUT_S)
            # drop queued device batches so their HBM frees promptly
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            with self._lock:
                self._resident = 0
            # drained batches free on the floor above; zero the component so
            # a worker that out-raced the drain can't leave phantom bytes
            obs_memory.release(self._mem_component)

    def _epoch_sync(self, epoch: Optional[int],
                    from_start: bool) -> Iterator[Any]:
        """depth=0: inline blocking placement (the A/B baseline). The wait
        metric keeps its meaning — time the step loop spends blocked getting
        the next batch onto the device — so input_wait_frac stays comparable
        across modes."""
        try:
            for batch, state in self.loader.epoch_items(epoch, from_start):
                if batch is None:
                    self.loader.state = state
                    continue
                with obs.timed_span(self.wait_name) as wait:
                    placed = self._place(batch)
                self.wait_s += wait.dur_s
                if self.watchdog is not None:
                    self.watchdog.heartbeat(self.watchdog_name)
                self.loader.state = state
                yield placed
        finally:
            # mirror the threaded worker: a finished epoch is idle, not
            # stalled — a stale beat would false-fire every inter-epoch gap
            if self.watchdog is not None:
                self.watchdog.clear(self.watchdog_name)

    def _worker(self, items: Iterator[tuple], q: "queue.Queue[tuple]",
                stop: threading.Event, slots: threading.Semaphore,
                ctx=None) -> None:
        """Producer: advance the host loader, place on device, enqueue.

        `ctx` is the consumer's captured trace context (trace.attach
        re-establishes it here so worker-side h2d spans join the trace).

        Every exit path funnels through `finally: items.close()` — closing
        the `epoch_items` generator from THIS thread (the only one that ever
        ran it) fires its `finally`, cancelling the host loader's pending
        decode futures; a cross-thread close would race "generator already
        executing"."""
        try:
            with trace.attach(ctx):
                for batch, state in items:
                    if self.watchdog is not None:
                        self.watchdog.heartbeat(self.watchdog_name)
                    if batch is None:  # exhaustion marker: no slot/placement
                        q.put(("state", None, state))
                        continue
                    while not stop.is_set():
                        if slots.acquire(timeout=_SENTINEL_POLL_S):
                            break
                    else:
                        return  # consumer gone; slot never acquired
                    if stop.is_set():
                        slots.release()
                        return
                    with self._lock:
                        self._resident += 1
                        self.max_resident = max(self.max_resident,
                                                self._resident)
                    placed = self._place(batch)
                    # ledger: measured bytes of the batch actually resident
                    # in the ring (released when the consumer takes it)
                    obs_memory.register(self._mem_component,
                                        obs_memory.tree_nbytes(placed))
                    q.put(("batch", placed, state))
        except BaseException as e:  # noqa: BLE001 - must cross the thread
            q.put(("error", e, None))
        else:
            q.put(("done", None, None))
        finally:
            # a finished/closed worker is idle, not stalled — stop the
            # watchdog from treating its silence as a hang
            if self.watchdog is not None:
                self.watchdog.clear(self.watchdog_name)
            items.close()

"""Near-zero-overhead wall-time spans: `with span("decode"): ...`.

The ONE span system of the telemetry spine. Every asynchronous layer
(decode pool, device prefetcher, train loop, serving flush thread) wraps
its blocking sections in named spans, and each span is recorded three ways
from the same enter/exit pair:

- **aggregated** per name into the current window — total, count and SELF
  time (duration less what child spans on the same thread cover) — which
  the trainer drains every `log_every` steps into the per-step wall-time
  breakdown (`obs/iter_s`, `obs/iter_self_s`, `obs/input_wait_s`, ...);
- **on the profiler's clock** as a `jax.profiler.TraceAnnotation` named
  `pva/<name>` (with `step_num` where the span has a step), so a device
  idle gap in a profiler trace can be put on the host span that caused it.
  With no profiler session live the annotation is a flag test in C++;
- **in the flight recorder ring** as an interval (`t0`, `dur_s`, `step`),
  so a crash dump carries the recent timeline.

Design constraints, in order:

- **Overhead.** Disabled: `span()` returns a shared no-op context manager
  (two attribute loads, no allocation, no annotation). Enabled: three clock
  reads, one annotation and one dict update under a lock — a microsecond
  against a decode or a train step; the <1%-of-step-time budget holds.
- **Stdlib-only import.** `obs/` must import without jax (serving worker
  threads, the merge CLI). The annotation class is taken lazily, and only
  in a process that has ALREADY imported jax: a process without jax has no
  profiler to write to.
- **Per-thread nesting.** Each thread keeps its own stack (threading.local)
  so concurrent producers/consumers never interleave; a span's parent is
  the span below it on ITS thread's stack, never another thread's. A span
  opened without a step inherits its parent's (the loop's `iter` span
  carries the global step, so `input_wait`, `step`, `log` share it).
  `current_stacks()` exposes every thread's open spans for the
  watchdog/doctor ("where is everyone stuck RIGHT NOW").
- **Self time, not name lists, keeps sums single-counted.** The window
  also accumulates self time per THREAD: the sum of the self times of
  everything one thread recorded is the wall time that thread spent inside
  any span, however the spans nest. The trainer's sum-to-wall check reads
  its own thread's. `BACKGROUND` still names the worker-side spans (they
  overlap the step loop and are reported, not summed).
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict, Optional, Tuple

from pytorchvideo_accelerate_tpu.obs.trace import get_tracer as _get_tracer
from pytorchvideo_accelerate_tpu.utils.sync import make_lock, shared_state

ANNOTATION_PREFIX = "pva/"


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def discard(self):
        return None


_NOOP = _Noop()

_annotation_cls = None


def _annotation():
    """`jax.profiler.TraceAnnotation`, or None in a process that has not
    imported jax (never imported from here: obs/ stays stdlib-importable)."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        # a thread can get here while the main thread is still inside
        # `import jax` (the module is registered before it is populated)
        profiler = getattr(jax, "profiler", None)
        cls = _annotation_cls = getattr(profiler, "TraceAnnotation", None)
    return cls


class _Span:
    """One timed section. After exit: `dur_s`, `self_s`, `t0_ns` (the start
    as `time.time_ns()`, the profiler's clock) and `step`."""

    __slots__ = ("_c", "name", "step", "t0_ns", "dur_s", "self_s",
                 "_live", "_dropped", "_t0", "_children_s", "_trace", "_ann")

    def __init__(self, collector: "SpanCollector", name: str,
                 step: Optional[int] = None, live: bool = True):
        self._c = collector
        self.name = name
        self.step = step
        self._live = live  # False: a bare stopwatch (`timed_span`, obs off)
        self._dropped = False
        self.t0_ns = 0
        self.dur_s = self.self_s = 0.0
        self._t0 = self._children_s = 0.0
        self._trace = self._ann = None

    def __enter__(self):
        if not self._live:
            self._t0 = time.perf_counter()
            return self
        stack = self._c._stack()
        if self.step is None and stack:
            self.step = stack[-1].step
        stack.append(self)
        # distributed-tracing hook (obs/trace.py): when the tracer is armed
        # AND this thread has an active trace context, the span doubles as
        # a trace event carrying trace/parent ids. Disarmed (or untraced):
        # one module-global read, no allocation.
        rt = _get_tracer()
        self._trace = rt.span_begin(self.name) if rt is not None else None
        if self.name not in AGGREGATE_ONLY:
            cls = _annotation()
            if cls is not None:
                name = ANNOTATION_PREFIX + self.name
                ann = (cls(name) if self.step is None
                       else cls(name, step_num=self.step))
                ann.__enter__()
                self._ann = ann
        self.t0_ns = time.time_ns()
        self._t0 = time.perf_counter()
        return self

    def discard(self) -> None:
        """Keep this open span out of every record: no aggregate, no flight
        or trace event, and its time stays its parent's own. For a section
        that turned out not to be one (the loop's `iter` when the epoch had
        run out and no step followed). Its children are recorded as ever;
        its annotation, already on the profiler's clock, closes on exit."""
        self._dropped = True

    def __exit__(self, exc_type, exc, tb):
        dt = self.dur_s = time.perf_counter() - self._t0
        if not self._live:
            return False
        ann = self._ann
        if ann is not None:
            ann.__exit__(exc_type, exc, tb)
            self._ann = None
        tok = self._trace
        if tok is not None:
            if self._dropped:
                tok.drop()
            else:
                tok.end(error=exc_type is not None)
            self._trace = None
        stack = self._c._stack()
        on_top = bool(stack) and stack[-1] is self
        if on_top:
            stack.pop()
        if self._dropped:
            return False
        if on_top and stack:
            stack[-1]._children_s += dt
        self.self_s = max(dt - self._children_s, 0.0)
        self._c._record(self.name, dt, self.self_s, self.t0_ns, self.step,
                        exc_type is not None)
        return False


# span names recorded on worker threads: they run CONCURRENTLY with the
# step loop (reported in the per-window breakdown, never part of the loop
# thread's sum; with `data.device_prefetch_depth 0` h2d runs inline, as a
# child of input_wait, and self time keeps the sum single-counted)
BACKGROUND = frozenset({"h2d", "decode", "batch", "serve_flush",
                        "eval_input_wait", "eval_h2d"})

# per-SAMPLE spans aggregate only: in the flight ring one big batch would
# evict the step/warning/watchdog timeline a crash dump exists to preserve,
# and in a profiler trace they would be tens of events a step.
AGGREGATE_ONLY = frozenset({"decode"})


@shared_state("_window", "_thread_self", "recorder")
class SpanCollector:
    """Thread-safe span aggregator: per-name (total_s, count, self_s)
    windows, per-thread self-time sums, per-thread open-span stacks."""

    def __init__(self, enabled: bool = True, recorder=None):
        self.enabled = enabled
        self.recorder = recorder  # FlightRecorder or None
        self._lock = make_lock("SpanCollector._lock")
        self._window: Dict[str, list] = {}
        self._thread_self: Dict[int, float] = {}
        self._tls = threading.local()
        # thread ident -> (thread name, live stack list); stacks are the
        # SAME list objects the threading.local holds, so reads see live
        # nesting without any per-span registration cost
        self._stacks: Dict[int, tuple] = {}

    # --- recording --------------------------------------------------------

    def span(self, name: str, step: Optional[int] = None):
        """Context manager timing a named section (no-op when disabled).
        `step` is the id the section belongs to (the trainer's global
        step); a span opened without one inherits its parent's."""
        if not self.enabled:
            return _NOOP
        return _Span(self, name, step)

    def timed_span(self, name: str) -> _Span:
        """`span()` for a caller that needs the duration itself (`dur_s`
        after exit; the prefetcher's `wait_s`): one pair of clock reads
        feeds both, and it is still read when telemetry is disabled."""
        return _Span(self, name, live=self.enabled)

    def _record(self, name: str, dur_s: float, self_s: float, t0_ns: int,
                step: Optional[int], error: bool) -> None:
        ident = threading.get_ident()
        with self._lock:
            entry = self._window.get(name)
            if entry is None:
                entry = self._window[name] = [0.0, 0, 0.0]
            entry[0] += dur_s
            entry[1] += 1
            entry[2] += self_s
            self._thread_self[ident] = (
                self._thread_self.get(ident, 0.0) + self_s)
        rec = self.recorder
        if rec is not None and name not in AGGREGATE_ONLY:
            fields = {"t0": round(t0_ns / 1e9, 6), "dur_s": round(dur_s, 6)}
            if step is not None:
                fields["step"] = step
            if error:
                fields["error"] = True
            rec.record("span", name, **fields)

    # --- nesting stacks ---------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
            t = threading.current_thread()
            with self._lock:
                if len(self._stacks) > 32:  # prune dead threads' leftovers
                    alive = {th.ident for th in threading.enumerate()}
                    for ident in [i for i, (_, s) in self._stacks.items()
                                  if not s and i not in alive]:
                        del self._stacks[ident]
                self._stacks[t.ident] = (t.name, st)
        return st

    def current_stacks(self) -> Dict[str, list]:
        """{"thread_name-ident": [outer, ..., inner]} for every thread with
        an open span — the "where is everyone" view for watchdog/doctor
        dumps. Keys carry the ident because thread NAMES collide (both
        prefetchers run a "device-prefetch" worker), and a stall dump must
        never shadow the wedged thread's stack with a healthy namesake's."""
        with self._lock:
            return {f"{name}-{ident}": [s.name for s in list(st)]
                    for ident, (name, st) in self._stacks.items() if st}

    # --- draining ---------------------------------------------------------

    def drain(self) -> Tuple[Dict[str, Tuple[float, int, float]],
                             Dict[int, float]]:
        """Drain the window accumulated since the last drain:
        ({name: (total_s, count, self_s)}, {thread ident: self_s summed over
        every span that thread recorded})."""
        with self._lock:
            window, self._window = self._window, {}
            by_thread, self._thread_self = self._thread_self, {}
        return {k: tuple(v) for k, v in window.items()}, by_thread


_DEFAULT = SpanCollector()


def get_collector() -> SpanCollector:
    return _DEFAULT


def span(name: str, step: Optional[int] = None):
    """`with span("decode"): ...` against the process-default collector."""
    return _DEFAULT.span(name, step)


def timed_span(name: str) -> _Span:
    return _DEFAULT.timed_span(name)


def current_stacks() -> Dict[str, list]:
    return _DEFAULT.current_stacks()

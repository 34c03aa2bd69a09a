"""Heartbeat hang watchdog: dump evidence BEFORE the external kill.

The failure mode this exists for (tier-1's 870s cap is one instance): a
wedged PJRT handshake, a stuck H2D copy, or a deadlocked queue leaves the
process silently idle until an external `timeout -k` kills it blind — no
stack, no timeline, nothing to diagnose. Each asynchronous component (train
loop, device prefetcher, serving batcher) pings `heartbeat(name)` whenever
it makes progress; a daemon poll thread checks ages, and the FIRST
component to exceed `timeout_s` triggers one stall dump:

- all-thread Python stacks (sys._current_frames) to stderr,
- the open span stacks (who was inside what when it froze),
- the flight-recorder ring to `<output_dir>/flight_record.json`.

The watchdog NEVER kills: it is a diagnoser, not an executioner — a false
positive (a legitimately long compile) costs one noisy dump, nothing more.
Per-component one-shot arming: a stalled name fires once, then re-arms on
its next heartbeat, so a wedged-then-recovered component can report again
while a permanently wedged one doesn't spam a dump per poll tick.
Components that finish cleanly call `clear(name)` so an idle-but-healthy
phase (between epochs, a drained prefetcher) is not a stall.

Sections (`with watchdog.section(name, detail)`) add ATTRIBUTION: while a
component is inside a section, a stall on it is reported as wedged inside
that detail string — how a straggling/wedged mesh collective (the
`parallel/hangcheck.py` collective-hang detector wraps every watched
collective in one, detail carrying the op + host index) is distinguished
from a merely slow input pipeline. Section exit CLEARS the component:
"no collective in flight" is idle, never a stall.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

from pytorchvideo_accelerate_tpu.utils.sync import (
    make_lock,
    make_thread,
    shared_state,
)


@shared_state("stall_count", "last_stalled", "last_attribution", "_thread")
class Watchdog:
    """No-progress detector over named heartbeats."""

    def __init__(self, timeout_s: float, output_dir: str = "",
                 recorder=None, collector=None,
                 on_stall: Optional[Callable[[List[str]], None]] = None,
                 poll_s: Optional[float] = None):
        if timeout_s <= 0:
            raise ValueError(f"watchdog timeout must be > 0, got {timeout_s}")
        self.timeout_s = float(timeout_s)
        self.output_dir = output_dir
        self.recorder = recorder      # FlightRecorder or None
        self.collector = collector    # SpanCollector or None (open spans)
        self.on_stall = on_stall      # test/ops hook, called after the dump
        self._poll_s = poll_s or min(max(self.timeout_s / 4.0, 0.02), 5.0)
        self._lock = make_lock("Watchdog._lock")
        self._beats = {}   # name -> last monotonic heartbeat
        self._fired = set()  # names already dumped for the current stall
        self._sections: Dict[str, Tuple[str, float]] = {}  # name -> (detail, t)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.stall_count = 0
        self.last_stalled: List[str] = []
        # stalled name -> (detail, seconds inside) for components that were
        # inside a section when they stalled (the collective-hang verdict)
        self.last_attribution: Dict[str, Tuple[str, float]] = {}

    # --- component side ---------------------------------------------------

    def heartbeat(self, name: str = "main") -> None:
        """Progress ping; the first ping registers the component."""
        with self._lock:
            self._beats[name] = time.monotonic()
            self._fired.discard(name)

    def beat_fn(self, name: str) -> Callable[[], None]:
        """Bound zero-arg pinger for components that take a plain callable."""
        return lambda: self.heartbeat(name)

    def clear(self, name: str) -> None:
        """Deregister a component that finished cleanly (no longer expected
        to make progress — not a stall)."""
        with self._lock:
            self._beats.pop(name, None)
            self._fired.discard(name)
            self._sections.pop(name, None)

    @contextlib.contextmanager
    def section(self, name: str, detail: str = ""):
        """Attributed progress window: heartbeat + mark `name` as inside
        `detail` on entry; a stall while open reports the detail (who is
        wedged in WHAT — a `psum` on host 3, not just "no progress").
        Exit clears the component entirely: a name that is only expected
        to progress while inside sections (a collective) is idle-healthy
        between them."""
        now = time.monotonic()
        with self._lock:
            self._beats[name] = now
            self._fired.discard(name)
            self._sections[name] = (detail, now)
        try:
            yield
        finally:
            self.clear(name)

    # --- lifecycle --------------------------------------------------------

    def start(self) -> "Watchdog":
        thread = self._thread
        if thread is not None and thread.is_alive():
            # already polling — or a stopped poller still draining a slow
            # stall dump: never spawn a second one (duplicate dumps)
            return self
        self._stop.clear()  # a stopped watchdog can be restarted
        thread = make_thread(
            target=self._run, name="pva-watchdog", daemon=True)
        # `_thread` is handed between start()/stop() callers (trainer main
        # thread, serving close path): same lock as the beat table
        with self._lock:
            self._thread = thread
        thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=self._poll_s * 4 + 1.0)
            if not thread.is_alive():
                with self._lock:
                    if self._thread is thread:  # a racing start() may have
                        self._thread = None     # installed a fresh poller
            # else: keep the handle so start() can see the straggler

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            self.check()

    # --- detection --------------------------------------------------------

    def check(self, now: Optional[float] = None) -> List[str]:
        """One poll: returns (and dumps for) newly-stalled components.
        Public so tests can drive detection deterministically."""
        now = time.monotonic() if now is None else now
        with self._lock:
            stalled = sorted(
                name for name, t in self._beats.items()
                if name not in self._fired and now - t > self.timeout_s)
            self._fired.update(stalled)
        if stalled:
            self._fire(stalled)
        return stalled

    def _fire(self, stalled: List[str]) -> None:
        # written on the poll thread, read by tests/operators from others —
        # same lock as the beat table (pva-tpu-lint lock-discipline)
        now = time.monotonic()
        with self._lock:
            self.stall_count += 1
            self.last_stalled = list(stalled)
            attribution = {
                name: (detail, round(now - t, 3))
                for name, (detail, t) in self._sections.items()
                if name in stalled}
            self.last_attribution = attribution
        lines = [
            f"[watchdog] NO PROGRESS from {', '.join(stalled)} for "
            f"> {self.timeout_s:g}s — dumping all-thread stacks + flight "
            "record before an external timeout kills the process blind",
        ]
        for name, (detail, age) in attribution.items():
            # the collective-hang verdict: wedged INSIDE an attributed
            # operation, not merely quiet between them
            lines.append(f"[watchdog] {name} wedged inside '{detail}' "
                         f"for {age:g}s")
        if self.collector is not None:
            open_spans = self.collector.current_stacks()
            if open_spans:
                lines.append(f"[watchdog] open spans: {open_spans}")
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            lines.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
            lines.append("".join(traceback.format_stack(frame)).rstrip())
        print("\n".join(lines), file=sys.stderr, flush=True)
        if self.recorder is not None:
            self.recorder.record(
                "watchdog", "stall", stalled=list(stalled),
                timeout_s=self.timeout_s,
                **({"attribution": {n: f"{d} ({a:g}s)"
                                    for n, (d, a) in attribution.items()}}
                   if attribution else {}))
            path = None
            if self.output_dir:
                import os

                path = self.recorder.dump(
                    os.path.join(self.output_dir, "flight_record.json"))
            else:
                path = self.recorder.dump()
            if path:
                print(f"[watchdog] flight record dumped to {path}",
                      file=sys.stderr, flush=True)
        if self.on_stall is not None:
            try:
                self.on_stall(list(stalled))
            except Exception:  # the hook must not kill the poll thread
                pass

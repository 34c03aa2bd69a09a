"""On-demand device profiler capture (pva-tpu-hbm layer c).

The ONE profiler capture path of the package: a live incident — serving
p99 burning NOW, a step-time regression appearing mid-run — needs a device
profile out of the running process, in a trace small enough to read. Two
triggers over one capture primitive:

- ``POST /profile?seconds=N`` on the serving server: a background
  capture window on a live process (409 while one is running — the
  profiler is a singleton resource);
- ``--obs.profile_steps A..B`` in the trainer: a run-relative step
  window (step 0 is this run's first step, so a resumed run profiles its
  warm steps, not a global step count it never sees). `--profile` is the
  shorthand for steps 2..6, published under `profile_dir`.

Captures are written ATOMICALLY under `output_dir`: the trace streams
into a dot-prefixed temp dir and is `os.replace`d to its final name
(`profile_<tag>/`) only after `stop_trace()` returns — a crashed or
half-done capture can never be mistaken for a complete one, and
`pva-tpu-trace` can merge complete captures with the trace rings by
timestamp. The flight ring gets start/stop events, so profile windows
line up against the incident timeline.

Arming discipline: the module-level hooks are one global read while
disarmed. jax is imported lazily inside the capture calls only — the
module stays stdlib-importable (serving worker threads, tests without a
device) and a backend without a profiler degrades to a recorded refusal,
never a crash.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Dict, Optional

from pytorchvideo_accelerate_tpu.utils.sync import (
    make_lock,
    make_thread,
    shared_state,
)

_DEFAULT: Optional["ProfilerCapture"] = None


def parse_steps(spec: str) -> Optional[tuple]:
    """"A..B" -> (A, B) run-relative step window; None for the empty
    spec. Raises ValueError on malformed/inverted windows (config-time
    validation, not a mid-run surprise)."""
    if not spec:
        return None
    parts = spec.split("..")
    if len(parts) != 2:
        raise ValueError(
            f"profile_steps must look like 'A..B', got {spec!r}")
    a, b = int(parts[0]), int(parts[1])
    if a < 0 or b <= a:
        raise ValueError(
            f"profile_steps window must satisfy 0 <= A < B, got {spec!r}")
    return a, b


@shared_state("_active_tag", "_tmp_dir", "_captures")
class ProfilerCapture:
    """One jax.profiler trace window at a time, atomically published."""

    def __init__(self, output_dir: str, recorder=None):
        self._lock = make_lock("obs.ProfilerCapture._lock")
        self.output_dir = output_dir
        self.recorder = recorder
        self._active_tag: Optional[str] = None
        self._tmp_dir: Optional[str] = None
        self._captures: int = 0
        self._thread = None

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._active_tag is not None

    def start(self, tag: Optional[str] = None) -> bool:
        """Open a trace window; False (not an exception) when one is
        already open or the backend has no profiler."""
        tag = tag or time.strftime("%Y%m%d-%H%M%S")
        with self._lock:
            if self._active_tag is not None:
                return False
            tmp = os.path.join(self.output_dir, f".profile_tmp_{tag}")
            self._active_tag, self._tmp_dir = tag, tmp
        try:
            import jax

            os.makedirs(tmp, exist_ok=True)
            # the least that still records host annotations (the `pva/*`
            # spans, `train`): no Python tracer, no level-2 host events,
            # no HLO proto (the step's text is read from the compiled
            # program). On a v5e host the runtime's own level-1 events
            # are still some 0.9 M a step (1.2 GB for 40 X3D-S steps,
            # PERF.md PR 25), so keep a window to tens of steps
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(tmp, profiler_options=options)
            # marks, on the trace's own clock, when start_trace returned:
            # what the device does between here and its first traced
            # execution is the profiler's arming, not the program's
            with jax.profiler.TraceAnnotation("pva/capture"):
                pass
        except Exception as e:
            with self._lock:
                self._active_tag = self._tmp_dir = None
            shutil.rmtree(tmp, ignore_errors=True)
            if self.recorder is not None:
                self.recorder.warn("profiler capture refused",
                                   error=f"{type(e).__name__}: {e}")
            return False
        if self.recorder is not None:
            self.recorder.record("profile", "start", tag=tag)
        return True

    def stop(self) -> Optional[str]:
        """Close the window and publish it atomically; returns the final
        directory, or None when nothing was open / publishing failed."""
        with self._lock:
            tag, tmp = self._active_tag, self._tmp_dir
            self._active_tag = self._tmp_dir = None
        if tag is None:
            return None
        final = os.path.join(self.output_dir, f"profile_{tag}")
        try:
            import jax

            jax.profiler.stop_trace()
            # the capture only exists once this rename lands — readers
            # never see a partial trace directory
            if os.path.isdir(final):
                shutil.rmtree(final, ignore_errors=True)
            os.replace(tmp, final)
        except Exception as e:
            shutil.rmtree(tmp, ignore_errors=True)
            if self.recorder is not None:
                self.recorder.warn("profiler capture lost",
                                   tag=tag, error=f"{type(e).__name__}: {e}")
            return None
        with self._lock:
            self._captures += 1
        if self.recorder is not None:
            self.recorder.record("profile", "stop", tag=tag, dir=final)
        return final

    def capture_for(self, seconds: float,
                    tag: Optional[str] = None) -> Optional[str]:
        """The POST /profile shape: start now, stop after `seconds` on a
        background daemon thread. Returns the pending capture's tag, or
        None when a window is already open / the backend refused."""
        tag = tag or time.strftime("%Y%m%d-%H%M%S")
        if not self.start(tag=tag):
            return None

        def _worker():
            time.sleep(max(0.0, float(seconds)))
            self.stop()

        self._thread = make_thread(target=_worker, daemon=True,
                                   name="pva-profile-capture")
        self._thread.start()
        return tag

    def join(self, timeout: Optional[float] = None) -> None:
        t = self._thread
        if t is not None:
            t.join(timeout)

    def snapshot(self) -> Dict:
        with self._lock:
            return {"busy": self._active_tag is not None,
                    "active_tag": self._active_tag,
                    "captures": self._captures,
                    "output_dir": self.output_dir}


def get_profiler() -> Optional[ProfilerCapture]:
    return _DEFAULT


def configure(enabled: bool = True,
              output_dir: Optional[str] = None,
              **kwargs) -> Optional[ProfilerCapture]:
    """Arm (or disarm) the process-default capture singleton."""
    global _DEFAULT
    if not enabled or output_dir is None:
        _DEFAULT = None
        return None
    _DEFAULT = ProfilerCapture(output_dir, **kwargs)
    return _DEFAULT

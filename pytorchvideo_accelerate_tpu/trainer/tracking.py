"""Experiment tracking multiplexer.

Replaces accelerate's tracker stack (SURVEY §2.2-A9: `GeneralTracker` ABC,
TensorBoard/wandb concrete trackers, `log_with="all"` auto-discovery at
tracking.py:1260-1290, main-process fan-out at accelerator.py:3356-3386).
Same shape here: a small Tracker protocol, concrete writers, and "all"
resolving to whatever is importable — wandb is absent in this image, so it
gates cleanly; tensorboard writes via tf.summary; jsonl is always available
and is what scripts/trace_gap_probe.py and the tests parse.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

from pytorchvideo_accelerate_tpu.reliability.faults import fault_point
from pytorchvideo_accelerate_tpu.reliability.retry import retry_call
from pytorchvideo_accelerate_tpu.utils.logging import get_logger
from pytorchvideo_accelerate_tpu.utils.sync import make_lock, shared_state

logger = get_logger("pva_tpu")


class Tracker:
    name = "base"

    def start(self, run_name: str, config: dict) -> None:  # pragma: no cover
        raise NotImplementedError

    def log(self, values: Dict[str, float], step: int) -> None:  # pragma: no cover
        raise NotImplementedError

    def finish(self) -> None:
        pass


class JsonlTracker(Tracker):
    """One JSON line per log call — always available, trivially parseable."""

    name = "jsonl"

    def __init__(self, logging_dir: str):
        self.logging_dir = logging_dir
        self._fh = None

    def start(self, run_name: str, config: dict) -> None:
        os.makedirs(self.logging_dir, exist_ok=True)
        path = os.path.join(self.logging_dir, f"{run_name}.jsonl")
        self._fh = open(path, "a")
        self._fh.write(json.dumps({"event": "start", "run": run_name,
                                   "time": time.time(), "config": config},
                                  default=str) + "\n")
        self._fh.flush()

    def log(self, values: Dict[str, float], step: int) -> None:
        if self._fh:
            self._fh.write(json.dumps({"step": int(step), **{k: float(v) for k, v in values.items()}}) + "\n")
            self._fh.flush()

    def finish(self) -> None:
        if self._fh:
            self._fh.write(json.dumps({"event": "end", "time": time.time()}) + "\n")
            self._fh.close()
            self._fh = None


class TensorBoardTracker(Tracker):
    name = "tensorboard"

    def __init__(self, logging_dir: str):
        self.logging_dir = logging_dir
        self._writer = None

    def start(self, run_name: str, config: dict) -> None:
        import tensorflow as tf  # installed in the build env

        self._writer = tf.summary.create_file_writer(
            os.path.join(self.logging_dir, run_name)
        )
        with self._writer.as_default():
            tf.summary.text("config", json.dumps(config, default=str), step=0)

    def log(self, values: Dict[str, float], step: int) -> None:
        import tensorflow as tf

        if self._writer:
            with self._writer.as_default():
                for k, v in values.items():
                    tf.summary.scalar(k, float(v), step=int(step))
            self._writer.flush()

    def finish(self) -> None:
        if self._writer:
            self._writer.close()
            self._writer = None


class WandbTracker(Tracker):
    name = "wandb"

    def __init__(self, logging_dir: str):
        self.logging_dir = logging_dir
        self._run = None

    def start(self, run_name: str, config: dict) -> None:
        import wandb

        self._run = wandb.init(name=run_name, config=config, dir=self.logging_dir)

    def log(self, values: Dict[str, float], step: int) -> None:
        if self._run:
            self._run.log(values, step=int(step))

    def finish(self) -> None:
        if self._run:
            self._run.finish()
            self._run = None


def _available(name: str) -> bool:
    if name == "jsonl":
        return True
    try:
        __import__({"tensorboard": "tensorflow", "wandb": "wandb"}[name])
        return True
    except Exception:
        return False


def resolve_trackers(spec: str, logging_dir: str) -> List[Tracker]:
    """`"all"` -> every importable tracker (accelerate tracking.py:1260-1290
    semantics); else a comma-list of names."""
    names = ["jsonl", "tensorboard", "wandb"] if spec == "all" else [
        s.strip() for s in spec.split(",") if s.strip()
    ]
    out: List[Tracker] = []
    for n in names:
        if not _available(n):
            logger.info("tracker %s unavailable; skipping", n)
            continue
        cls = {"jsonl": JsonlTracker, "tensorboard": TensorBoardTracker,
               "wandb": WandbTracker}[n]
        out.append(cls(logging_dir))
    return out


@shared_state("trackers")
class TrackerHub:
    """Fan-out facade: `init_trackers`/`log`/`end_training` equivalents
    (reference run.py:231,274,323). Construct on the main process only.

    Fan-out is NON-FATAL and RETRIED: a raising tracker (broken
    tensorboard install, wandb network hiccup, full disk under the jsonl
    file) gets `retries` total attempts with short backoff
    (reliability/retry.py — tracker outages are usually transient), and
    only an exhausted budget disables it — a logging failure must never
    kill a training step, and a blip must not cost the rest of the run's
    metrics. The surviving trackers keep logging.

    The disable path REBINDS `self.trackers` under a lock instead of
    mutating the live list: `log()` is called from the train loop and from
    serving/metric threads, and pva-tpu-tsan flagged the old bare
    `list.remove` racing a concurrent fan-out's iteration copy — two
    threads disabling at once could resurrect a just-removed tracker."""

    def __init__(self, spec: str, logging_dir: str, retries: int = 2):
        self._lock = make_lock("TrackerHub._lock")
        self.trackers = resolve_trackers(spec, logging_dir)
        self.retries = max(int(retries), 1)

    def _fanout(self, op: str, fn) -> None:
        with self._lock:
            trackers = list(self.trackers)
        for t in trackers:
            def attempt(t=t):
                # chaos hook: an injected raise exercises exactly the
                # retry-then-disable path a real tracker outage takes
                fault_point("tracker.log")
                fn(t)

            try:
                retry_call(attempt, name=f"tracker.{op}",
                           attempts=self.retries, retry_on=(Exception,),
                           base_delay_s=0.02, max_delay_s=0.25,
                           deadline_s=2.0)
            except Exception as e:  # noqa: BLE001 - any tracker bug qualifies
                logger.warning(
                    "tracker %r raised in %s (%s: %s) after %d attempt(s); "
                    "disabling it — a logging failure must never kill a "
                    "training step",
                    t.name, op, type(e).__name__, e, self.retries)
                with self._lock:
                    self.trackers = [x for x in self.trackers if x is not t]
                try:
                    from pytorchvideo_accelerate_tpu.obs import get_recorder

                    get_recorder().warn(f"tracker {t.name} disabled",
                                        op=op, error=str(e)[:200])
                except Exception:  # pragma: no cover - obs must stay optional
                    pass

    def start(self, run_name: str, config: dict) -> None:
        self._fanout("start", lambda t: t.start(run_name, config))

    def log(self, values: Dict[str, float], step: int) -> None:
        self._fanout("log", lambda t: t.log(values, step))

    def finish(self) -> None:
        self._fanout("finish", lambda t: t.finish())


class DeferredStepLogger:
    """One-step-delayed metric logging off the dispatch critical path.

    `float(metrics["loss"])` at `log_every` inside the step loop blocks the
    host on the CURRENT step's result before the next one can dispatch —
    exactly the sync the async-dispatch design works to avoid. Instead,
    `defer()` stashes the device scalars (kicking off their D2H copies
    asynchronously where the backend supports it) and `flush()` — called on
    the NEXT loop iteration, after another step has been dispatched — turns
    them into floats. By then the deferred step has all but certainly
    retired, so the fetch is a cache read, not a pipeline stall; at worst it
    blocks one step later than the old code did, never on the step just
    dispatched.

    Stash-then-flush also means at most one pending log at a time: a second
    `defer()` before `flush()` flushes the first (never silently drops it).
    """

    def __init__(self, hub: TrackerHub, on_flush=None):
        self.hub = hub
        # optional observer of the flushed floats (the obs layer mirrors
        # grad/param-norm gauges + the non-finite counter into the metric
        # registry here, off the dispatch critical path)
        self.on_flush = on_flush
        self._pending: Optional[tuple] = None

    def defer(self, values: Dict[str, object], step: int) -> None:
        if self._pending is not None:
            self.flush()
        for v in values.values():
            start = getattr(v, "copy_to_host_async", None)
            if start is not None:
                try:  # best-effort: a plain float has nothing to start
                    start()
                except Exception:  # pragma: no cover - backend-dependent
                    pass
        self._pending = (values, step)

    def flush(self) -> None:
        """Fetch + log the stashed metrics, if any (loop iteration top and
        epoch end both call this; safe to call with nothing pending)."""
        if self._pending is None:
            return
        values, step = self._pending
        self._pending = None
        floats = {k: float(v) for k, v in values.items()}
        if self.on_flush is not None:
            try:
                self.on_flush(floats, step)
            except Exception:  # observability must not kill the step loop
                pass
        self.hub.log(floats, step=step)

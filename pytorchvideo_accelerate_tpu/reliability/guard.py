"""TrainGuard: self-healing training — anomaly detection, last-known-good
rollback, replay bundles (docs/RELIABILITY.md § divergence runbook).

PR 6 made the stack survive *process-level* failures; this closes the loop
on *semantic* ones: a nonfinite loss, a grad spike that poisons the run, a
divergence that a multi-day Kinetics job would otherwise ride to a dead
checkpoint. The obs spine already computes the signals (loss, grad_norm,
the in-graph nonfinite flag); the guard turns them into recovery:

1. **In-graph skip-batch** (`trainer/steps.py guard_skip`): with the guard
   armed, a step whose loss or grad norm is nonfinite DISCARDS its own
   update inside the compiled step (`jnp.where` on every state leaf — no
   recompile, no host round-trip), so a single NaN batch can never poison
   params/EMA/optimizer state. Host-side detection is one step late by
   design (the deferred-fetch discipline); the in-graph skip is why that
   latency is safe.
2. **EWMA spike detection** (`SpikeDetector`): per-metric exponential
   moving mean/variance over loss and grad_norm; an UPWARD z-score
   excursion past `guard.spike_zscore` is an anomaly. Downward cliffs
   (warmup, an LR drop) are improvements and never fire; a warmup
   observation budget keeps the young-variance phase quiet.
3. **Last-known-good ring**: an own orbax `Checkpointer` under
   `<output_dir>/guard_lkg`, saved every `guard.lkg_every_steps` and
   ADVANCED ONLY WHEN THE WINDOW IS HEALTHY (no anomaly observed within
   the cadence window); `guard.lkg_keep` bounds the ring (orbax
   max_to_keep pruning).
4. **Escalation ladder**: anomaly streak < `guard.rollback_after` →
   *skip* (recorded; the in-graph skip already protected the state).
   Streak at the threshold → *rollback*: restore the LKG through the
   mesh-portable restore path and fast-forward the loader PAST the
   offending span (the consumed-position `LoaderState` recorded with the
   anomaly), so a deterministic bad span is not replayed into the same
   divergence. More than `guard.max_rollbacks` rollbacks → *halt*
   (`GuardHalt`), because a rollback loop means the problem is the data
   or the optimizer, not transient luck — see the runbook.
5. **Replay bundle**: the first anomalous step of every streak dumps
   `<output_dir>/replay/step_<N>/` — the batch tensors (`.npy`, bf16
   widened to f32), RNG seed/step, loader position, config, and metric
   evidence, all timestamp-free so the same anomaly dumps byte-identical
   bundles — a repro artifact, not a mystery.

Disarmed (`guard.enabled=false`, the default) nothing here is
constructed, the compiled step carries no skip branch, and the step loop
does one `is None` check — the `faults.py`/tsan structural-zero-overhead
discipline. Armed, the per-step cost is one deferred scalar fetch of an
already-retired step (the `DeferredStepLogger` pattern: never blocks the
step just dispatched) and one held batch reference.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from pytorchvideo_accelerate_tpu.utils.logging import get_logger

logger = get_logger("pva_tpu")

REPLAY_DIRNAME = "replay"


class GuardHalt(RuntimeError):
    """The escalation ladder's top: rollbacks exhausted (or impossible).
    Carries the replay-bundle path in its message — the repro artifact the
    divergence runbook starts from."""


class SpikeDetector:
    """EWMA mean/variance z-score detector over one scalar stream.

    `update(value)` returns `None` (healthy), `"nonfinite"`, or
    `"spike"`. Design points, each locked by tests/test_zguard.py:

    - UPWARD excursions only: a loss cliff downward (warmup progress, an
      LR-schedule drop) is an improvement, never an anomaly.
    - `warmup` observations pass freely while still feeding the EWMA —
      early-training statistics are too young to judge against.
    - An anomalous value is NOT absorbed into the EWMA: a divergence must
      not drag the baseline up after itself and mask its own tail.
    - Nonfinite values short-circuit (and are never absorbed).
    """

    def __init__(self, alpha: float = 0.05, zscore: float = 6.0,
                 warmup: int = 20):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = float(alpha)
        self.zscore = float(zscore)
        self.warmup = int(warmup)
        self.n = 0
        self.mean = 0.0
        self.var = 0.0

    def update(self, value: float) -> Optional[str]:
        v = float(value)
        if not math.isfinite(v):
            return "nonfinite"
        if self.n >= self.warmup:
            std = math.sqrt(self.var) if self.var > 0 else 0.0
            if std > 0 and (v - self.mean) / std > self.zscore:
                return "spike"
        d = v - self.mean
        self.mean += self.alpha * d
        # EW variance (West): blends the squared innovation
        self.var = (1.0 - self.alpha) * (self.var + self.alpha * d * d)
        self.n += 1
        return None


# --- replay bundles ---------------------------------------------------------

def _np_host(value) -> np.ndarray:
    """Host numpy view of a (possibly device, possibly bf16) array. Non-f32
    floats widen to float32 — numpy's format has no bf16, and the widening
    is value-exact — with the original dtype recorded by the caller."""
    arr = np.asarray(value)
    if arr.dtype.kind == "f" and arr.dtype.itemsize < 4:
        arr = arr.astype(np.float32)
    elif arr.dtype not in (np.dtype(np.float32), np.dtype(np.float64),
                           np.dtype(np.int32), np.dtype(np.int64),
                           np.dtype(np.uint8), np.dtype(np.bool_)):
        arr = arr.astype(np.float32)
    return arr


def dump_replay_bundle(path: str, batch: Dict[str, Any],
                       meta: Dict[str, Any]) -> str:
    """Write a deterministic replay bundle directory: one `<key>.npy` per
    batch leaf + a sorted-keys `meta.json`, staged in a tmp dir and
    `os.rename`d into place (a kill mid-dump leaves no half bundle).
    Deliberately timestamp-free: the same anomaly dumps byte-identical
    bundles, which is what makes one a REPRO artifact."""
    import jax

    host = {str(k): _np_host(v)
            for k, v in jax.device_get(dict(batch)).items()}
    meta = dict(meta)
    meta["arrays"] = {
        k: {"shape": list(v.shape), "dtype": str(v.dtype),
            "source_dtype": str(np.asarray(batch[k]).dtype)}
        for k, v in host.items()}
    tmp = f"{path}.tmp-{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    try:
        for k, v in host.items():
            np.save(os.path.join(tmp, f"{k}.npy"), v)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f, indent=1, sort_keys=True, default=str)
        if os.path.isdir(path):  # re-dump of the same step: replace whole
            import shutil

            shutil.rmtree(path)
        os.rename(tmp, path)
    except OSError:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return path


def load_replay_bundle(path: str):
    """Read a bundle back -> `(meta, {key: np.ndarray})`."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    arrays = {k: np.load(os.path.join(path, f"{k}.npy"))
              for k in meta.get("arrays", {})}
    return meta, arrays


# --- the guard --------------------------------------------------------------

@dataclass
class GuardAction:
    """A verdict the step loop must act on (skips are absorbed inside the
    guard; only rollback crosses this boundary — halt raises)."""

    kind: str  # "rollback"
    lkg_step: int
    resume_position: Dict[str, int]  # LoaderState dict PAST the bad span
    bundle_path: str = ""
    reason: str = ""


@dataclass
class _Stash:
    step: int
    metrics: Dict[str, Any]  # device scalars — fetched one step later
    batch: Any               # device batch reference (one batch of HBM)
    position: Dict[str, int]  # post-consumption LoaderState dict


class TrainGuard:
    """The trainer-side state machine. One instance per Trainer when
    `guard.enabled`; `pva-tpu-doctor` reads the last one constructed via
    `guard_snapshot()`."""

    def __init__(self, cfg, output_dir: str, mesh=None, tp: bool = True,
                 config_dict: Optional[dict] = None, seed: int = 0):
        self.cfg = cfg
        self.output_dir = output_dir
        self.mesh = mesh
        self.tp = tp
        self.config_dict = config_dict or {}
        self.seed = int(seed)
        policy = getattr(cfg, "policy", "both")
        if policy not in ("nonfinite", "spike", "both"):
            raise ValueError(
                f"guard.policy must be nonfinite|spike|both, got {policy!r}")
        self.policy = policy
        self.detectors: Dict[str, SpikeDetector] = {
            name: SpikeDetector(alpha=cfg.ewma_alpha,
                                zscore=cfg.spike_zscore,
                                warmup=cfg.warmup_steps)
            for name in ("loss", "grad_norm")}
        self._pending: Optional[_Stash] = None
        self._streak = 0
        self._streak_bundle = ""
        self._last_anomaly_step: Optional[int] = None
        self.skips = 0
        self.rollbacks = 0
        self.lkg_step: Optional[int] = None
        self.last_verdict: Optional[dict] = None
        self.last_rollback: Optional[dict] = None
        self.events: List[dict] = []  # bounded evidence trail (snapshot)
        self.quarantine = None  # attached by the trainer when one exists
        self._ckpt = None  # lazy: only a run that ever saves pays orbax
        global _last_guard
        _last_guard = self

    # --- LKG ring ---------------------------------------------------------

    @property
    def lkg_dir(self) -> str:
        return os.path.join(self.output_dir, "guard_lkg")

    def _checkpointer(self):
        if self._ckpt is None:
            from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
                Checkpointer,
            )

            self._ckpt = Checkpointer(self.lkg_dir,
                                      max_to_keep=max(self.cfg.lkg_keep, 1),
                                      use_async=True)
        return self._ckpt

    def ring_steps(self) -> List[int]:
        if self._ckpt is not None:
            return list(self._ckpt.all_steps())
        try:  # closed/never-opened ring: read the directory (doctor view)
            return sorted(int(d) for d in os.listdir(self.lkg_dir)
                          if d.isdigit())
        except OSError:
            return []

    def _maybe_save_lkg(self, gstep: int, live_state, loader_state) -> None:
        """Advance the LKG ring iff due AND the window is healthy: no
        anomaly observed within the last cadence window. (The state saved
        is the live one — its most recent step's metrics are one deferred
        fetch away from observation; the in-graph skip guarantees nothing
        nonfinite can be inside it regardless.)"""
        every = max(int(self.cfg.lkg_every_steps), 1)
        due = self.lkg_step is None or gstep - self.lkg_step >= every
        healthy = (self._streak == 0
                   and (self._last_anomaly_step is None
                        or gstep - self._last_anomaly_step >= every))
        if not (due and healthy and gstep > 0):
            return
        ckpt = self._checkpointer()
        if gstep in (ckpt.all_steps() or ()):
            # a post-rollback trajectory can revisit a step index the ring
            # already holds; replace it so the ring tracks THIS trajectory
            ckpt.delete(gstep)
        ckpt.save(gstep, live_state,
                  {"kind": "lkg", "data_state": loader_state.to_dict()})
        self.lkg_step = gstep
        self._event("lkg_save", step=gstep)
        self._publish("lkg_save")
        try:
            from pytorchvideo_accelerate_tpu.obs import get_registry

            get_registry().gauge(
                "pva_guard_lkg_step",
                "last-known-good checkpoint step the guard would roll back "
                "to").set(gstep)
        except Exception:  # pragma: no cover - telemetry stays optional
            pass

    # --- per-step hook -----------------------------------------------------

    def step(self, gstep: int, metrics: Dict[str, Any], batch,
             loader_state, live_state) -> Optional[GuardAction]:
        """Called right after dispatching step `gstep` (metrics are its
        device scalars). Observes the PREVIOUS step's stash — whose step
        has retired behind the one just dispatched, so the scalar fetch
        never stalls the pipeline — then stashes this one. Returns a
        `GuardAction` on rollback, raises `GuardHalt` at the ladder top."""
        prev, self._pending = self._pending, _Stash(
            gstep, metrics, batch, loader_state.to_dict())
        if prev is None:
            return None
        return self._observe(prev, gstep, live_state, loader_state)

    def flush(self, live_state, loader_state) -> Optional[GuardAction]:
        """Epoch-end: observe the final pending step (its result has been
        synced by the epoch-end fetch already)."""
        prev, self._pending = self._pending, None
        if prev is None:
            return None
        return self._observe(prev, prev.step, live_state, loader_state)

    def _verdict(self, loss: float, grad_norm: float) -> Optional[dict]:
        nonfinite = not (math.isfinite(loss) and math.isfinite(grad_norm))
        if nonfinite:
            if self.policy == "spike":
                # even spike-only policy must not FEED nonfinite values
                # into the EWMAs; it just doesn't escalate on them
                return None
            return {"kind": "nonfinite"}
        if self.policy == "nonfinite":
            for name, v in (("loss", loss), ("grad_norm", grad_norm)):
                self.detectors[name].update(v)  # keep baselines warm
            return None
        for name, v in (("loss", loss), ("grad_norm", grad_norm)):
            kind = self.detectors[name].update(v)
            if kind == "spike":
                return {"kind": "spike", "metric": name}
        return None

    def _observe(self, stash: _Stash, live_gstep: int, live_state,
                 live_loader_state) -> Optional[GuardAction]:
        loss = float(stash.metrics["loss"])
        grad_norm = float(stash.metrics["grad_norm"])
        verdict = self._verdict(loss, grad_norm)
        if verdict is None:
            self._streak = 0
            self._streak_bundle = ""
            self._maybe_save_lkg(live_gstep, live_state, live_loader_state)
            return None

        self._streak += 1
        self._last_anomaly_step = stash.step
        verdict.update(step=stash.step, loss=loss, grad_norm=grad_norm,
                       streak=self._streak, position=dict(stash.position))
        self.last_verdict = verdict
        if self._streak == 1:
            verdict["bundle"] = self._dump_bundle(stash, verdict)
            self._streak_bundle = verdict["bundle"]

        if self._streak < max(int(self.cfg.rollback_after), 1):
            self.skips += 1
            self._event("skip", **{k: v for k, v in verdict.items()
                                   if k != "position"})
            self._publish("skip")
            self._warn("guard: anomalous step skipped", verdict)
            return None

        # ladder: rollback — or halt when rollbacks are exhausted or there
        # is nothing to roll back to
        if self.rollbacks >= int(self.cfg.max_rollbacks):
            self._halt(verdict,
                       f"{self.rollbacks} rollback(s) already spent "
                       f"(guard.max_rollbacks={self.cfg.max_rollbacks}) — "
                       "a rollback loop means the data or the optimizer, "
                       "not luck")
        if self.lkg_step is None:
            self._halt(verdict,
                       "no last-known-good checkpoint exists yet "
                       "(anomaly inside the first guard.lkg_every_steps "
                       "window)")
        self.rollbacks += 1
        self._streak = 0
        self._pending = None  # the just-dispatched step is abandoned too
        action = GuardAction(
            kind="rollback", lkg_step=int(self.lkg_step),
            resume_position=dict(stash.position),
            bundle_path=self._streak_bundle,
            reason=f"{verdict['kind']} at step {stash.step} "
                   f"(loss={loss:g}, grad_norm={grad_norm:g})")
        self.last_rollback = {
            "lkg_step": action.lkg_step,
            "anomaly_step": stash.step,
            "resume_position": action.resume_position,
            "bundle": action.bundle_path, "reason": action.reason}
        self._event("rollback", **self.last_rollback)
        self._publish("rollback")
        self._warn("guard: rolling back to last-known-good",
                   self.last_rollback)
        return action

    def _halt(self, verdict: dict, why: str) -> None:
        self._event("halt", step=verdict.get("step"), why=why)
        self._publish("halt")
        raise GuardHalt(
            f"TrainGuard halt: {verdict['kind']} anomaly at step "
            f"{verdict.get('step')} — {why}. Replay bundle: "
            f"{self._streak_bundle or verdict.get('bundle') or 'none'} "
            "(docs/RELIABILITY.md § divergence runbook)")

    # --- recovery ----------------------------------------------------------

    def restore(self, state_template, action: GuardAction):
        """Mesh-portable LKG restore (`trainer/checkpoint.Checkpointer`),
        fenced behind the ring's async saves. Returns the restored state;
        the caller fast-forwards the loader to `action.resume_position`."""
        ckpt = self._checkpointer()
        ckpt.wait()
        state, _extra, step = ckpt.restore(
            state_template, step=action.lkg_step, mesh=self.mesh,
            tp=self.tp)
        return state, step

    # --- evidence ----------------------------------------------------------

    def _dump_bundle(self, stash: _Stash, verdict: dict) -> str:
        path = os.path.join(self.output_dir, REPLAY_DIRNAME,
                            f"step_{stash.step}")
        meta = {
            "step": stash.step,
            "seed": self.seed,
            "position": dict(stash.position),
            "verdict": {k: v for k, v in verdict.items()
                        if k not in ("position", "bundle")},
            "config": self.config_dict,
            "note": "rng = RngManager(seed).step_key(step); batch leaves "
                    "below (bf16 widened to f32; see arrays.*.source_dtype)",
        }
        try:
            return dump_replay_bundle(path, stash.batch, meta)
        except Exception as e:  # noqa: BLE001 - evidence must not kill recovery
            logger.warning("guard: replay bundle dump failed (%s: %s)",
                           type(e).__name__, e)
            return ""

    def _event(self, action: str, **info) -> None:
        self.events.append({"action": action, **info})
        del self.events[:-64]

    def _publish(self, action: str) -> None:
        try:
            from pytorchvideo_accelerate_tpu.obs import get_registry

            get_registry().counter(
                "pva_guard_events_total",
                "TrainGuard ladder events (skip/rollback/halt/lkg_save), "
                "by action", labelnames=("action",)).inc(action=action)
        except Exception:  # pragma: no cover - telemetry stays optional
            pass

    def _warn(self, msg: str, info: dict) -> None:
        try:
            from pytorchvideo_accelerate_tpu.obs import get_recorder

            get_recorder().warn(msg, **{k: str(v)[:200]
                                        for k, v in info.items()})
        except Exception:  # pragma: no cover
            pass

    def perf_keys(self) -> Dict[str, int]:
        """fit()'s perf-dict contribution (both 0 on a clean run:
        tests/test_fit_result.py)."""
        return {"guard_rollbacks": int(self.rollbacks),
                "quarantined_clips": (len(self.quarantine)
                                      if self.quarantine is not None else 0)}

    def close(self) -> None:
        if self._ckpt is not None:
            self._ckpt.close()
            self._ckpt = None


def poison_batch(batch):
    """Chaos helper for the ``nan`` kind at `step.dispatch`
    (trainer/loop.py): NaN-poison the float clip leaves of a dispatched
    batch — the deterministic stand-in for a numerically-diverged input.
    Non-float leaves (u8 clips, labels, masks) pass through untouched."""
    import jax.numpy as jnp

    out = dict(batch)
    for k in ("video", "slow", "fast"):
        v = out.get(k)
        if v is not None and jnp.issubdtype(v.dtype, jnp.floating):
            out[k] = v * jnp.asarray(float("nan"), v.dtype)
    return out


_last_guard: Optional[TrainGuard] = None


def guard_snapshot(output_dir: str = "") -> dict:
    """Doctor view (`pva-tpu-doctor diagnose()`): LKG step + ring contents,
    rollback/skip counts, last anomaly verdict, quarantine list, and —
    given an output_dir — the on-disk replay bundles and quarantine
    sidecar a SECOND shell can read while the run is wedged or dead."""
    out: dict = {}
    g = _last_guard
    out["armed"] = g is not None
    if g is not None:
        out.update(lkg_step=g.lkg_step, lkg_ring=g.ring_steps(),
                   rollbacks=g.rollbacks, skips=g.skips,
                   last_verdict=g.last_verdict,
                   last_rollback=g.last_rollback,
                   events=list(g.events[-10:]))
        if g.quarantine is not None:
            out["quarantine"] = g.quarantine.snapshot()
    if output_dir:
        rdir = os.path.join(output_dir, REPLAY_DIRNAME)
        try:
            out["replay_bundles"] = sorted(
                d for d in os.listdir(rdir) if d.startswith("step_"))
        except OSError:
            out["replay_bundles"] = []
        sidecar = os.path.join(output_dir, "quarantine.json")
        if os.path.exists(sidecar):
            try:
                with open(sidecar) as f:
                    out["quarantine_sidecar"] = json.load(f)
            except (OSError, ValueError) as e:
                out["quarantine_sidecar"] = {
                    "error": f"{type(e).__name__}: {e}"}
    return out

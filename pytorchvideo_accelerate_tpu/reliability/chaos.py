"""pva-tpu-chaos: the bundled chaos scenario + console script.

The proving harness for the resilience substrate (docs/RELIABILITY.md),
same contract as `pva-tpu-lint`/`pva-tpu-tsan`: a seeded scenario runs
every recovery path the way production fails, asserts recovery, and
`chaos_findings == 0` gates `scripts/analyze.sh`.

Legs (all seeded via one `--seed`, CPU-only, replayable):

- **replay**: the same FaultPlan seed must fire the identical hit
  sequence twice — the property every other leg's determinism rests on;
- **decode**: injected decode failures on a real (tiny, generated) video
  tree; the retry + substitution machinery must deliver every batch;
- **ckpt**: a partial-write fault inside the atomic artifact writer; the
  retry must land a complete artifact and the destination must never
  hold a truncated file — not even transiently;
- **tracker**: a transient tracker outage recovers via retry (no metric
  loss); a permanent one disables the tracker without killing anything;
- **preempt**: a real SIGTERM mid-epoch (slow-worker + slow-dispatch
  faults armed) takes the grace path — emergency checkpoint, flight
  dump, exit 0 — and `resume=auto` lands on the exact step and finishes
  the run;
- **preempt_mesh**: the same grace path across a MESH RESHAPE — a
  forced-host subprocess trains on a (2, 2) (data, model) train mesh,
  SIGTERMs itself mid-run, and a second subprocess resumes with
  `resume=auto` on a (4, 1) mesh: it must land on the exact emergency
  step and finish (the mesh-portable-checkpoint contract,
  docs/PARALLELISM.md runbook);
- **quarantine**: a deterministically-corrupt clip (seeded garbage bytes)
  exhausts its persisted failure budget across runs, lands in the
  quarantine sidecar, and the NEXT run's sampler excludes it with zero
  decode attempts — every epoch still delivers full batches;
- **guard_nan**: seeded NaN poisoning of two consecutive dispatches — the
  in-graph skip absorbs the first, the TrainGuard ladder rolls the second
  back to the last-known-good step with the loader fast-forwarded past
  the poisoned span, the run finishes with finite loss, and the replay
  bundle is loadable + byte-deterministic;
- **collective_hang**: a wedged mesh `psum` (injected delay inside the
  watched section, forced-host child) trips the watchdog DURING the
  wedge with per-host, per-op attribution — evidence before the external
  kill;
- **dataplane_kill**: two real decode-worker processes feed a
  RemoteClipFeed; one is SIGKILLed mid-epoch with leases outstanding —
  the unacked span re-leases to the survivor, the batch stream stays
  byte-identical to the local loader's (zero duplicate/missing), and the
  dead worker's quarantine verdicts survive in the persisted sidecar;
- **serve**: synthetic overload against a micro-batcher + admission
  controller — load sheds with 503/Retry-After semantics before latency
  collapses, an injected flush fault fails one batch (not the thread),
  and the service recovers to `healthy`, then drains clean;
- **replica_kill**: two real serving processes (stub engine behind the
  REAL `InferenceServer` + fleet `Scheduler`) behind the fleet router
  under open-loop load; one replica is SIGKILLed mid-load — the router
  must route around it (mid-flight requests re-dispatched, ZERO non-shed
  failures), pool membership must drop it within the health-check
  interval, and the surviving replica's p99 must return under the SLO;
- **stream_replica_kill**: two real serving processes (session-capable
  stub stream engine behind the REAL `InferenceServer` /stream +
  `Scheduler` session launches) behind the affinity router, holding
  LIVE streaming sessions; the replica holding sessions is SIGKILLed
  mid-stream — affinity re-routes to the survivor, every affected
  session re-establishes DETERMINISTICALLY from its client's resendable
  window (each label's logits must equal the window-content expectation
  recomputed client-side, i.e. the stream resumes at the correct window
  position), with zero non-shed client-visible failures.

Exit codes: 0 clean, 1 findings, 2 usage.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import threading
import time
from typing import Callable, List, Optional

from pytorchvideo_accelerate_tpu.reliability import faults
from pytorchvideo_accelerate_tpu.reliability.faults import FaultPlan, FaultSpec
from pytorchvideo_accelerate_tpu.reliability.preemption import (
    get_guard,
    read_emergency_record,
)
from pytorchvideo_accelerate_tpu.utils.sync import make_thread

Log = Callable[[str], None]


def _leg(report: dict, name: str) -> dict:
    out = report["legs"].setdefault(name, {})
    return out


def _finding(report: dict, leg: str, msg: str) -> None:
    report["findings"].append(f"{leg}: {msg}")


# --- legs -------------------------------------------------------------------

def leg_replay(report: dict, seed: int, log: Log) -> None:
    """Same seed → byte-identical fault sequence (the determinism every
    chaos assertion rests on)."""
    leg = _leg(report, "replay")

    def one_run() -> List[tuple]:
        faults.arm(FaultPlan(seed, [
            FaultSpec("decode.read", kind="raise", p=0.4),
            FaultSpec("prefetch.h2d", kind="delay", p=0.3, delay_s=0.0),
        ]))
        try:
            for _ in range(64):
                try:
                    faults.fault_point("decode.read")
                except faults.InjectedFault:
                    pass
                faults.fault_point("prefetch.h2d")
        finally:
            faults.disarm()
        return [(e["point"], e["hit"], e["kind"])
                for e in faults.fault_history()]

    a, b = one_run(), one_run()
    leg["fires"] = len(a)
    if not a:
        _finding(report, "replay", "seeded plan fired nothing in 64 hits")
    if a != b:
        _finding(report, "replay",
                 f"fault sequence not replayable: {a[:5]} != {b[:5]}")
    log(f"[chaos] replay: {len(a)} fires, sequences identical={a == b}")


def _write_video_tree(root: str, n_per_class: int = 2) -> bool:
    """Tiny real mp4 tree (2 classes); False when the codec is missing."""
    try:
        import cv2
        import numpy as np
    except Exception:
        return False
    rng = np.random.default_rng(0)
    for c in range(2):
        d = os.path.join(root, f"class{c}")
        os.makedirs(d, exist_ok=True)
        for v in range(n_per_class):
            wr = cv2.VideoWriter(os.path.join(d, f"v{v}.mp4"),
                                 cv2.VideoWriter_fourcc(*"mp4v"),
                                 30.0, (48, 32))
            if not wr.isOpened():
                return False
            for _ in range(12):
                wr.write(rng.integers(0, 255, (32, 48, 3), np.uint8))
            wr.release()
    return True


def leg_decode(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """Injected decode failures must never cost a batch: transient ones
    recover via retry, persistent ones via the substitution path."""
    from pytorchvideo_accelerate_tpu.data.manifest import scan_directory
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader,
        VideoClipSource,
    )
    from pytorchvideo_accelerate_tpu.data.transforms import make_transform

    leg = _leg(report, "decode")
    root = os.path.join(tmpdir, "videos")
    if not _write_video_tree(root, n_per_class=3):
        leg["skipped"] = "no mp4 codec on this host"
        log("[chaos] decode: skipped (no codec)")
        return
    tf = make_transform(training=True, num_frames=4, crop_size=24,
                        min_short_side_scale=26, max_short_side_scale=30)
    src = VideoClipSource(scan_directory(root), tf, clip_duration=0.2,
                          training=True, seed=seed, decode_retries=2,
                          retry_base_delay_s=0.001)
    # num_workers=1: the per-point hit SEQUENCE is seeded either way, but
    # a single decode thread makes the whole leg's timeline replayable
    loader = ClipLoader(src, global_batch_size=2, shuffle=True,
                        num_workers=1, seed=seed)
    faults.arm(FaultPlan(seed, [FaultSpec("decode.read", kind="raise",
                                          p=0.35)]))
    try:
        batches = sum(1 for _ in loader.epoch(0))
    finally:
        faults.disarm()
        loader.close()
    fires = len(faults.fault_history())
    leg.update(batches=batches, fires=fires)
    want = loader.batches_per_epoch()
    if batches != want:
        _finding(report, "decode",
                 f"epoch yielded {batches}/{want} batches under faults")
    if fires == 0:
        _finding(report, "decode", "no decode faults fired (vacuous leg)")
    log(f"[chaos] decode: {batches}/{want} batches with {fires} injected "
        "failures")


def leg_ckpt(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """A write that dies mid-file must retry into a COMPLETE artifact and
    never leave a truncated file at the destination."""
    import jax.numpy as jnp
    import optax

    from pytorchvideo_accelerate_tpu.reliability.atomic import (
        atomic_write_bytes,
    )
    from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
        export_inference,
        load_inference,
    )
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    leg = _leg(report, "ckpt")
    state = TrainState.create(
        {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}, {}, optax.sgd(0.1))
    art = os.path.join(tmpdir, "artifact")
    # one partial-write on the FIRST write of the export: the tmp file is
    # truncated and the write raises; the retry must produce a complete
    # artifact, and the destination must never have held the prefix
    faults.arm(FaultPlan(seed, [FaultSpec("ckpt.write",
                                          kind="partial_write",
                                          at_hits=(0,), max_fires=1)]))
    try:
        export_inference(art, state, meta={"num_classes": 4,
                                           "model": "tiny"})
    finally:
        faults.disarm()
    fires = len(faults.fault_history())
    try:
        params, _stats, meta = load_inference(art)
        complete = "w" in params and meta.get("num_classes") == 4
    except Exception as e:  # noqa: BLE001 - the artifact IS the assertion
        complete = False
        leg["load_error"] = f"{type(e).__name__}: {e}"
    leftovers = [f for f in os.listdir(art) if ".tmp" in f]
    leg.update(fires=fires, complete=complete, tmp_leftovers=leftovers)
    if fires != 1:
        _finding(report, "ckpt", f"expected 1 injected write fault, {fires}")
    if not complete:
        _finding(report, "ckpt", "artifact incomplete after retried write")
    if leftovers:
        _finding(report, "ckpt", f"tmp files left behind: {leftovers}")
    # and when every attempt dies, the destination must simply not exist
    dead = os.path.join(tmpdir, "dead.json")
    faults.arm(FaultPlan(seed, [FaultSpec("ckpt.write", kind="raise")]))
    try:
        atomic_write_bytes(dead, b"{}")
    except faults.InjectedFault:
        pass
    else:
        _finding(report, "ckpt", "always-failing write did not raise")
    finally:
        faults.disarm()
    if os.path.exists(dead):
        _finding(report, "ckpt", "failed write left a destination file")
    log(f"[chaos] ckpt: retried partial write -> complete={complete}, "
        f"no tmp leftovers={not leftovers}")


def leg_tracker(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """Transient tracker outage: retry recovers, zero metric loss.
    Permanent outage: disabled after the budget, run unharmed."""
    from pytorchvideo_accelerate_tpu.trainer.tracking import TrackerHub

    leg = _leg(report, "tracker")
    logdir = os.path.join(tmpdir, "logs")
    hub = TrackerHub("jsonl", logdir, retries=3)
    hub.start("chaosrun", {})
    # hit 0 was start(); fail the first attempt of the first two log()
    # fan-outs — each retries into the next hit, which succeeds
    faults.arm(FaultPlan(seed, [FaultSpec("tracker.log", kind="raise",
                                          at_hits=(1, 3), max_fires=2)]))
    try:
        for i in range(5):
            hub.log({"x": float(i)}, step=i)
    finally:
        faults.disarm()
    fires = len(faults.fault_history())
    hub.finish()
    path = os.path.join(logdir, "chaosrun.jsonl")
    with open(path) as f:
        steps = [json.loads(ln).get("step") for ln in f
                 if "step" in ln]
    leg.update(fires=fires, survivors=len(hub.trackers), logged=len(steps))
    if len(hub.trackers) != 1:
        _finding(report, "tracker",
                 "transient outage disabled the tracker despite retries")
    if sorted(s for s in steps if s is not None) != [0, 1, 2, 3, 4]:
        _finding(report, "tracker", f"metric loss under outage: {steps}")
    # permanent outage: every attempt fails -> disabled, nothing raises
    hub2 = TrackerHub("jsonl", logdir, retries=2)
    hub2.start("chaosrun2", {})
    faults.arm(FaultPlan(seed, [FaultSpec("tracker.log", kind="raise")]))
    try:
        hub2.log({"x": 1.0}, step=0)
    finally:
        faults.disarm()
    if hub2.trackers:
        _finding(report, "tracker",
                 "permanently failing tracker was not disabled")
    hub2.finish()
    log(f"[chaos] tracker: {fires} injected failures, "
        f"{len(steps)} steps logged, survivors={len(hub.trackers)}")


def leg_preempt(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """Mid-epoch SIGTERM under slow-worker faults: grace path saves at the
    consumed step, exits clean; resume=auto lands exactly there and
    finishes the run.

    This leg is also the live regression test for the resume
    re-materialization in `Checkpointer.restore`: resuming mid-epoch and
    TRAINING on the restored state with jax's persistent compilation
    cache enabled (every entry point configures one) heap-corrupted the pinned
    jaxlib until restore started copying every leaf into an XLA-owned
    buffer."""
    from pytorchvideo_accelerate_tpu.config import (
        CheckpointConfig, DataConfig, ModelConfig, OptimConfig, TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    leg = _leg(report, "preempt")
    outdir = os.path.join(tmpdir, "run")

    def cfg(resume: str = "") -> TrainConfig:
        return TrainConfig(
            model=ModelConfig(name="tiny3d", num_classes=4,
                              dropout_rate=0.0),
            data=DataConfig(synthetic=True, synthetic_num_videos=16,
                            num_frames=4, crop_size=24, batch_size=2,
                            num_workers=1, limit_val_batches=1),
            optim=OptimConfig(num_epochs=2, lr=0.01),
            checkpoint=CheckpointConfig(output_dir=outdir,
                                        resume_from_checkpoint=resume),
            seed=seed,
        )

    tr = Trainer(cfg())
    total = tr.total_steps
    # slow worker + slow dispatch: every step pays an injected delay, so
    # the SIGTERM below always lands mid-epoch, never after the run
    faults.arm(FaultPlan(seed, [
        FaultSpec("step.dispatch", kind="delay", p=1.0, delay_s=0.05),
        FaultSpec("prefetch.h2d", kind="delay", p=0.5, delay_s=0.01),
    ]))
    # pre-install the guard so the kill can never race the dump-only
    # handler during Trainer warmup; fit()'s own install is then a no-op
    get_guard().install()
    in_fit = threading.Event()
    in_fit.set()

    def killer():
        time.sleep(0.4)
        if in_fit.is_set():  # a real signal, mid-epoch, main thread
            os.kill(os.getpid(), __import__("signal").SIGTERM)

    kt = make_thread(target=killer, name="chaos-sigterm", daemon=True)
    kt.start()
    try:
        res = tr.fit()
    finally:
        in_fit.clear()
        kt.join(timeout=5.0)
        faults.disarm()
        get_guard().uninstall()
    rec = read_emergency_record(outdir)
    leg.update(preempted=bool(res.get("preempted")),
               stopped_at=res.get("steps"),
               emergency=rec and {"step": rec["step"],
                                  "reason": rec.get("reason")},
               total_steps=total)
    if not res.get("preempted"):
        _finding(report, "preempt", "SIGTERM did not take the grace path")
        return
    if rec is None:
        _finding(report, "preempt", "no emergency_checkpoint.json record")
        return
    if not 0 < rec["step"] < total:
        _finding(report, "preempt",
                 f"emergency step {rec['step']} not mid-run (total {total})")
    # recovery: resume=auto must land on the EXACT saved step and finish
    tr2 = Trainer(cfg(resume="auto"))
    latest = tr2.checkpointer.latest_step()
    if latest != rec["step"]:
        _finding(report, "preempt",
                 f"resume=auto found step {latest}, emergency saved "
                 f"{rec['step']}")
    res2 = tr2.fit()
    leg["resumed_to"] = res2.get("steps")
    if res2.get("preempted") or res2.get("steps") != total:
        _finding(report, "preempt",
                 f"resumed run did not complete: {res2.get('steps')}/"
                 f"{total} steps")
    log(f"[chaos] preempt: SIGTERM at step {rec['step']}/{total}, "
        f"resumed and finished at {res2.get('steps')}")


# the (data, model) shapes the mesh-reshape preemption leg crosses, and the
# forced-host device count both subprocesses run under
_MESH_LEG_DEVICES = 4
_MESH_LEG_TRAIN = (2, 2)
_MESH_LEG_RESUME = (4, 1)

# subprocess body for both phases of leg_preempt_mesh: train tiny3d on a
# (data, model) train mesh; in "kill" mode, self-SIGTERM shortly after fit
# starts (the preemption guard is pre-installed, so the signal takes the
# grace path wherever it lands — compile or step loop) and report the
# emergency record; in resume mode, resume=auto on the reshaped mesh and
# report the step it landed on. One JSON line to stdout (forcehost
# contract).
_MESH_LEG_CODE = """
import json, os, sys, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
from pytorchvideo_accelerate_tpu.config import (
    CheckpointConfig, DataConfig, MeshConfig, ModelConfig, OptimConfig,
    TrainConfig)
from pytorchvideo_accelerate_tpu.reliability.preemption import (
    get_guard, read_emergency_record)
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

outdir, kill, data_ax, model_ax, seed = (
    {outdir!r}, {kill!r} == "kill", {data_ax}, {model_ax}, {seed})
cfg = TrainConfig(
    mesh=MeshConfig(data=data_ax, model=model_ax),
    model=ModelConfig(name="tiny3d", num_classes=4, dropout_rate=0.0),
    data=DataConfig(synthetic=True, synthetic_num_videos=16, num_frames=4,
                    crop_size=24, batch_size=2, num_workers=1,
                    limit_val_batches=1),
    optim=OptimConfig(num_epochs=2, lr=0.01),
    checkpoint=CheckpointConfig(output_dir=outdir,
                                resume_from_checkpoint="" if kill
                                else "auto"),
    seed=seed,
)
tr = Trainer(cfg)
found = (tr.checkpointer.latest_step()
         if (not kill and tr.checkpointer) else None)
if kill:
    get_guard().install()  # never race the dump-only default handler
    t = threading.Thread(
        target=lambda: (time.sleep(0.5),
                        os.kill(os.getpid(), __import__("signal").SIGTERM)),
        daemon=True)
    t.start()
res = tr.fit()
rec = read_emergency_record(outdir)
out = {{"mesh": [data_ax, model_ax], "preempted": bool(res.get("preempted")),
        "steps": res.get("steps"), "total": tr.total_steps,
        "emergency_step": rec and rec.get("step"), "found": found}}
print("\\n" + json.dumps(out))
"""


def leg_preempt_mesh(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """Preemption grace across a mesh reshape: SIGTERM on a (2, 2) train
    mesh, emergency save, `resume=auto` on (4, 1) lands on the same step
    and finishes. Both halves run in forced-host subprocesses (this
    process's device count latched at backend init and cannot change), so
    the leg exercises the REAL cross-shape restore path — orbax resharding
    into the new mesh's layouts — not an in-process approximation."""
    from pytorchvideo_accelerate_tpu.utils.forcehost import run_forced_host

    leg = _leg(report, "preempt_mesh")
    outdir = os.path.join(tmpdir, "mesh_run")

    def phase(kill: str, shape) -> dict:
        return run_forced_host(
            _MESH_LEG_CODE.format(outdir=outdir, kill=kill,
                                  data_ax=shape[0], model_ax=shape[1],
                                  seed=seed),
            _MESH_LEG_DEVICES, timeout=420.0)

    a = phase("kill", _MESH_LEG_TRAIN)
    leg["train"] = a
    if not a.get("preempted"):
        _finding(report, "preempt_mesh",
                 "SIGTERM did not take the grace path on the (2,2) mesh")
        return
    if not a.get("emergency_step"):
        _finding(report, "preempt_mesh", "no emergency checkpoint record")
        return
    b = phase("resume", _MESH_LEG_RESUME)
    leg["resume"] = b
    # resume=auto must FIND the exact emergency step (the reshaped restore
    # re-places every leaf under the new mesh's shardings; a step drift
    # means it read stale or partial state), then run to completion
    if b.get("found") != a["emergency_step"]:
        _finding(report, "preempt_mesh",
                 f"resume=auto on the reshaped mesh found step "
                 f"{b.get('found')}, emergency saved {a['emergency_step']}")
    if b.get("preempted") or (b.get("steps") or 0) < a["emergency_step"]:
        _finding(report, "preempt_mesh",
                 f"resume on the reshaped mesh did not complete: {b}")
        return
    log(f"[chaos] preempt_mesh: SIGTERM at step {a['emergency_step']} on "
        f"mesh {a['mesh']}, resume=auto on {b['mesh']} landed on the same "
        f"step and finished at {b['steps']}")


# subprocess body for both phases of leg_preempt_pipeline: VideoMAE-tiny
# PRETRAIN on a (data, model) train mesh, pipelined over the model axis in
# the kill phase (parallel/pipeline.py) and UNPIPELINED on the reshaped
# resume mesh — the checkpoint-interchange contract the pipeline's
# param-tree identity exists to keep. Same forcehost one-JSON-line shape
# as _MESH_LEG_CODE.
_PIPELINE_LEG_CODE = """
import json, os, sys, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
from pytorchvideo_accelerate_tpu.config import (
    CheckpointConfig, DataConfig, MeshConfig, ModelConfig, OptimConfig,
    ParallelConfig, TrainConfig)
from pytorchvideo_accelerate_tpu.reliability.preemption import (
    get_guard, read_emergency_record)
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

outdir, kill, data_ax, model_ax, stages, bsz, seed = (
    {outdir!r}, {kill!r} == "kill", {data_ax}, {model_ax}, {stages},
    {bsz}, {seed})
cfg = TrainConfig(
    mesh=MeshConfig(data=data_ax, model=model_ax),
    parallel=ParallelConfig(pipeline_stages=stages,
                            pipeline_microbatches=2 if stages > 1 else 0),
    model=ModelConfig(name="videomae_t_pretrain", num_classes=4,
                      dropout_rate=0.0),
    data=DataConfig(synthetic=True, synthetic_num_videos=16, num_frames=4,
                    crop_size=32, batch_size=bsz, num_workers=1,
                    limit_val_batches=1),
    optim=OptimConfig(num_epochs=2, lr=0.01),
    checkpoint=CheckpointConfig(output_dir=outdir,
                                resume_from_checkpoint="" if kill
                                else "auto"),
    seed=seed,
)
tr = Trainer(cfg)
found = (tr.checkpointer.latest_step()
         if (not kill and tr.checkpointer) else None)
if kill:
    get_guard().install()  # never race the dump-only default handler
    t = threading.Thread(
        target=lambda: (time.sleep(0.5),
                        os.kill(os.getpid(), __import__("signal").SIGTERM)),
        daemon=True)
    t.start()
res = tr.fit()
rec = read_emergency_record(outdir)
out = {{"mesh": [data_ax, model_ax], "stages": stages,
        "preempted": bool(res.get("preempted")),
        "steps": res.get("steps"), "total": tr.total_steps,
        "emergency_step": rec and rec.get("step"), "found": found,
        "bubble_frac": res.get("pipeline_bubble_frac_analytic")}}
print("\\n" + json.dumps(out))
"""


def leg_preempt_pipeline(report: dict, tmpdir: str, seed: int,
                         log: Log) -> None:
    """Leg 14 — preemption grace across a PIPELINE layout change: SIGTERM
    mid-pipelined-epoch on a (2, 2) mesh running the VideoMAE pretrain
    trunk as a 2-stage pipeline, emergency save, `resume=auto` on (4, 1)
    UNPIPELINED lands on the exact step and finishes. Extends
    leg_preempt_mesh to the pipelined layout: the stage pipeline keeps
    its param tree identical to the plain model (parallel/pipeline.py),
    so the reshaped restore needs no conversion — this leg is the
    runtime proof. Both phases keep the same GLOBAL batch (per-shard
    batch_size compensates for the data-axis change), so steps/epoch and
    the resume arithmetic line up across layouts."""
    from pytorchvideo_accelerate_tpu.utils.forcehost import run_forced_host

    leg = _leg(report, "preempt_pipeline")
    outdir = os.path.join(tmpdir, "pipeline_run")

    def phase(kill: str, shape, stages: int, bsz: int) -> dict:
        return run_forced_host(
            _PIPELINE_LEG_CODE.format(outdir=outdir, kill=kill,
                                      data_ax=shape[0], model_ax=shape[1],
                                      stages=stages, bsz=bsz, seed=seed),
            _MESH_LEG_DEVICES, timeout=420.0)

    # global batch 8 in both phases: (2,2) pipelined at 4/shard,
    # (4,1) unpipelined at 2/shard
    a = phase("kill", _MESH_LEG_TRAIN, stages=2, bsz=4)
    leg["train"] = a
    if not a.get("preempted"):
        _finding(report, "preempt_pipeline",
                 "SIGTERM did not take the grace path on the pipelined "
                 "(2,2) mesh")
        return
    if not a.get("emergency_step"):
        _finding(report, "preempt_pipeline",
                 "no emergency checkpoint record from the pipelined run")
        return
    b = phase("resume", _MESH_LEG_RESUME, stages=1, bsz=2)
    leg["resume"] = b
    if b.get("found") != a["emergency_step"]:
        _finding(report, "preempt_pipeline",
                 f"resume=auto unpipelined on the reshaped mesh found step "
                 f"{b.get('found')}, emergency saved {a['emergency_step']}")
    if b.get("preempted") or (b.get("steps") or 0) < a["emergency_step"]:
        _finding(report, "preempt_pipeline",
                 f"unpipelined resume did not complete: {b}")
        return
    log(f"[chaos] preempt_pipeline: SIGTERM at step {a['emergency_step']} "
        f"on pipelined mesh {a['mesh']} (P={a['stages']}), resume=auto "
        f"unpipelined on {b['mesh']} landed on the same step and finished "
        f"at {b['steps']}")


# serving-control-plane engine double (bucket geometry + a host-side
# forward slow enough to build a queue; no jax — the serving legs measure
# the control plane, not the chip) — the shared serving/stub.py double
from pytorchvideo_accelerate_tpu.serving.stub import StubEngine as _StubEngine  # noqa: E402


def leg_serve(report: dict, seed: int, log: Log) -> None:
    """Synthetic overload: shed with Retry-After semantics before the
    queue saturates, survive an injected flush fault, recover to healthy,
    then drain clean."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.serving.admission import (
        AdmissionController,
    )
    from pytorchvideo_accelerate_tpu.serving.batcher import (
        MicroBatcher,
        QueueFullError,
    )
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats

    leg = _leg(report, "serve")
    stats = ServingStats(window=256)
    mb = MicroBatcher(_StubEngine(forward_s=0.02), max_wait_ms=1.0,
                      max_queue=16, stats=stats, retry_after_s=0.25)
    stats.queue_depth_fn = mb.queue_depth
    ac = AdmissionController(max_queue=16, shed_frac=0.5, recover_frac=0.2,
                             retry_after_s=0.25)
    clip = {"video": np.zeros((2, 4, 4, 3), np.float32)}
    served, shed, errors = [], [], []
    # one injected flush failure partway through the flood: the batch's
    # futures must fail (the 500 path) without taking the flush thread
    faults.arm(FaultPlan(seed, [FaultSpec("serve.flush", kind="raise",
                                          at_hits=(3,), max_fires=1)]))

    def client(k: int):
        # open-loop arrival: submit the whole burst without waiting
        # (overload means arrivals outrun the drain), collect at the end;
        # the admit-then-submit sequence mirrors server.py's do_POST
        futs = []
        for _ in range(40):
            ok, retry_after = ac.admit(mb.queue_depth())
            if not ok:
                stats.observe_shed(ac.state())
                shed.append(retry_after)
                continue
            try:
                futs.append(mb.submit(clip))
            except QueueFullError as e:
                shed.append(e.retry_after_s)
        for fut in futs:
            try:
                served.append(fut.result(timeout=30.0))
            except Exception as e:  # noqa: BLE001 - injected flush fault
                errors.append(type(e).__name__)

    try:
        ts = [make_thread(target=client, args=(k,), name=f"chaos-client-{k}",
                          daemon=True) for k in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30.0)
        # the flood is over: depth drains, the state machine must recover
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and mb.queue_depth() > 0:
            time.sleep(0.01)
        ac.admit(mb.queue_depth())  # drives degraded -> healthy
        recovered = ac.state()
        # drain: stop admitting, flush in-flight, close
        ac.start_draining()
        drained_admit = ac.admit(0)
        drained = mb.drain(timeout_s=5.0)
    finally:
        faults.disarm()
        mb.close()
    fires = len(faults.fault_history())
    snap = stats.snapshot()
    leg.update(served=len(served), shed=len(shed), errors=len(errors),
               flush_fires=fires, recovered_state=recovered,
               stats_shed=snap["shed"], drained=drained)
    if not shed:
        _finding(report, "serve", "overload never shed (vacuous leg)")
    if shed and min(shed) <= 0:
        _finding(report, "serve", "shed without a Retry-After hint")
    if not served:
        _finding(report, "serve", "overload starved every request")
    if fires and not errors:
        _finding(report, "serve",
                 "injected flush fault did not surface to any future")
    if recovered != "healthy":
        _finding(report, "serve",
                 f"state stuck at {recovered!r} after the flood drained")
    if snap["shed"] <= 0:
        _finding(report, "serve", "shed counter not visible on /stats")
    if drained_admit[0]:
        _finding(report, "serve", "draining state admitted a request")
    if not drained:
        _finding(report, "serve", "drain left requests queued")
    log(f"[chaos] serve: {len(served)} served, {len(shed)} shed, "
        f"{len(errors)} failed by injected flush fault, "
        f"recovered={recovered!r}, drained={drained}")


# subprocess body for leg_replica_kill: the shared stub engine (host-side
# forward, no model compile) behind the REAL fleet Scheduler +
# InferenceServer, so the leg's HTTP surface, shed mapping, and /healthz
# state are production code. One JSON line {{"url": ...}} to stdout once
# bound, then serve.
_REPLICA_SRV_CODE = """
import json
from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
from pytorchvideo_accelerate_tpu.serving.server import InferenceServer
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
from pytorchvideo_accelerate_tpu.serving.stub import StubEngine

engine = StubEngine(forward_s={forward_s})
engine.model_name = "chaos-stub"
stats = ServingStats(window=512)
sched = Scheduler(engine, stats=stats, max_queue=128,
                  realtime_deadline_ms=10000.0)
srv = InferenceServer(engine, sched, stats, host="127.0.0.1", port=0,
                      request_timeout_s=30.0)
host, port = srv.address
print(json.dumps({{"url": "http://%s:%d" % (host, port)}}), flush=True)
srv.serve_forever(drain_on_sigterm=False)
"""

# SLO the surviving replica's post-kill probe burst must hold: the stub
# forward is ~5 ms, so 500 ms p99 means "recovered", not "fast hardware"
_KILL_RECOVERY_SLO_MS = 500.0


def _read_url_line(proc, timeout_s: float = 90.0) -> str:
    """First stdout line of a replica subprocess, with a deadline (a
    replica that never binds must fail the leg, not hang the scenario);
    the wedge-safe reader lives in fleet/pool.py, shared with every other
    spawn site."""
    from pytorchvideo_accelerate_tpu.fleet.pool import read_line_with_deadline

    line, eof = read_line_with_deadline(proc, timeout_s,
                                        name="chaos-replica-read")
    if not (line or "").strip():
        raise RuntimeError(
            f"replica subprocess {'closed stdout' if eof else 'produced no URL'}"
            f" within {timeout_s}s (exit={proc.poll()})")
    return json.loads(line)["url"]


def leg_replica_kill(report: dict, seed: int, log: Log) -> None:
    """SIGKILL one of two serving processes mid-load: the fleet router
    routes around it with zero non-shed failures, membership drops it
    within the health interval, and recovered p99 holds the SLO."""
    import signal as _signal
    import subprocess

    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.loadgen import (
        LoadGen,
        heavy_tail_clip_factory,
    )
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        HttpReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router

    leg = _leg(report, "replica_kill")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs: List[subprocess.Popen] = []
    router = None
    health_interval_s = 0.25
    try:
        for _ in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 _REPLICA_SRV_CODE.format(forward_s=0.005)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True))
        replicas = [HttpReplica(f"kill-{i}", _read_url_line(p),
                                pid=p.pid, timeout_s=20.0)
                    for i, p in enumerate(procs)]
        pool = ReplicaPool(replicas, health_interval_s=health_interval_s)
        router = Router(pool, retries=3)

        clip = {"video": np.zeros((2, 4, 4, 3), np.float32)}
        kill_at: dict = {}

        def killer():
            time.sleep(0.8)  # mid-load, by construction
            kill_at["t"] = time.monotonic()
            os.kill(procs[0].pid, _signal.SIGKILL)

        kt = make_thread(target=killer, name="chaos-replica-kill",
                         daemon=True)
        kt.start()
        load = LoadGen(router.submit, rate_rps=40.0, duration_s=2.5,
                       clip_factory=heavy_tail_clip_factory(clip),
                       seed=seed).run()
        kt.join(timeout=5.0)
        # membership: the dead replica must leave the routable set within
        # (about) one health interval of the kill — route-around on the
        # request path is immediate, this checks the poller's verdict too
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline and len(pool.routable()) != 1:
            time.sleep(0.02)
        detected_s = time.monotonic() - kill_at.get("t", time.monotonic())
        routable = len(pool.routable())
        # recovery: a fresh probe burst rides the survivor only; its p99
        # must be back under the SLO (no lingering dead-replica timeouts)
        probe = LoadGen(router.submit, rate_rps=40.0, duration_s=1.0,
                        clip_factory=heavy_tail_clip_factory(clip),
                        seed=seed + 1).run()
        snap = router.fleet_snapshot()
        leg.update(load={k: load[k] for k in
                         ("offered", "completed", "failed", "shed",
                          "p99_ms", "open_loop_ok")},
                   probe={k: probe[k] for k in
                          ("completed", "failed", "p99_ms")},
                   routable=routable,
                   detected_s=round(detected_s, 3),
                   router_retries=snap.get("router_retries"))
        if load["failed"] > 0:
            _finding(report, "replica_kill",
                     f"{int(load['failed'])} non-shed failures under the "
                     "kill (route-around must re-dispatch)")
        if routable != 1:
            _finding(report, "replica_kill",
                     f"dead replica still routable ({routable} routable "
                     f"after {detected_s:.2f}s; interval "
                     f"{health_interval_s}s)")
        if probe["failed"] > 0 or probe["completed"] <= 0:
            _finding(report, "replica_kill",
                     f"post-kill probe burst unhealthy: {probe}")
        if probe["p99_ms"] > _KILL_RECOVERY_SLO_MS:
            _finding(report, "replica_kill",
                     f"recovered p99 {probe['p99_ms']} ms > "
                     f"{_KILL_RECOVERY_SLO_MS} ms SLO")
        log(f"[chaos] replica_kill: {int(load['completed'])} served "
            f"through the kill ({int(load['failed'])} failed, "
            f"{snap.get('router_retries')} re-dispatched), dead replica "
            f"out in {detected_s:.2f}s, recovered p99 "
            f"{probe['p99_ms']} ms")
    finally:
        if router is not None:
            router.close()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10.0)
            except Exception:
                pass


# subprocess body for leg_stream_replica_kill: the session-capable stub
# stream engine behind the REAL fleet Scheduler (session launches) +
# InferenceServer (/stream endpoint, 409 resend protocol). One JSON line
# {{"url": ...}} once bound, then serve.
_STREAM_SRV_CODE = """
import json
from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
from pytorchvideo_accelerate_tpu.serving.server import InferenceServer
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
from pytorchvideo_accelerate_tpu.serving.stub import StubStreamEngine

engine = StubStreamEngine(forward_s={forward_s})
engine.model_name = "chaos-stream-stub"
stats = ServingStats(window=512)
sched = Scheduler(engine, stats=stats, max_queue=128,
                  realtime_deadline_ms=10000.0)
srv = InferenceServer(engine, sched, stats, host="127.0.0.1", port=0,
                      request_timeout_s=30.0)
host, port = srv.address
print(json.dumps({{"url": "http://%s:%d" % (host, port)}}), flush=True)
srv.serve_forever(drain_on_sigterm=False)
"""


def leg_stream_replica_kill(report: dict, seed: int, log: Log) -> None:
    """SIGKILL the replica holding live streaming sessions mid-stream:
    affinity re-routes every session to the survivor, re-establish from
    the client's resendable window is deterministic (label logits equal
    the window-content expectation recomputed client-side — the stream
    resumes at the correct window position, not merely 'somewhere'),
    and nothing fails non-shed."""
    import signal as _signal
    import subprocess

    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.pool import (
        HttpReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.serving.stub import stub_stream_logits

    leg = _leg(report, "stream_replica_kill")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    T, S, HW, NCLS = 8, 2, 4, 4
    n_sessions, n_advances, kill_after = 4, 10, 4
    procs: List[subprocess.Popen] = []
    router = None
    rng = np.random.default_rng(seed)
    try:
        for _ in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 _STREAM_SRV_CODE.format(forward_s=0.002)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True))
        replicas = [HttpReplica(f"skill-{i}", _read_url_line(p),
                                pid=p.pid, timeout_s=20.0)
                    for i, p in enumerate(procs)]
        pool = ReplicaPool(replicas, health_interval_s=0.25)
        router = Router(pool, retries=3)

        windows = {f"st-{i}": rng.standard_normal(
            (T, HW, HW, 3)).astype(np.float32) for i in range(n_sessions)}
        failures, mismatches, sheds = 0, 0, 0
        # establish every session (spreads over both replicas: idle ties
        # rotate round-robin)
        for sid, win in windows.items():
            fut = router.submit({}, session={"sid": sid, "window": win,
                                             "stride": S})
            out = np.asarray(fut.result(timeout=30))
            want = stub_stream_logits(win, NCLS)
            if abs(out[0] - want[0]) > 1e-4:
                mismatches += 1
        holders = {sid: router._affinity.get(sid) for sid in windows}
        victim_name = replicas[0].name
        victim_sessions = [s for s, h in holders.items()
                           if h == victim_name]
        leg["victim_sessions"] = len(victim_sessions)
        killed = {"done": False}
        for k in range(n_advances):
            if k == kill_after and not killed["done"]:
                os.kill(procs[0].pid, _signal.SIGKILL)
                killed["done"] = True
                log(f"[chaos] stream_replica_kill: killed {victim_name} "
                    f"holding {len(victim_sessions)} live session(s)")
            futs = {}
            for sid in windows:
                frames = rng.standard_normal(
                    (S, HW, HW, 3)).astype(np.float32)
                windows[sid] = np.concatenate(
                    [windows[sid][S:], frames], axis=0)
                # the resendable window rides every advance — the
                # re-establish-anywhere contract replica death needs
                futs[sid] = router.submit(
                    {"video": frames},
                    session={"sid": sid, "window": windows[sid],
                             "stride": S})
            for sid, fut in futs.items():
                try:
                    out = np.asarray(fut.result(timeout=30))
                except Exception as e:  # noqa: BLE001 - verdict, not crash
                    from pytorchvideo_accelerate_tpu.serving.batcher import (
                        QueueFullError,
                    )

                    if isinstance(e, QueueFullError):
                        sheds += 1
                    else:
                        failures += 1
                    continue
                want = stub_stream_logits(windows[sid], NCLS)
                if abs(out[0] - want[0]) > 1e-4:
                    mismatches += 1
        moved = [s for s in victim_sessions
                 if router._affinity.get(s) not in (None, victim_name)]
        leg.update(advances=n_advances * n_sessions, failed=failures,
                   shed=sheds, mismatches=mismatches,
                   moved=len(moved))
        if failures:
            _finding(report, "stream_replica_kill",
                     f"{failures} non-shed client-visible failure(s) "
                     "across the kill (affinity re-route + re-establish "
                     "must absorb replica death)")
        if mismatches:
            _finding(report, "stream_replica_kill",
                     f"{mismatches} label(s) diverged from the client-"
                     "window expectation (session did not resume at the "
                     "correct window position)")
        if victim_sessions and not moved:
            _finding(report, "stream_replica_kill",
                     "no victim session re-routed off the killed replica")
        log(f"[chaos] stream_replica_kill: {n_advances * n_sessions} "
            f"advances over {n_sessions} sessions through the kill "
            f"({failures} failed, {sheds} shed, {mismatches} position "
            f"mismatches, {len(moved)}/{len(victim_sessions)} victim "
            "sessions re-homed)")
    finally:
        if router is not None:
            router.close()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10.0)
            except Exception:
                pass


# subprocess body for leg_stream_kv_kill: a REAL causal-masked
# videomae_t served through KV-ring streaming (trunk="causal") behind
# the real fleet Scheduler + InferenceServer. Deterministic by
# construction (jax.random.key(0) init, CPU) so a same-seed local
# engine reproduces every state the fleet can reach. One JSON line
# {{"url": ...}} once warmed + bound, then serve.
_KV_SRV_CODE = """
import json

import numpy as np
import jax

from pytorchvideo_accelerate_tpu.config import ModelConfig
from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
from pytorchvideo_accelerate_tpu.models import create_model
from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
from pytorchvideo_accelerate_tpu.serving.server import InferenceServer
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
from pytorchvideo_accelerate_tpu.streaming import StreamingEngine

cfg = ModelConfig(name="videomae_t", num_classes={ncls},
                  dropout_rate=0.0, attn_mask="causal")
model = create_model(cfg, "fp32")
variables = model.init(jax.random.key(0),
                       np.zeros((1, {t}, {hw}, {hw}, 3), np.float32))
engine = InferenceEngine(model, variables["params"],
                         variables.get("batch_stats", {{}}),
                         num_classes={ncls}, max_batch_size=2,
                         model_name="videomae_t")
stream = StreamingEngine(engine, session_budget_mb=32.0,
                         session_ttl_s=60.0, name="chaos-kv",
                         trunk="causal")
stream.warmup_stream({t}, {hw}, {hw}, 3, {s})
stats = ServingStats(window=512)
sched = Scheduler(stream, stats=stats, max_queue=128,
                  realtime_deadline_ms=30000.0)
srv = InferenceServer(stream, sched, stats, host="127.0.0.1", port=0,
                      request_timeout_s=60.0)
host, port = srv.address
print(json.dumps({{"url": "http://%s:%d" % (host, port)}}), flush=True)
srv.serve_forever(drain_on_sigterm=False)
"""


def leg_stream_kv_kill(report: dict, seed: int, log: Log) -> None:
    """SIGKILL the replica holding KV-BACKED streaming sessions (a real
    causal-trunk videomae_t, per-layer KV rings) mid-stream: affinity
    re-routes every session to the survivor, which re-establishes from
    the client's resendable window — rebuilding the KV ring state
    deterministically. The verdict is exact, not shape-level: every
    label through the kill must match a same-seed local engine to the
    serving tolerance, where the expectation legitimately FORKS at the
    re-establish (a fresh KV establish carries window-only context and
    window-order positions — the carry-semantics twin of hot-swap), so
    the local mirror forks exactly where the fleet did, and at least
    one victim session must take the fork."""
    import signal as _signal
    import subprocess

    import jax
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        HttpReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
    from pytorchvideo_accelerate_tpu.streaming import StreamingEngine

    leg = _leg(report, "stream_kv_kill")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    T, S, HW, NCLS = 8, 2, 16, 4
    TOL = 2e-4
    n_sessions, n_advances, kill_after = 4, 6, 3
    procs: List[subprocess.Popen] = []
    router = None
    rng = np.random.default_rng(seed)
    try:
        for _ in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 _KV_SRV_CODE.format(t=T, s=S, hw=HW, ncls=NCLS)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True))
        # the same-seed oracle, built while the replicas warm: same init
        # key + trunk mode -> bit-for-bit the weights they serve; its
        # sessions mirror the remote session state branch by branch
        cfg = ModelConfig(name="videomae_t", num_classes=NCLS,
                          dropout_rate=0.0, attn_mask="causal")
        model = create_model(cfg, "fp32")
        variables = model.init(
            jax.random.key(0), np.zeros((1, T, HW, HW, 3), np.float32))
        oracle = StreamingEngine(
            InferenceEngine(model, variables["params"],
                            variables.get("batch_stats", {}),
                            num_classes=NCLS, max_batch_size=2,
                            model_name="videomae_t"),
            session_budget_mb=32.0, session_ttl_s=600.0,
            name="chaos-kv-oracle", trunk="causal")

        def want(item):
            out = oracle.advance_batch([dict(item)])[0]
            if isinstance(out, Exception):
                raise out
            return np.asarray(out, np.float32)

        replicas = [HttpReplica(f"kvr-{i}", _read_url_line(p),
                                pid=p.pid, timeout_s=60.0)
                    for i, p in enumerate(procs)]
        pool = ReplicaPool(replicas, health_interval_s=0.25)
        router = Router(pool, retries=3)

        windows = {f"st-{i}": rng.standard_normal(
            (T, HW, HW, 3)).astype(np.float32) for i in range(n_sessions)}
        failures, sheds, mismatches, forked = 0, 0, 0, 0
        for sid, win in windows.items():
            out = np.asarray(router.submit(
                {}, session={"sid": sid, "window": win,
                             "stride": S}).result(timeout=60), np.float32)
            if float(np.max(np.abs(out - want(
                    {"sid": sid, "window": win, "stride": S})))) > TOL:
                mismatches += 1
        holders = {sid: router._affinity.get(sid) for sid in windows}
        victim_name = replicas[0].name
        victim_sessions = [s for s, h in holders.items()
                           if h == victim_name]
        leg["victim_sessions"] = len(victim_sessions)
        for k in range(n_advances):
            if k == kill_after:
                os.kill(procs[0].pid, _signal.SIGKILL)
                log(f"[chaos] stream_kv_kill: killed {victim_name} "
                    f"holding {len(victim_sessions)} KV session(s)")
            futs, sent = {}, {}
            for sid in windows:
                frames = rng.standard_normal(
                    (S, HW, HW, 3)).astype(np.float32)
                sent[sid] = frames
                windows[sid] = np.concatenate(
                    [windows[sid][S:], frames], axis=0)
                futs[sid] = router.submit(
                    {"video": frames},
                    session={"sid": sid, "window": windows[sid],
                             "stride": S})
            for sid, fut in futs.items():
                try:
                    out = np.asarray(fut.result(timeout=60), np.float32)
                except Exception as e:  # noqa: BLE001 - verdict, not crash
                    from pytorchvideo_accelerate_tpu.serving.batcher import (
                        QueueFullError,
                    )

                    if isinstance(e, QueueFullError):
                        sheds += 1
                    else:
                        failures += 1
                    continue
                adv = want({"sid": sid, "frames": sent[sid]})
                if float(np.max(np.abs(out - adv))) <= TOL:
                    continue
                # the advance expectation missed: the fleet may have
                # re-established this session from the resendable window
                # (replica death). Fork the mirror the same way and
                # re-judge — the fresh-establish KV rebuild is itself
                # deterministic, so this branch is exact too.
                oracle.end_session(sid)
                est = want({"sid": sid, "window": windows[sid],
                            "stride": S})
                if float(np.max(np.abs(out - est))) <= TOL:
                    forked += 1
                    continue
                mismatches += 1
        moved = [s for s in victim_sessions
                 if router._affinity.get(s) not in (None, victim_name)]
        leg.update(advances=n_advances * n_sessions, failed=failures,
                   shed=sheds, mismatches=mismatches, moved=len(moved),
                   kv_reestablished=forked)
        if failures:
            _finding(report, "stream_kv_kill",
                     f"{failures} non-shed client-visible failure(s) "
                     "across the kill (affinity re-route + KV "
                     "re-establish must absorb replica death)")
        if mismatches:
            _finding(report, "stream_kv_kill",
                     f"{mismatches} label(s) matched NEITHER the "
                     "continuous-KV expectation nor the deterministic "
                     "window-rebuild (KV state did not survive or "
                     "rebuild correctly)")
        if victim_sessions and not forked:
            _finding(report, "stream_kv_kill",
                     "no session took the re-establish fork: the kill "
                     "never exercised the KV rebuild-from-window path")
        if victim_sessions and not moved:
            _finding(report, "stream_kv_kill",
                     "no victim session re-routed off the killed replica")
        log(f"[chaos] stream_kv_kill: {n_advances * n_sessions} advances "
            f"over {n_sessions} KV sessions through the kill "
            f"({failures} failed, {sheds} shed, {mismatches} mismatches, "
            f"{forked} deterministic KV re-establishes, "
            f"{len(moved)}/{len(victim_sessions)} victims re-homed)")
    finally:
        if router is not None:
            router.close()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10.0)
            except Exception:
                pass


def leg_autoscale_kill(report: dict, seed: int, log: Log) -> None:
    """SIGKILL a pooled replica UNDER AUTOSCALER CONTROL
    (fleet/control/autoscaler.py): the controller must confirm the corpse
    (`dead_after_ticks` consecutive unroutable ticks + a dead health
    verdict), replace it with EXACTLY ONE spawn (a dead name is never
    double-counted against the target), re-home its sessions onto
    survivors with zero non-shed failures and position-correct labels —
    and still refuse to drain the last routable replica no matter what
    the signals say (the never-scale-to-zero floor is structural)."""
    import signal as _signal
    import subprocess

    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.control import Autoscaler
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        HttpReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.serving.stub import stub_stream_logits

    leg = _leg(report, "autoscale_kill")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    T, S, HW, NCLS = 8, 2, 4, 4
    n_sessions, n_advances, kill_after = 4, 6, 2
    procs: List[subprocess.Popen] = []
    router = None
    asc = None
    spawn_n = {"n": 0}
    rng = np.random.default_rng(seed)
    try:
        for _ in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 _STREAM_SRV_CODE.format(forward_s=0.002)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True))
        replicas = [HttpReplica(f"akill-{i}", _read_url_line(p),
                                pid=p.pid, timeout_s=20.0)
                    for i, p in enumerate(procs)]
        pool = ReplicaPool(replicas, health_interval_s=0.25)
        router = Router(pool, retries=3)

        def spawn():
            p = subprocess.Popen(
                [sys.executable, "-c",
                 _STREAM_SRV_CODE.format(forward_s=0.002)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True)
            procs.append(p)  # cleanup owns every child, spawned or seed
            spawn_n["n"] += 1
            r = HttpReplica(f"akill-sp-{spawn_n['n']}", _read_url_line(p),
                            pid=p.pid, timeout_s=20.0)
            r._proc = p
            return r

        def reap(replica):
            p = getattr(replica, "_proc", None)
            if p is not None:
                try:
                    p.kill()
                    p.wait(timeout=10.0)
                except Exception:
                    pass

        # replacement is the only control loop under test: the watermarks
        # park at +/-inf so no pressure/idle decision can fire, and the
        # controller is stepped MANUALLY (never start()ed) so the tick
        # count the corpse confirmation needs is deterministic
        asc = Autoscaler(router, spawn_fn=spawn, reap_fn=reap,
                         min_replicas=2, max_replicas=4,
                         slo_p99_ms=1e9, queue_high=1e9, queue_low=0.0,
                         cooldown_s=0.05, interval_s=0.05, ewma_alpha=1.0,
                         drain_grace_s=1.0, dead_after_ticks=2)

        windows = {f"as-{i}": rng.standard_normal(
            (T, HW, HW, 3)).astype(np.float32) for i in range(n_sessions)}
        failures, mismatches, sheds = 0, 0, 0

        def advance_all():
            nonlocal failures, mismatches, sheds
            futs = {}
            for sid in windows:
                frames = rng.standard_normal(
                    (S, HW, HW, 3)).astype(np.float32)
                windows[sid] = np.concatenate(
                    [windows[sid][S:], frames], axis=0)
                # the resendable window rides every advance — the
                # re-establish-anywhere contract replacement needs
                futs[sid] = router.submit(
                    {"video": frames},
                    session={"sid": sid, "window": windows[sid],
                             "stride": S})
            for sid, fut in futs.items():
                try:
                    out = np.asarray(fut.result(timeout=30))
                except Exception as e:  # noqa: BLE001 - verdict, not crash
                    from pytorchvideo_accelerate_tpu.serving.batcher import (
                        QueueFullError,
                    )

                    if isinstance(e, QueueFullError):
                        sheds += 1
                    else:
                        failures += 1
                    continue
                want = stub_stream_logits(windows[sid], NCLS)
                if abs(out[0] - want[0]) > 1e-4:
                    mismatches += 1

        for sid, win in windows.items():
            out = np.asarray(router.submit(
                {}, session={"sid": sid, "window": win,
                             "stride": S}).result(timeout=30))
            if abs(out[0] - stub_stream_logits(win, NCLS)[0]) > 1e-4:
                mismatches += 1
        holders = {sid: router._affinity.get(sid) for sid in windows}
        victim_name = replicas[0].name
        victim_sessions = [s for s, h in holders.items()
                           if h == victim_name]
        leg["victim_sessions"] = len(victim_sessions)
        for _ in range(kill_after):
            advance_all()
        os.kill(procs[0].pid, _signal.SIGKILL)
        log(f"[chaos] autoscale_kill: killed {victim_name} holding "
            f"{len(victim_sessions)} live session(s)")
        time.sleep(0.6)  # > one poller interval: the corpse leaves routable
        replaced = False
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if asc.step() == "replace":
                replaced = True
                break
            time.sleep(0.15)
        names = sorted(r.name for r in pool.replicas)
        leg.update(replaced=replaced, spawns=spawn_n["n"], members=names)
        if not replaced:
            _finding(report, "autoscale_kill",
                     "controller never replaced the killed replica "
                     f"(membership {names})")
        if victim_name in names:
            _finding(report, "autoscale_kill",
                     f"corpse {victim_name} still in membership after the "
                     "replace (a dead name the target keeps paying for)")
        if spawn_n["n"] != 1:
            _finding(report, "autoscale_kill",
                     f"{spawn_n['n']} spawn(s) for ONE dead replica (the "
                     "corpse was double-counted against the target)")
        for _ in range(kill_after, n_advances):
            advance_all()
        moved = [s for s in victim_sessions
                 if router._affinity.get(s) not in (None, victim_name)]
        leg.update(advances=n_advances * n_sessions, failed=failures,
                   shed=sheds, mismatches=mismatches, moved=len(moved))
        if failures:
            _finding(report, "autoscale_kill",
                     f"{failures} non-shed client-visible failure(s) "
                     "across kill + replace (re-route, re-establish and "
                     "the replacement must absorb replica death)")
        if mismatches:
            _finding(report, "autoscale_kill",
                     f"{mismatches} label(s) diverged from the client-"
                     "window expectation through the replacement")
        if victim_sessions and not moved:
            _finding(report, "autoscale_kill",
                     "no victim session re-routed off the killed replica")
        # last-healthy probe (white-box): drain down to ONE routable
        # replica, then demand the controller refuses to drain IT
        first = asc._drain_one(pool.routable())
        last = asc._drain_one(pool.routable())
        n_routable = len(pool.routable())
        leg.update(drain_first=bool(first), drain_last_refused=not last,
                   routable_after=n_routable)
        if not first:
            _finding(report, "autoscale_kill",
                     "drain of a redundant replica refused with 2 routable")
        if last or n_routable != 1:
            _finding(report, "autoscale_kill",
                     "controller drained (or lost) the LAST routable "
                     f"replica ({n_routable} left) — the fleet can scale "
                     "to zero")
        log(f"[chaos] autoscale_kill: replace={replaced} "
            f"(spawns {spawn_n['n']}), {n_advances * n_sessions} advances "
            f"({failures} failed, {sheds} shed, {mismatches} mismatches, "
            f"{len(moved)}/{len(victim_sessions)} victims re-homed), "
            f"last-healthy drain refused={not last}")
    finally:
        if asc is not None:
            asc.close()
        if router is not None:
            router.close()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10.0)
            except Exception:
                pass


def leg_guard_nan(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """NaN spike mid-epoch (seeded ``nan`` faults at `step.dispatch`): the
    in-graph skip absorbs the first poisoned step, the second crosses the
    ladder → auto-rollback to the last-known-good step with the loader
    fast-forwarded PAST the poisoned span; the run completes with finite
    loss and leaves a loadable, byte-stable replay bundle
    (reliability/guard.py; docs/RELIABILITY.md § divergence runbook)."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import (
        CheckpointConfig, DataConfig, GuardConfig, ModelConfig, OptimConfig,
        TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.reliability.guard import (
        dump_replay_bundle,
        load_replay_bundle,
    )
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    leg = _leg(report, "guard_nan")
    outdir = os.path.join(tmpdir, "guard_run")
    cfg = TrainConfig(
        model=ModelConfig(name="tiny3d", num_classes=4, dropout_rate=0.0),
        data=DataConfig(synthetic=True, synthetic_num_videos=16,
                        num_frames=4, crop_size=24, batch_size=2,
                        num_workers=1, limit_val_batches=1),
        optim=OptimConfig(num_epochs=2, lr=0.01),
        checkpoint=CheckpointConfig(output_dir=outdir),
        # LKG every 3 steps so one exists before the injected anomaly;
        # warmup high = the spike detector stays quiet and the NONFINITE
        # path alone drives the ladder (deterministic leg)
        guard=GuardConfig(enabled=True, lkg_every_steps=3, lkg_keep=2,
                          rollback_after=2, max_rollbacks=2,
                          warmup_steps=1000),
        seed=seed,
    )
    tr = Trainer(cfg)
    guard = tr.train_guard
    # poison the 6th and 7th dispatches: consecutive nonfinite steps —
    # skip at streak 1, rollback at streak 2 (guard.rollback_after)
    faults.arm(FaultPlan(seed, [FaultSpec("step.dispatch", kind="nan",
                                          at_hits=(5, 6), max_fires=2)]))
    try:
        res = tr.fit()
    finally:
        faults.disarm()
    fires = [e for e in faults.fault_history()
             if e["point"] == "step.dispatch"]
    leg.update(fires=len(fires), rollbacks=res.get("guard_rollbacks"),
               skips=guard.skips, steps=res.get("steps"),
               train_loss=res.get("train_loss"))
    if len(fires) != 2:
        _finding(report, "guard_nan",
                 f"expected 2 injected nan dispatches, got {len(fires)}")
    if res.get("guard_rollbacks") != 1:
        _finding(report, "guard_nan",
                 f"expected exactly 1 rollback, got "
                 f"{res.get('guard_rollbacks')}")
        return
    rb = guard.last_rollback
    leg["rollback"] = {k: rb[k] for k in ("lkg_step", "anomaly_step",
                                          "resume_position")}
    if rb["lkg_step"] >= rb["anomaly_step"]:
        _finding(report, "guard_nan",
                 f"rollback target {rb['lkg_step']} not before the "
                 f"anomaly step {rb['anomaly_step']}")
    if rb["lkg_step"] not in (3, 4, 5):
        _finding(report, "guard_nan",
                 f"LKG step {rb['lkg_step']} outside the healthy window "
                 "before the injected anomalies (steps 6-7)")
    # loader position intact: the resume position is the anomalous
    # batch's CONSUMED position (post-batch == step index in epoch 0),
    # i.e. the poisoned span is skipped, nothing else is
    if rb["resume_position"] != {"epoch": 0,
                                 "position": rb["anomaly_step"]}:
        _finding(report, "guard_nan",
                 f"loader not fast-forwarded past the poisoned span: "
                 f"{rb['resume_position']} (anomaly step "
                 f"{rb['anomaly_step']})")
    if res.get("preempted") or not np.isfinite(res.get("train_loss",
                                                       float("nan"))):
        _finding(report, "guard_nan",
                 f"run did not recover to a finite loss: {res}")
    # the replay bundle is the repro artifact: loadable, carries the
    # poison, and the writer is byte-deterministic (same input → same
    # bytes, the property that makes a bundle replayable evidence)
    meta, arrs = load_replay_bundle(rb["bundle"])
    if np.isfinite(arrs["video"]).all():
        _finding(report, "guard_nan",
                 "replay bundle batch does not carry the injected NaNs")
    if meta["verdict"]["kind"] != "nonfinite":
        _finding(report, "guard_nan",
                 f"bundle verdict {meta['verdict']} is not nonfinite")
    a = dump_replay_bundle(os.path.join(tmpdir, "redump_a"), arrs,
                           {"step": meta["step"]})
    b = dump_replay_bundle(os.path.join(tmpdir, "redump_b"), arrs,
                           {"step": meta["step"]})
    for fname in sorted(os.listdir(a)):
        with open(os.path.join(a, fname), "rb") as fa, \
                open(os.path.join(b, fname), "rb") as fb:
            if fa.read() != fb.read():
                _finding(report, "guard_nan",
                         f"replay bundle not byte-deterministic: {fname}")
    log(f"[chaos] guard_nan: {len(fires)} poisoned steps -> "
        f"{guard.skips} skip(s) + rollback to step {rb['lkg_step']}, "
        f"resumed past position {rb['resume_position']['position']}, "
        f"finished at step {res.get('steps')} with finite loss")


def leg_quarantine(report: dict, tmpdir: str, seed: int, log: Log) -> None:
    """A deterministically-corrupt clip (seeded garbage bytes — the
    decode.read failure that kills the same index every epoch): the
    failure budget fills across runs sharing the persisted sidecar, the
    clip is quarantined, every epoch still delivers full batches, and the
    next run's sampler excludes the clip without a single decode
    attempt."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.data.manifest import (
        Quarantine,
        scan_directory,
    )
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader,
        VideoClipSource,
    )
    from pytorchvideo_accelerate_tpu.data.transforms import make_transform
    from pytorchvideo_accelerate_tpu.obs import get_registry

    leg = _leg(report, "quarantine")
    root = os.path.join(tmpdir, "qvideos")
    if not _write_video_tree(root, n_per_class=3):
        leg["skipped"] = "no mp4 codec on this host"
        log("[chaos] quarantine: skipped (no codec)")
        return
    bad_path = os.path.join(root, "class0", "v0.mp4")
    rng = np.random.default_rng(seed)
    with open(bad_path, "wb") as f:  # corrupt-bytes, seeded
        f.write(rng.integers(0, 256, 4096, dtype=np.uint8).tobytes())
    sidecar = os.path.join(tmpdir, "quarantine.json")
    tf = make_transform(training=True, num_frames=4, crop_size=24,
                        min_short_side_scale=26, max_short_side_scale=30)
    counter = get_registry().counter(
        "pva_data_quarantined_total", "", labelnames=("site",))
    before = counter.value(site="decode")

    def run(epoch: int):
        """One fresh 'run' over the tree: new source + loader, shared
        persisted sidecar (budget counts at most one failure per run)."""
        q = Quarantine(sidecar, budget=2)
        src = VideoClipSource(scan_directory(root), tf, clip_duration=0.2,
                              training=True, seed=seed, decode_retries=1,
                              retry_base_delay_s=0.001, quarantine=q)
        loader = ClipLoader(src, global_batch_size=2, shuffle=True,
                            num_workers=1, seed=seed)
        try:
            batches = sum(1 for _ in loader.epoch(epoch))
        finally:
            loader.close()
        return q, src, loader, batches

    q1, src1, loader1, b1 = run(0)   # failure 1 of 2: under budget
    q2, _src2, _l2, b2 = run(1)      # failure 2: quarantined + persisted
    q3, src3, loader3, b3 = run(2)   # excluded: zero decode attempts
    want = loader1.batches_per_epoch()
    leg.update(batches=[b1, b2, b3], want=want,
               quarantined=sorted(q3.paths()),
               counter_delta=counter.value(site="decode") - before)
    if not (b1 == b2 == b3 == want):
        _finding(report, "quarantine",
                 f"epochs under a corrupt clip yielded {[b1, b2, b3]} "
                 f"batches, want {want} each")
    if q1.contains(bad_path):
        _finding(report, "quarantine",
                 "clip quarantined on its FIRST failure (budget=2 should "
                 "absorb one transient)")
    if not q2.contains(bad_path):
        _finding(report, "quarantine",
                 "second failing run did not quarantine the clip")
    if not q3.contains(bad_path):
        _finding(report, "quarantine",
                 "sidecar did not persist across runs (round-trip lost)")
    bad_idx = next(i for i, e in enumerate(src3.manifest.entries)
                   if e.path == bad_path)
    plan = loader3._epoch_indices(3)
    if bad_idx in plan:
        _finding(report, "quarantine",
                 "sampler still schedules the quarantined clip")
    if len(plan) != len(src3.manifest):
        _finding(report, "quarantine",
                 "exclusion changed epoch geometry (batch count drift)")
    if counter.value(site="decode") - before != 1:
        _finding(report, "quarantine",
                 f"pva_data_quarantined_total moved by "
                 f"{counter.value(site='decode') - before}, want 1")
    log(f"[chaos] quarantine: corrupt clip sidelined after 2 failing "
        f"runs, {b1}/{b2}/{b3} of {want} batches delivered, sampler "
        f"excludes index {bad_idx}")


def leg_dataplane_kill(report: dict, tmpdir: str, seed: int,
                       log: Log) -> None:
    """Leg 13: decode-worker SIGKILL mid-epoch (docs/INPUT_PIPELINE.md §
    disaggregated data plane). Two worker PROCESSES feed a RemoteClipFeed;
    one is SIGKILLed with leases outstanding — the feed must re-lease the
    unacked span to the survivor and the full batch stream must be
    byte-identical to the local loader's (zero duplicate, zero missing →
    identical training loss by construction). With a codec present, the
    tree carries a deterministically-corrupt clip: the remote worker's
    quarantine report must land in the trainer's persisted sidecar and
    SURVIVE the reporting worker's death."""
    import signal as signal_mod

    import numpy as np

    from pytorchvideo_accelerate_tpu.data.manifest import (
        Quarantine,
        scan_directory,
    )
    from pytorchvideo_accelerate_tpu.data.pipeline import ClipLoader
    from pytorchvideo_accelerate_tpu.dataplane import spec as dpspec
    from pytorchvideo_accelerate_tpu.dataplane.bench import batch_digest
    from pytorchvideo_accelerate_tpu.dataplane.feed import RemoteClipFeed

    leg = _leg(report, "dataplane_kill")
    tspec = dict(num_frames=4, training=True, crop_size=24,
                 min_short_side_scale=26, max_short_side_scale=30)
    root = os.path.join(tmpdir, "dpvideos")
    bad_path = None
    if _write_video_tree(root, n_per_class=6):
        bad_path = os.path.join(root, "class0", "v0.mp4")
        rng = np.random.default_rng(seed)
        with open(bad_path, "wb") as f:  # seeded corrupt bytes
            f.write(rng.integers(0, 256, 4096, dtype=np.uint8).tobytes())
        spec = dpspec.video_spec(
            scan_directory(root), tspec, clip_duration=0.2, training=True,
            seed=seed, decode_retries=1, retry_base_delay_s=0.001)
    else:
        leg["codec"] = "unavailable (synthetic source, quarantine half skipped)"
        spec = dpspec.synthetic_spec(tspec, num_videos=12, num_classes=4,
                                     seed=seed)

    def make_loader() -> ClipLoader:
        # shuffle=False pins the corrupt clip (sorted index 0) into the
        # FIRST batch, so the quarantine report deterministically precedes
        # the kill; byte parity is shuffle-independent anyway
        return ClipLoader(dpspec.build_source(spec), global_batch_size=4,
                          shuffle=False, num_workers=1, seed=seed)

    loader = make_loader()
    try:
        # batch_digest is THE byte-identity definition (shared with
        # dataplane/bench.py — the two gates must agree on it)
        local = [batch_digest(b) for b, _ in
                 loader.epoch_items(0, from_start=True) if b is not None]
    finally:
        loader.close()

    sidecar = os.path.join(tmpdir, "dp_quarantine.json")
    quarantine = Quarantine(sidecar, budget=1, site="dataplane")
    loader = make_loader()
    feed = RemoteClipFeed(loader, spec, spawn=2, credits=2,
                          quarantine=quarantine, batch_timeout_s=120.0)
    remote: List[str] = []
    victim = None
    try:
        for i, (batch, _state) in enumerate(
                feed.epoch_items(0, from_start=True)):
            if batch is None:
                continue
            remote.append(batch_digest(batch))
            if victim is None and i == 0:
                # wait for the quarantine verdict (codec runs), then kill
                # the REPORTING worker — its completed verdicts must
                # outlive it; prefer a moment when it holds leases so the
                # re-lease path is exercised, not just membership
                deadline = time.monotonic() + 20.0
                while (bad_path is not None
                       and not quarantine.contains(bad_path)
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                stats = feed.stats()
                reporters = {q["pid"] for q in stats["qreports"]}
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    stats = feed.stats()
                    busy = [w for w in stats["workers"].values()
                            if w["outstanding"] > 0
                            and (not reporters or w["pid"] in reporters)]
                    if busy:
                        break
                    time.sleep(0.005)
                cand = [w["pid"] for w in stats["workers"].values()
                        if w["outstanding"] > 0] or \
                       [w["pid"] for w in stats["workers"].values()]
                victim = (next((p for p in cand if p in reporters), None)
                          or cand[0])
                os.kill(victim, signal_mod.SIGKILL)
        stats = feed.stats()
    finally:
        feed.close()
        loader.close()
    leg.update(batches=len(remote), want=len(local), victim_pid=victim,
               releases=stats.get("releases"),
               workers_lost=stats.get("workers_lost"),
               qreports=len(stats.get("qreports", [])))
    if remote != local:
        _finding(report, "dataplane_kill",
                 f"remote stream diverged after the kill: {len(remote)} "
                 f"batches vs {len(local)} local (dup/missing/reordered)")
    if stats.get("workers_lost") != 1:
        _finding(report, "dataplane_kill",
                 f"feed lost {stats.get('workers_lost')} workers, want "
                 "exactly the SIGKILLed one")
    if bad_path is not None:
        if not quarantine.contains(bad_path):
            _finding(report, "dataplane_kill",
                     "remote decode failure never reached the trainer's "
                     "quarantine sidecar")
        elif not Quarantine(sidecar, budget=1).contains(bad_path):
            _finding(report, "dataplane_kill",
                     "quarantine verdict did not persist to the sidecar "
                     "(lost with the dead worker)")
    log(f"[chaos] dataplane_kill: worker {victim} SIGKILLed mid-epoch; "
        f"{stats.get('releases')} span(s) re-leased, {len(remote)}/"
        f"{len(local)} batches byte-identical, quarantine "
        f"{'persisted' if bad_path else 'n/a (no codec)'}")


# forced-host child for leg_collective_hang: a REAL mesh psum wedged by an
# injected delay inside the watched section; the watchdog (tiny timeout)
# must fire DURING the wedge with per-host attribution. One JSON line to
# stdout (forcehost contract).
_HANG_LEG_CODE = """
import json, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from jax.sharding import PartitionSpec as P
from pytorchvideo_accelerate_tpu.config import MeshConfig
from pytorchvideo_accelerate_tpu.obs.watchdog import Watchdog
from pytorchvideo_accelerate_tpu.parallel import collectives, hangcheck
from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
from pytorchvideo_accelerate_tpu.reliability import faults

evidence = {{}}
wd = Watchdog({timeout}, on_stall=lambda names: evidence.update(
    stalled=list(names),
    attribution={{n: list(v)
                  for n, v in dict(wd.last_attribution or {{}}).items()}}))
wd.start()
hangcheck.install_collective_watch(wd)
mesh = make_train_mesh(MeshConfig(data={devices}, model=1))
f = jax.jit(jax.shard_map(
    lambda x: collectives.psum(x, "data"), mesh=mesh,
    in_specs=P("data"), out_specs=P(), check_vma=False))
x = np.ones(({devices},), np.float32)
warm = float(np.asarray(f(x)).ravel()[0])  # compile outside the wedge
faults.arm(faults.FaultPlan({seed}, [faults.FaultSpec(
    "collective.sync", kind="delay", delay_s={wedge},
    at_hits=(0,), max_fires=1)]))
t0 = time.monotonic()
try:
    with hangcheck.collective_section("psum", step=1):
        out = float(np.asarray(f(x)).ravel()[0])
finally:
    faults.disarm()
elapsed = time.monotonic() - t0
wd.stop()
# second diagnostic on the SAME op: the schedule recorder
# (parallel/schedule_recorder.py) must name the psum one emulated host
# skips — the watchdog says WHERE the pod wedged, the recorder says WHY
from pytorchvideo_accelerate_tpu.parallel import schedule_recorder as sr
rec = sr.CollectiveScheduleRecorder()
sr.install_schedule_recorder(rec)
try:
    for h in range(2):
        with rec.as_host(f"host={{h}}/2"):
            with hangcheck.collective_section("step_dispatch", step=1):
                pass
            if h == 0:  # host 1 SKIPS the psum — the deadlock shape
                with hangcheck.collective_section("psum", step=1):
                    float(np.asarray(f(x)).ravel()[0])
            with hangcheck.collective_section("epoch_sync"):
                pass
    div = sr.diff_schedules(rec.schedules())
finally:
    sr.uninstall_schedule_recorder()
first = div.get("first_divergence") or {{}}
print("\\n" + json.dumps({{
    "stalled": evidence.get("stalled"),
    "attribution": evidence.get("attribution"),
    "elapsed_s": round(elapsed, 3),
    "fires": len(faults.fault_history()), "psum": out, "warm": warm,
    "sched_diverged": div.get("diverged"),
    "sched_tick": first.get("tick"),
    "sched_ops": {{h: (e[1] if e else None)
                   for h, e in (first.get("hosts") or {{}}).items()}}}}))
"""

_HANG_LEG_DEVICES = 4
_HANG_LEG_TIMEOUT_S = 0.3
_HANG_LEG_WEDGE_S = 1.5


def leg_collective_hang(report: dict, seed: int, log: Log) -> None:
    """Wedged mesh collective in a forced-host child: an injected delay
    inside the watched `psum` section must trip the watchdog DURING the
    wedge (section exit clears the component, so evidence means it fired
    while stuck) with attribution naming the op and the host — the
    evidence an external kill would otherwise destroy."""
    from pytorchvideo_accelerate_tpu.utils.forcehost import run_forced_host

    leg = _leg(report, "collective_hang")
    out = run_forced_host(
        _HANG_LEG_CODE.format(timeout=_HANG_LEG_TIMEOUT_S,
                              devices=_HANG_LEG_DEVICES,
                              wedge=_HANG_LEG_WEDGE_S, seed=seed),
        _HANG_LEG_DEVICES, timeout=300.0)
    leg.update(out)
    if out.get("fires") != 1:
        _finding(report, "collective_hang",
                 f"expected 1 injected collective wedge, got "
                 f"{out.get('fires')}")
    if out.get("stalled") != ["collective"]:
        _finding(report, "collective_hang",
                 f"watchdog did not fire on the wedged collective: "
                 f"stalled={out.get('stalled')}")
        return
    detail = (out.get("attribution") or {}).get("collective", ["", 0])[0]
    if "psum" not in detail or "host=" not in detail:
        _finding(report, "collective_hang",
                 f"stall not attributed to the collective per host: "
                 f"{detail!r}")
    if out.get("elapsed_s", 0) < _HANG_LEG_WEDGE_S:
        _finding(report, "collective_hang",
                 f"wedge did not actually hold the section "
                 f"({out.get('elapsed_s')}s < {_HANG_LEG_WEDGE_S}s)")
    if out.get("psum") != float(_HANG_LEG_DEVICES):
        _finding(report, "collective_hang",
                 f"psum returned {out.get('psum')} after the wedge")
    # the recorder's first-divergence must name the SAME op the watchdog
    # attributed, on the host that skipped it — the two diagnostics are
    # pinned to each other so they can't drift apart
    ops = out.get("sched_ops") or {}
    if not (out.get("sched_diverged") is True
            and ops.get("host=0/2") == "psum"
            and ops.get("host=1/2") != "psum"
            and "host=1/2" in ops):
        _finding(report, "collective_hang",
                 f"schedule recorder did not name the skipped psum per "
                 f"host: diverged={out.get('sched_diverged')} ops={ops}")
    log(f"[chaos] collective_hang: watchdog attributed the wedge to "
        f"{detail!r} before the {_HANG_LEG_WEDGE_S}s delay released; "
        f"recorder first-divergence at tick {out.get('sched_tick')} "
        f"names {ops}")


def leg_sigterm_plumbing(report: dict, log: Log) -> None:
    """The raw signal path: a real SIGTERM to the installed guard sets the
    request (and does NOT kill), outside any trainer."""
    from pytorchvideo_accelerate_tpu.reliability.preemption import (
        PreemptionGuard,
    )
    import signal as _signal

    leg = _leg(report, "sigterm")
    g = PreemptionGuard()
    if not g.install():  # not the main thread (embedded runs): skip
        leg["skipped"] = "not the main thread"
        return
    try:
        os.kill(os.getpid(), _signal.SIGTERM)
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and not g.requested:
            time.sleep(0.005)
        leg.update(requested=g.requested, reason=g.reason)
        if not g.requested:
            _finding(report, "sigterm",
                     "SIGTERM did not set the preemption request")
    finally:
        g.uninstall()
    log(f"[chaos] sigterm: guard caught the signal "
        f"(reason={leg.get('reason')!r})")


# --- scenario ---------------------------------------------------------------

def run_scenario(seed: int = 42, smoke: bool = True,
                 log: Optional[Log] = None) -> dict:
    """Run every leg; returns the report dict. `smoke` is accepted for
    CLI-symmetry with pva-tpu-tsan — the scenario is already sized for CI
    (tiny shapes, two short tiny3d fits); full mode is identical today."""
    from pytorchvideo_accelerate_tpu.obs import trace as obstrace

    log = log or (lambda msg: None)
    t0 = time.perf_counter()
    report: dict = {"seed": int(seed), "smoke": bool(smoke),
                    "findings": [], "legs": {}}
    # distributed tracing ARMED across every leg: the recovery machinery
    # (retries, sheds, drains, rollbacks) must behave identically with the
    # tracer live — the "chaos gate stays clean with tracing armed"
    # obligation. Seeded, so the sampling decisions replay with the run.
    obstrace.configure_tracing(1.0, seed=seed, capacity=2048)
    try:
        with tempfile.TemporaryDirectory(prefix="pva_chaos_") as tmpdir:
            for fn, args in (
                    (leg_replay, (report, seed, log)),
                    (leg_sigterm_plumbing, (report, log)),
                    (leg_decode, (report, tmpdir, seed, log)),
                    (leg_quarantine, (report, tmpdir, seed, log)),
                    (leg_dataplane_kill, (report, tmpdir, seed, log)),
                    (leg_ckpt, (report, tmpdir, seed, log)),
                    (leg_tracker, (report, tmpdir, seed, log)),
                    (leg_serve, (report, seed, log)),
                    (leg_replica_kill, (report, seed, log)),
                    (leg_stream_replica_kill, (report, seed, log)),
                    (leg_stream_kv_kill, (report, seed, log)),
                    (leg_autoscale_kill, (report, seed, log)),
                    (leg_collective_hang, (report, seed, log)),
                    (leg_guard_nan, (report, tmpdir, seed, log)),
                    (leg_preempt, (report, tmpdir, seed, log)),
                    (leg_preempt_mesh, (report, tmpdir, seed, log)),
                    (leg_preempt_pipeline, (report, tmpdir, seed, log)),
            ):
                try:
                    fn(*args)
                except Exception as e:  # noqa: BLE001 - a crashed leg IS a finding
                    faults.disarm()  # never leak an armed plan into later legs
                    _finding(report, fn.__name__,
                             f"leg crashed: {type(e).__name__}: {e}")
    finally:
        obstrace.disable_tracing()
    report["elapsed_s"] = round(time.perf_counter() - t0, 3)
    log(f"[chaos] scenario done in {report['elapsed_s']}s: "
        f"{len(report['findings'])} finding(s)")
    return report


def finding_count(report: dict) -> int:
    return len(report.get("findings", ()))


def publish(report: dict) -> None:
    """Mirror the verdict into the obs spine (gauge + flight ring), the
    lint/tsan pattern: crash dumps carry the chaos verdict too."""
    from pytorchvideo_accelerate_tpu import obs

    obs.get_registry().gauge(
        "pva_chaos_findings",
        "findings from the last pva-tpu-chaos scenario").set(
            finding_count(report))
    for f in report.get("findings", ()):
        obs.get_recorder().record("chaos", "finding", detail=f[:200])


def format_report(report: dict) -> str:
    lines: List[str] = []
    for name, leg in report.get("legs", {}).items():
        lines.append(f"leg {name}: " + json.dumps(leg, default=str))
    for f in report.get("findings", ()):
        lines.append(f"FINDING {f}")
    lines.append(
        f"pva-tpu-chaos: {finding_count(report)} finding(s) over "
        f"{len(report.get('legs', {}))} legs in "
        f"{report.get('elapsed_s', 0)}s (seed {report.get('seed')})")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pva-tpu-chaos",
        description="deterministic fault-injection scenario over the "
                    "data/train/serve resilience layer; see "
                    "docs/RELIABILITY.md")
    ap.add_argument("--smoke", action="store_true",
                    help="the CI lane (the scenario is CI-sized either way)")
    ap.add_argument("--seed", type=int, default=42,
                    help="fault-plan seed: same seed, same fault sequence")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    # the trainer legs must not wedge a CLI run on a half-attached
    # accelerator: CPU unless the caller overrides (the tsan CLI pattern)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    report = run_scenario(seed=args.seed, smoke=args.smoke, log=log)
    publish(report)
    if args.format == "json":
        print(json.dumps(report, indent=1, default=str))
    else:
        print(format_report(report))
    return 1 if finding_count(report) else 0


if __name__ == "__main__":
    sys.exit(main())

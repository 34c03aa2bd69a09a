#!/usr/bin/env python
"""Benchmark harness: clips/sec/chip on the reference training workloads.

Prints exactly ONE compact JSON line (<=1.5 KB — the driver captures only a
~2 KB stdout tail) to stdout:
    {"metric": "...", "value": N, "unit": "clips/sec/chip", "vs_baseline": N,
     "mfu": ..., "tflops_per_sec": ..., "step_ms_blocked": ..., "suspect": B,
     "models": {name: clips_per_sec}}
Full per-model dicts and the host data-pipeline blocks go to
bench_partial.json (flushed throughout the run); logs go to stderr. Runs on
the attached TPU by default and fails when it finds none; pass --smoke for
a CPU-sized sanity run.

Headline workload matches the reference launch recipe
(run_slowfast_r50.sh:3-12, SURVEY §6): SlowFast-R50, 32 frames, 256^2 crops,
batch 8 per chip, bf16 compute (standing in for the recipe's fp16 AMP),
measuring the compiled train step (fwd+bwd+update, BN stats, metrics) end to
end. The BASELINE configs 2/4/5 (x3d_s, mvit_b, videomae_b_pretrain) are
benched too and reported under "models".

Process arrangement (the chip belongs to one process at a time):
- the PARENT process never touches devices (it forces the CPU platform);
  every device-facing bench runs in a DISPOSABLE CHILD subprocess with its
  own kill timeout, one after another, so a stuck compile loses one model,
  not the round, and no two processes ever want the chip at once;
- a non-smoke child that does not get a TPU exits non-zero, and the run
  then exits non-zero WITHOUT a headline: there is no CPU fallback;
- partial results are flushed to bench_partial.json after every model.

Self-audit (so impossible numbers can't pass unremarked):
- per-step FLOPs come from XLA's own `compiled.cost_analysis()`;
- achieved TFLOP/s and MFU are derived from the *blocked* per-step latency
  (each step synced before the next dispatch — no async-dispatch inflation);
- the pipelined throughput loop rotates distinct batches so a
  constant-folding/caching runtime can't replay one result;
- if pipelined step time is <50%% of blocked step time, OR the implied MFU
  exceeds 100%% of the chip's bf16 peak, the run is flagged
  ("suspect": true) — the platform isn't executing with real device timing.
"""

import argparse
import datetime
import json
import math
import os
import signal
import statistics
import subprocess
import sys
import time


def log(*args):
    print(*args, file=sys.stderr, flush=True)


HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from pytorchvideo_accelerate_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)
from pytorchvideo_accelerate_tpu.utils.hw import (  # noqa: E402
    device_summary,
    peak_tflops,
    resolve_peak,
)


# Benchmark workloads: BASELINE.md configs. (model, frames, crop, per-chip
# batch, pretraining?). x3d_s samples 13f@160 (BASELINE config 2), mvit_b and
# videomae_b use 16f@224 (configs 4/5).
WORKLOADS = {
    "slowfast_r50": dict(num_frames=32, crop=256, batch_size=8, pretrain=False),
    "x3d_s": dict(num_frames=13, crop=160, batch_size=8, pretrain=False),
    "mvit_b": dict(num_frames=16, crop=224, batch_size=8, pretrain=False),
    "videomae_b_pretrain": dict(num_frames=16, crop=224, batch_size=8,
                                pretrain=True),
    # r5 zoo additions — opt-in (--models), not in the default set: the
    # default bench covers the four BASELINE configs and every extra child
    # spends chip minutes
    "r2plus1d_r50": dict(num_frames=16, crop=224, batch_size=8,
                         pretrain=False),
    "csn_r101": dict(num_frames=32, crop=224, batch_size=8, pretrain=False),
}

# the driver's plain `python bench.py` measures these (BASELINE configs);
# `--models all` or explicit names reach the rest of WORKLOADS
DEFAULT_MODELS = ("slowfast_r50", "x3d_s", "mvit_b", "videomae_b_pretrain")


def _scratch_outdir(tag: str) -> str:
    """Scratch output_dir for a bench lane's Trainer runs: flight
    records, trace rings, and checkpoints land here — NEVER the repo
    root (TrainConfig's default output_dir "."), whose generated
    flight_record.json used to re-churn ~1000 lines into the worktree
    every round. Left to the OS tempdir reaper: a post-crash record must
    survive long enough for pva-tpu-doctor --obs-dir to read it."""
    import tempfile

    return tempfile.mkdtemp(prefix=f"pva_bench_{tag}_")


def _utcnow() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime("%FT%TZ")


def _setup_jax(smoke: bool, child: str | None = None):
    """Backend + persistent compile cache config (child processes and the
    device-free parent both go through here)."""
    import jax

    if smoke:
        jax.config.update("jax_platforms", "cpu")
    if child == "__stream__":
        # The persistent cache intermittently corrupted the native heap in
        # THIS child only ("free(): invalid pointer" / SIGSEGV inside the
        # trunk sub-lane's warmup_stream compiles — the lane's only
        # compiles slow enough to be serialized; ~half of runs with the
        # cache on, 0/10 with it off; seen on the jaxlib before 0.9.0,
        # not re-verified on it). Until that interaction is understood, the
        # stream child runs on in-process jit caches alone, whatever the
        # environment says; its post-warmup recompile count already
        # proves flatness.
        jax.config.update("jax_enable_compilation_cache", False)
        return jax
    enable_compile_cache()
    return jax


def bench_model(name: str, wl: dict, args, n_chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorchvideo_accelerate_tpu.utils.bench_setup import (
        build_step_setup, fetch_loss, xla_flops,
    )

    frames, crop, bsz = wl["num_frames"], wl["crop"], wl["batch_size"]
    if args.smoke:
        frames, crop, bsz = max(frames // 4, 4), 64, 2
        if name == "videomae_b_pretrain":
            crop = 64  # tubelet 16 divides
    num_classes = 700  # Kinetics-700 (BASELINE.json metric)
    setup = build_step_setup(
        name, frames=frames, crop=crop, batch_per_chip=bsz,
        num_classes=num_classes, alpha=args.alpha, pretrain=wl["pretrain"],
        total_steps=args.steps + args.warmup,
        # raw-u8 batches (default, supervised): 4x less host->device
        # transfer during setup, with the normalize affine fused into the
        # step (the host_cast=u8 production path). --inputs f32 stages
        # float32 clips instead; the effective mode is recorded per model.
        input_u8=args.inputs == "u8",
    )
    B, state = setup.global_batch, setup.state

    log(f"[{name}] global batch {B} ({bsz}/chip), {frames} frames @ {crop}^2")

    # two distinct device batches, rotated through the timing loop
    gbs = [setup.device_batch(0), setup.device_batch(1)]

    # --- compile + XLA's own FLOPs estimate -------------------------------
    t0 = time.perf_counter()
    compiled = setup.step.lower(state, gbs[0], jax.random.key(0)).compile()
    compile_s = time.perf_counter() - t0
    flops_per_step = xla_flops(compiled)
    log(f"[{name}] compile: {compile_s:.1f}s, "
        f"flops/step: {flops_per_step and f'{flops_per_step / 1e12:.2f}T'}")

    # Sync discipline: every timed window ends in a value fetch (see
    # utils/bench_setup.fetch_loss)
    _fetch = fetch_loss

    for i in range(max(args.warmup, 1)):  # >=1: later loops read `metrics`
        state, metrics = compiled(state, gbs[i % 2], jax.random.key(i))
    _fetch(metrics)

    # value-fetch round-trip floor: tiny fresh result each probe, so the
    # timing is dispatch + transfer with negligible compute
    one = jnp.ones((1,), jnp.float32)
    rtts = []
    for i in range(5):
        y = one * float(i + 1)
        t0 = time.perf_counter()
        np.asarray(y)
        rtts.append(time.perf_counter() - t0)
    rtt_ms = statistics.median(rtts) * 1e3

    # --- blocked per-step latency (upper bound; includes one RTT) ---------
    blocked = []
    for i in range(args.steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, gbs[i % 2], jax.random.key(50 + i))
        _fetch(metrics)
        blocked.append(time.perf_counter() - t0)
    blocked_ms = statistics.median(blocked) * 1e3

    # --- pipelined throughput (async dispatch, one value-sync at the end;
    # the queue is empty here because the blocked loop fetched every step) -
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, metrics = compiled(state, gbs[i % 2], jax.random.key(100 + i))
    _fetch(metrics)
    dt = time.perf_counter() - t0
    pipelined_ms = dt / args.steps * 1e3

    clips_per_sec = B * args.steps / dt
    per_chip = clips_per_sec / n_chips
    # RTT-corrected latency is the fair comparison for the pipelining ratio
    suspect = pipelined_ms < 0.5 * max(blocked_ms - rtt_ms, 1e-6)

    dev = jax.devices()[0]
    # datasheet peak where one exists; a measured matmul-rate calibration
    # on platforms without one (CPU smoke) — labeled, so the MFU stops
    # being null without ever impersonating a silicon fraction
    peak, peak_source = resolve_peak(dev)
    tflops = mfu = None
    if flops_per_step:
        # throughput MFU from the pipelined rate — the deployment-relevant
        # number (the async train loop runs pipelined), and the one with
        # the RTT amortized across the whole window
        tflops = flops_per_step / (pipelined_ms / 1e3) / 1e12 / n_chips
        if peak:
            mfu = tflops / peak
            if mfu > 1.0 and peak_source == "datasheet":
                # >100% of bf16 peak is physically impossible: the
                # platform isn't timing real execution (a sync that
                # returned before the device finished). A measured peak is
                # a proxy ceiling, not physics — exempt from the verdict.
                suspect = True
    log(f"[{name}] {args.steps} steps: blocked {blocked_ms:.1f} ms/step "
        f"(rtt {rtt_ms:.1f}), "
        f"pipelined {pipelined_ms:.1f} ms/step -> {per_chip:.2f} clips/s/chip"
        f"{f', {tflops:.1f} TFLOP/s/chip' if tflops else ''}"
        f"{f', MFU {mfu:.1%}' if mfu else ''}"
        f"{' SUSPECT (device timing not trustworthy)' if suspect else ''}, "
        f"final loss {float(metrics['loss']):.3f}")

    out = {
        "clips_per_sec_per_chip": round(per_chip, 3),
        "step_ms_blocked": round(blocked_ms, 3),
        "step_ms_pipelined": round(pipelined_ms, 3),
        "fetch_rtt_ms": round(rtt_ms, 3),
        "sync": "value-fetch",
        "inputs": "u8" if setup.input_u8 else "f32",
        "compile_s": round(compile_s, 1),
        "batch_per_chip": bsz,
        "frames": frames,
        "crop": crop,
        "suspect": suspect,
        "smoke": bool(args.smoke),
        "platform": dev.platform,
    }
    if flops_per_step:
        out["flops_per_step"] = flops_per_step
        out["tflops_per_sec_per_chip"] = round(tflops, 2)
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
        out["mfu_peak_source"] = peak_source
    return out


# smoke-mode geometry for the trainer lane (frames, crop, per-chip batch);
# module-level so the tier-1 contract test can shrink it further — it checks
# perf-dict plumbing, not CPU conv throughput
SMOKE_TRAINER_SHAPE = (8, 64, 2)


def hbm_headline() -> dict:
    """The memory-ledger triple an obs-armed lane carries (pva-tpu-hbm,
    obs/memory.py): device high-water mark, the fraction of live bytes
    the ledger can attribute to a component, and the provenance label.
    Hosts whose backend exposes no `memory_stats()` (the CPU smoke box)
    report `hbm_source="estimate"` with the peak ATTRIBUTED sum — the
    bench never fakes device bytes. Empty when no ledger is armed."""
    from pytorchvideo_accelerate_tpu.obs import memory as obs_memory

    led = obs_memory.get_ledger()
    if led is None:
        return {}
    return {"hbm_peak_bytes": int(led.peak_bytes()),
            "hbm_attributed_frac": round(led.attributed_frac(), 4),
            "hbm_source": led.source()}


def bench_trainer(args) -> dict:
    """Trainer.fit() on synthetic data — its steady-state clips/s/chip is
    compared (in the parent) against the raw-step number to prove the hot
    loop doesn't sync away the pipelining (VERDICT r2 weak #4)."""
    import jax

    from pytorchvideo_accelerate_tpu.config import (
        CheckpointConfig, DataConfig, GuardConfig, ModelConfig,
        OptimConfig, TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    frames, crop, bsz = SMOKE_TRAINER_SHAPE if args.smoke else (32, 256, 8)
    n_videos = bsz * len(jax.devices()) * (4 if args.smoke else 16)
    cfg = TrainConfig(
        model=ModelConfig(name="slowfast_r50", num_classes=700),
        data=DataConfig(synthetic=True, synthetic_num_videos=n_videos,
                        num_frames=frames, crop_size=crop, batch_size=bsz,
                        num_workers=2, limit_val_batches=1),
        optim=OptimConfig(num_epochs=2),  # epoch 1 excludes compile
        # flight records / trace rings land under the lane's scratch dir,
        # never the repo root (the default output_dir ".") — a bench
        # round must not churn a generated artifact into the worktree
        checkpoint=CheckpointConfig(output_dir=_scratch_outdir("trainer")),
        # guard ARMED: the lane doubles as the proof that the self-healing
        # machinery (in-graph skip branch + per-step observation) keeps
        # train_recompiles == 0 and reports zero verdicts on a clean run
        guard=GuardConfig(enabled=True),
        mixed_precision="bf16",
    )
    tr = Trainer(cfg)
    res = tr.fit()
    # perf-dict contract: the span-sourced obs keys (obs/ telemetry spine,
    # default-on), the legacy prefetch keys, and the guard verdicts must
    # be present — the smoke run doubles as the CI check that none of the
    # instrumentation silently fell out of fit()
    for key in ("input_wait_frac", "steps_per_sec", "obs_step_s",
                "obs_input_wait_frac", "obs_h2d_s", "train_recompiles",
                "guard_rollbacks", "quarantined_clips"):
        assert key in res, f"fit() perf dict missing {key!r}: {sorted(res)}"
    # steady-state: train-section wall time of the post-compile epoch only
    # (excludes compile, eval, checkpointing — the quantity the raw-step
    # number measures)
    steps_per_epoch = res["steps"] // cfg.optim.num_epochs
    dt = res["epoch_train_times"][-1]
    clips = steps_per_epoch * bsz * len(jax.devices())
    cps_chip = clips / dt / len(jax.devices())
    log(f"[trainer] fit() steady-state epoch: {steps_per_epoch} steps in "
        f"{dt:.2f}s = {cps_chip:.2f} clips/s/chip (incl. data pipeline), "
        f"input_wait_frac {res['input_wait_frac']:.3f}")
    return {"trainer_cps_chip": cps_chip,
            "input_wait_frac": res["input_wait_frac"],
            "obs_step_s": res["obs_step_s"],
            "obs_input_wait_frac": res["obs_input_wait_frac"],
            "obs_h2d_s": res["obs_h2d_s"],
            # steady-state jit-cache growth after warmup (the
            # pva_train_recompiles gauge; analysis/recompile_guard) —
            # anything but 0 means mid-training XLA compile stalls
            "train_recompiles": res["train_recompiles"],
            # self-healing guard verdicts (reliability/guard.py): rollback
            # and quarantine counts — a clean run reports 0 for both
            "guard_rollbacks": res["guard_rollbacks"],
            "quarantined_clips": res["quarantined_clips"],
            "mfu": res.get("mfu"),
            # analytic-counter MFU (analysis/gc_flops.py via fit()):
            # non-null wherever the step traces, including CPU smoke —
            # with the provenance labels the headline must carry
            "mfu_analytic": res.get("mfu_analytic"),
            "mfu_source": res.get("mfu_source"),
            "mfu_peak_source": res.get("mfu_peak_source"),
            # memory-ledger triple (obs/memory.py; the Trainer armed the
            # ledger, so train_state/prefetch-ring bytes are attributed)
            **hbm_headline(),
            "smoke": bool(args.smoke)}


# forced-host slice size for the smoke-mode MULTICHIP lane (the same 8 fake
# CPU devices tier-1 tests mesh semantics on); module-level so tests can
# shrink it
MULTICHIP_FORCED_DEVICES = 8
# bf16 loss-parity tolerance across shard counts (summation-order variance;
# a real sharding bug is orders of magnitude above it — same rationale as
# __graft_entry__'s dryrun)
MULTICHIP_PARITY_RTOL = 2e-2


def _multichip_shape(n: int) -> tuple:
    """(data, model) for the N-device point of the scaling lane: the 2-D
    layout the tentpole exercises — (2,4) at 8 devices."""
    if n >= 8 and n % 4 == 0:
        return (n // 4, 4)
    if n >= 4 and n % 2 == 0:
        return (n // 2, 2)
    return (n, 1)


def bench_multichip(args) -> dict:
    """The MULTICHIP scaling lane: 1 -> N clips/s/chip through the trainer's
    2-D (data, model) GSPMD backbone, with self-verifying numerics.

    Three probes, one honest record:
    - PARITY: the same fixed global batch stepped K times on a 1-device
      mesh and on the N-device (data, model) mesh must produce the same
      per-step loss trajectory (sharding changes the schedule, not the
      math) — `mesh_parity` within MULTICHIP_PARITY_RTOL;
    - SCALING: pipelined clips/s/chip at each mesh point — flat or better
      from 1 -> N is the healthy reading. Forced-host CPU points are
      tagged `forced_host` and are NEVER device numbers;
    - PORTABILITY: a checkpoint written under (1, N) restores under (N, 1)
      and under a single-device mesh at the identical step, and the next
      step's loss matches — the mesh-reshape restore contract
      (docs/PARALLELISM.md runbook).

    Plus one short Trainer.fit() on the N-device mesh so the
    steady-state-zero recompile contract (`train_recompiles == 0`) is
    proven under the 2-D layout, and per-chip MFU rides along whenever the
    XLA flops capture succeeds (whole-program FLOPs / mesh size — model-
    axis shards attributed once, never double-counted).
    """
    import jax
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import (
        CheckpointConfig, DataConfig, MeshConfig, ModelConfig,
        OptimConfig, TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.utils.bench_setup import (
        build_step_setup, fetch_loss, xla_flops,
    )

    devices = jax.devices()
    n = len(devices)
    platform = devices[0].platform
    out: dict = {
        "n_devices": n,
        "platform": platform,
        # smoke mode runs on the forced-host CPU slice by design (a
        # non-smoke child without a TPU never gets here: child_main)
        "forced_host": bool(args.smoke),
        "smoke": bool(args.smoke),
    }
    data_dim, model_dim = _multichip_shape(n)
    out["mesh_shape"] = [data_dim, model_dim]
    model_name = "tiny3d" if args.smoke else "slowfast_r50"
    frames, crop = (4, 32) if args.smoke else (8, 128)
    # smallest global batch >= 8 every mesh point divides (lcm, not
    # doubling: a 12/24/40-device slice has data_dim = 3/6/10, which no
    # power of two ever divides)
    GB = math.lcm(8, data_dim)
    # smoke (forced-host) runs the lane in fp32: the parity probe is a
    # NUMERICS gate and bf16 summation-order noise compounds across update
    # steps into false divergence. On device the lane stays bf16 (the
    # throughput dtype) and parity compares the FIRST step only — the
    # pre-update forward+loss, where 2e-2 covers reduction-order variance
    # (the dryrun_multichip precedent).
    mp = "fp32" if args.smoke else "bf16"
    out.update(model=model_name, frames=frames, crop=crop, global_batch=GB,
               mixed_precision=mp)
    k_parity = 3
    k_compare = k_parity if mp == "fp32" else 1
    k_timed = args.steps if not args.smoke else 3

    def make_point(devs, mesh_cfg):
        # dropout OFF: in-graph random masks are layout-invariant across
        # mesh shapes only under a partitionable threefry
        # (jax_threefry_partitionable); without it a parity probe with
        # dropout compares two different (both valid) training runs, so
        # the probe does not lean on the flag — the dryrun_multichip
        # convention
        return build_step_setup(
            model_name, frames=frames, crop=crop, batch_per_chip=1,
            num_classes=16, global_batch=GB, devices=list(devs),
            mesh_cfg=mesh_cfg, total_steps=k_parity + k_timed + 4,
            mixed_precision=mp, overrides={"dropout_rate": 0.0},
        )

    def run_point(setup, label):
        """K parity steps (each loss fetched) then a timed pipelined loop."""
        losses = []
        state = setup.state
        gbs = [setup.device_batch(0), setup.device_batch(1)]
        for i in range(k_parity):
            state, metrics = setup.step(state, gbs[i % 2], jax.random.key(i))
            losses.append(fetch_loss(metrics))
        t0 = time.perf_counter()
        for i in range(k_timed):
            state, metrics = setup.step(state, gbs[i % 2],
                                        jax.random.key(100 + i))
        fetch_loss(metrics)
        dt = time.perf_counter() - t0
        cps = GB * k_timed / dt
        log(f"[multichip] {label}: losses {[round(v, 4) for v in losses]}, "
            f"{cps:.2f} clips/s ({cps / setup.n_chips:.2f}/chip)")
        return losses, cps

    # 1-device reference, then the N-device (data, model) point
    ref = make_point(devices[:1], MeshConfig(data=1, model=1))
    ref_losses, ref_cps = run_point(ref, "1-device")
    curve = {"1": round(ref_cps, 3)}
    parity_max_rel = 0.0
    if n > 1:
        big = make_point(devices, MeshConfig(data=data_dim, model=model_dim))
        big_losses, big_cps = run_point(
            big, f"{n}-device ({data_dim},{model_dim})")
        curve[str(n)] = round(big_cps / n, 3)
        parity_max_rel = max(
            abs(a - b) / max(abs(b), 1e-9)
            for a, b in zip(big_losses[:k_compare], ref_losses[:k_compare]))
        flops = None
        try:
            flops = xla_flops(big.step.lower(
                big.state, big.device_batch(0), jax.random.key(0)).compile())
        except Exception as e:
            log(f"[multichip] flops capture failed: {type(e).__name__}: {e}")
        peak, peak_source = resolve_peak(devices[0])
        if flops:
            step_s = GB / big_cps
            tflops_chip = flops / step_s / 1e12 / n
            out["multichip_tflops_per_chip"] = round(tflops_chip, 3)
            if peak:
                out["multichip_mfu"] = round(tflops_chip / peak, 4)
        # analytic counter (analysis/gc_flops.py): the mfu_analytic
        # numerator this lane headlines even where cost-model capture
        # failed — the exact hole that kept mfu null on r03-r05
        try:
            from pytorchvideo_accelerate_tpu.analysis.graphcheck import (
                analytic_step_flops,
            )

            aflops, _ = analytic_step_flops(
                big.step, (big.state, big.device_batch(0),
                           jax.random.key(0)))
            if aflops and peak:
                step_s = GB / big_cps
                out["multichip_mfu_analytic"] = round(
                    aflops / step_s / 1e12 / n / peak, 4)
                out["multichip_mfu_source"] = (
                    "costmodel" if flops else "analytic")
                if peak_source:
                    out["multichip_mfu_peak_source"] = peak_source
        except Exception as e:
            log(f"[multichip] analytic flops failed: "
                f"{type(e).__name__}: {e}")
    out["cps_per_chip"] = curve
    out["parity_max_rel"] = round(parity_max_rel, 6)
    out["mesh_parity"] = bool(parity_max_rel <= MULTICHIP_PARITY_RTOL)

    # checkpoint portability: save under (1, N), restore under (N, 1) and
    # single-chip; the restored state continues with the identical loss
    if n > 1:
        import shutil
        import tempfile

        from pytorchvideo_accelerate_tpu.trainer.checkpoint import Checkpointer

        ckpt_dir = tempfile.mkdtemp(prefix="pva_multichip_ckpt_")
        try:
            a = make_point(devices, MeshConfig(data=1, model=n))
            sa = a.state
            sa, _ = a.step(sa, a.device_batch(0), jax.random.key(0))
            ckpt = Checkpointer(ckpt_dir, use_async=False)
            ckpt.save(1, sa)
            ckpt.wait()
            _, m2 = a.step(sa, a.device_batch(1), jax.random.key(1))
            ref_next = fetch_loss(m2)
            diffs = []
            for tag, devs, mcfg in (
                    (f"({n},1)", devices, MeshConfig(data=n, model=1)),
                    ("single", devices[:1], MeshConfig(data=1, model=1))):
                b = make_point(devs, mcfg)
                sb, _, step_b = ckpt.restore(b.state, step=1, mesh=b.mesh)
                _, mb = b.step(sb, b.device_batch(1), jax.random.key(1))
                next_b = fetch_loss(mb)
                rel = abs(next_b - ref_next) / max(abs(ref_next), 1e-9)
                diffs.append(rel)
                log(f"[multichip] ckpt (1,{n})->{tag}: step {step_b}, "
                    f"next loss {next_b:.5f} vs {ref_next:.5f} "
                    f"(rel {rel:.2e})")
                if step_b != 1:
                    diffs.append(float("inf"))
            ckpt.close()
            out["ckpt_max_rel"] = round(max(diffs), 6)
            out["mesh_ckpt_portable"] = bool(
                max(diffs) <= MULTICHIP_PARITY_RTOL)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    # Trainer.fit() through the N-device 2-D mesh: the recompile contract
    # must hold under the (data, model) layout, not just 1-D DP
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    tcfg = TrainConfig(
        mesh=MeshConfig(data=data_dim, model=model_dim),
        # flight records land under the lane's scratch dir, never "."
        checkpoint=CheckpointConfig(output_dir=_scratch_outdir("multichip")),
        model=ModelConfig(name=model_name, num_classes=16, dropout_rate=0.0),
        data=DataConfig(synthetic=True,
                        synthetic_num_videos=max(4 * data_dim, 8),
                        num_frames=frames, crop_size=crop, batch_size=2,
                        num_workers=1, limit_val_batches=1),
        optim=OptimConfig(num_epochs=1, lr=0.01),
        mixed_precision="bf16",
    )
    # pva-tpu-spmdcheck dynamic half (docs/STATIC_ANALYSIS.md § spmdcheck):
    # record the REAL fit's collective schedule through the hangcheck
    # sections, then replay a deterministic probe segment (real host
    # collectives) under two emulated host labels and diff — run-to-run
    # schedule determinism is the property every pod host must have, so
    # the emulation diffs the real mechanism and the lane headlines
    # spmd_schedule_divergence == 0 forever
    from pytorchvideo_accelerate_tpu.parallel import (
        collectives,
        schedule_recorder as sched_rec,
    )
    from pytorchvideo_accelerate_tpu.parallel.hangcheck import (
        collective_section,
    )

    rec = sched_rec.CollectiveScheduleRecorder(host="fit")
    sched_rec.install_schedule_recorder(rec)
    try:
        res = Trainer(tcfg).fit()
        # non-vacuity: the real fit must have flowed through the watched
        # sections (ckpt_save/ckpt_close at minimum ride every fit)
        out["spmd_fit_sections"] = rec.counts().get("fit", 0)
        for h in range(2):
            with rec.as_host(f"host={h}/2"):
                for i in range(3):
                    with collective_section("step_dispatch", step=i):
                        pass
                    collectives.host_allgather(np.int32(i))
                    collectives.host_broadcast(np.int32(i))
        probe = {k: v for k, v in rec.schedules().items() if k != "fit"}
        div = sched_rec.diff_schedules(probe)
        sched_rec.publish_schedule_report(div)
        out["spmd_schedule_divergence"] = int(div.get(
            "divergence_count", 0))
        # seeded counterpart, every run: one emulated host SKIPS a
        # broadcast — the differ MUST name it, or the clean 0 above is
        # vacuous
        rec.clear()
        for h in range(2):
            with rec.as_host(f"host={h}/2"):
                collectives.host_allgather(np.int32(0))
                if h == 0:
                    collectives.host_broadcast(np.int32(1))
                with collective_section("epoch_sync"):
                    pass
        seeded = sched_rec.diff_schedules(rec.schedules())
        first = seeded.get("first_divergence") or {}
        seeded_ops = {k: (e[1] if e else None)
                      for k, e in (first.get("hosts") or {}).items()}
        out["spmd_divergence_detected"] = bool(
            seeded.get("diverged")
            and "host_broadcast" in seeded_ops.values())
    finally:
        sched_rec.uninstall_schedule_recorder()
    out["train_recompiles"] = res.get("train_recompiles")
    out["trainer_cps_chip"] = round(
        res.get("clips_per_sec", 0.0) / max(n, 1), 3)
    if res.get("mfu") is not None and "multichip_mfu" not in out:
        out["multichip_mfu"] = round(res["mfu"], 4)
    if (res.get("mfu_analytic") is not None
            and "multichip_mfu_analytic" not in out):
        out["multichip_mfu_analytic"] = round(res["mfu_analytic"], 4)
        if res.get("mfu_source"):
            out["multichip_mfu_source"] = res.get("mfu_source")
        if res.get("mfu_peak_source"):
            out["multichip_mfu_peak_source"] = res.get("mfu_peak_source")
    log(f"[multichip] {json.dumps(out)}")
    return out


def bench_data(args) -> dict:
    """Host input-pipeline microbench (SURVEY §7 hard-part 1): encodes a
    small synthetic video tree, then measures raw cv2 decode vs pre-decoded
    cache clips/sec and ClipLoader end-to-end throughput on both transports.

    Non-smoke shapes are the production-bound ones (320x256 source ->
    256^2 crops, the reference transform geometry, run_slowfast_r50.sh):
    these numbers are host-CPU-real — trustworthy on any box, including when
    device timing is not — and they bound the chips/host ratio the input
    pipeline can feed."""
    import shutil
    import tempfile

    import numpy as np

    try:
        import cv2
    except ImportError:
        return {"error": "cv2 unavailable"}

    from pytorchvideo_accelerate_tpu.data.cache import (
        bench_decode_vs_cache, build_cache,
    )
    from pytorchvideo_accelerate_tpu.data.manifest import scan_directory
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader, VideoClipSource,
    )
    from pytorchvideo_accelerate_tpu.data.transforms import make_transform

    tmp = tempfile.mkdtemp(prefix="pva_bench_data_")
    fps = 30.0
    n_videos, n_frames = (4, 24) if args.smoke else (8, 64)
    w_px, h_px = (96, 64) if args.smoke else (320, 256)
    crop = 64 if args.smoke else 256  # reference crop (run_slowfast_r50.sh)
    num_frames = 8
    clip_duration = num_frames * 2 / fps  # sampling_rate 2
    out: dict = {"video_px": f"{w_px}x{h_px}", "crop": crop,
                 "num_videos": n_videos}
    rng = np.random.default_rng(0)
    try:
        root = os.path.join(tmp, "train")
        for c in range(2):
            cls = os.path.join(root, f"class{c}")
            os.makedirs(cls)
            for v in range(n_videos // 2):
                wr = cv2.VideoWriter(
                    os.path.join(cls, f"v{v}.mp4"),
                    cv2.VideoWriter_fourcc(*"mp4v"), fps, (w_px, h_px))
                if not wr.isOpened():
                    return {"error": "mp4v codec unavailable"}
                for _ in range(n_frames):
                    wr.write(rng.integers(0, 255, (h_px, w_px, 3), np.uint8))
                wr.release()

        cache_dir = os.path.join(tmp, "cache")
        t0 = time.perf_counter()
        build_cache(root, cache_dir, fps=fps, short_side=min(h_px, w_px),
                    num_workers=2)
        out["cache_build_s"] = round(time.perf_counter() - t0, 2)
        out.update(bench_decode_vs_cache(
            root, cache_dir, clip_duration=clip_duration,
            n_clips=16 if args.smoke else 48, num_workers=2))

        # loader end-to-end: decode + transforms + batch assembly
        tf = make_transform(num_frames=num_frames, training=True,
                            min_short_side_scale=crop,
                            max_short_side_scale=crop + 64, crop_size=crop)
        manifest = scan_directory(root)
        epochs = 2 if args.smoke else 4
        n_workers = 2 if args.smoke else 4
        out["num_workers"] = n_workers
        def run_loader(key: str, transform, transport: str):
            src = VideoClipSource(manifest, transform, clip_duration,
                                  training=True, seed=0)
            loader = ClipLoader(src, global_batch_size=4, shuffle=True,
                                num_workers=n_workers, transport=transport)
            try:
                clips = 0
                for _ in loader.epoch(0):  # warm pools/caches; drain fully
                    pass                   # so no background decode skews timing
                t0 = time.perf_counter()
                for ep in range(1, epochs + 1):
                    for batch in loader.epoch(ep):
                        clips += batch["label"].shape[0]
                out[key] = round(clips / (time.perf_counter() - t0), 2)
                if loader.transport != transport:  # native lib unavailable
                    out[key + "_note"] = f"fell back to {loader.transport}"
            finally:
                loader.close()

        run_loader("loader_thread_clips_per_sec", tf, "thread")
        run_loader("loader_process_clips_per_sec", tf, "process")
        # u8-through transform (host_cast=u8): quantifies the host-side
        # win of skipping normalize + batching quarter-size clips
        run_loader("loader_thread_u8_clips_per_sec",
                   make_transform(num_frames=num_frames, training=True,
                                  min_short_side_scale=crop,
                                  max_short_side_scale=crop + 64,
                                  crop_size=crop, output_dtype="uint8"),
                   "thread")
        log(f"[data] {out}")
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_serving(args) -> dict:
    """Serving-lane smoke (--serve-smoke): a tiny engine + micro-batcher
    under a threaded synthetic client, measuring what the serving docs tell
    operators to watch — p50/p99 request latency and the batcher fill
    ratio. CPU-real numbers (tiny3d model, parent process is CPU-pinned):
    they prove the queue->bucket->mask->futures machinery and its stats
    plumbing, not chip throughput."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh
    from pytorchvideo_accelerate_tpu.serving import (
        InferenceEngine, MicroBatcher, ServingStats,
    )

    frames, crop, n_requests = (4, 32, 32) if args.smoke else (8, 64, 96)
    num_classes = 16
    mcfg = ModelConfig(name="tiny3d", num_classes=num_classes,
                       dropout_rate=0.0)
    model = create_model(mcfg, "bf16")
    variables = model.init(jax.random.key(0),
                           np.zeros((1, frames, crop, crop, 3), np.float32))
    mesh = make_mesh()
    stats = ServingStats(window=256)
    engine = InferenceEngine(
        model, variables["params"], variables.get("batch_stats", {}), mesh,
        num_classes=num_classes, max_batch_size=8, stats=stats)
    batcher = MicroBatcher(engine, max_wait_ms=2.0, max_queue=512,
                           stats=stats)
    stats.queue_depth_fn = batcher.queue_depth
    rng = np.random.default_rng(0)
    clip = rng.standard_normal((frames, crop, crop, 3)).astype(np.float32)
    try:
        engine.warmup({"video": clip})  # compiles every bucket up front
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=8) as pool:
            futs = [pool.submit(lambda: batcher.submit({"video": clip})
                                .result(timeout=120))
                    for _ in range(n_requests)]
            logits = [f.result(timeout=180) for f in futs]
        dt = time.perf_counter() - t0
        assert all(np.asarray(l).shape == (num_classes,) for l in logits)
    finally:
        batcher.close()
    snap = stats.snapshot()
    out = {
        "serve_p50_ms": snap["p50_ms"],
        "serve_p99_ms": snap["p99_ms"],
        "serve_fill_ratio": snap["batch_fill_ratio"],
        "serve_rps": round(n_requests / dt, 2),
        "serve_batches": snap["batches"],
        "serve_compiled_buckets": snap["compiled_buckets"],
        "n_requests": n_requests,
        "buckets": list(engine.buckets),
        "smoke": bool(args.smoke),
    }
    log(f"[serving] {out}")
    return out


def bench_transport_crossover(args) -> dict:
    """Thread vs process worker pools on a transform-heavy (GIL-bound)
    workload — no video decode, pure numpy per-item work — at >=4 workers
    (VERDICT r3 item 6: find where, if anywhere, the process transport wins
    on this host, and record the machine context the answer depends on)."""
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader, SyntheticClipSource,
    )

    import numpy as np

    n_items = 32 if args.smoke else 96
    frames, size = (8, 112) if args.smoke else (16, 224)
    out: dict = {"cpus": os.cpu_count(), "num_workers": 4,
                 "frames": frames, "size": size}

    def heavy_transform(raw, rng):
        # deliberately GIL-holding numpy work sized like a real augment stack
        v = raw[:frames].astype(np.float32) / 255.0
        for _ in range(6):
            v = np.clip(v * 1.01 + 0.001, -3, 3)
            v = (v - v.mean(axis=(1, 2), keepdims=True)) / (
                v.std(axis=(1, 2), keepdims=True) + 1e-5)
        return {"video": v}

    for transport in ("thread", "process"):
        src = SyntheticClipSource(heavy_transform, num_videos=n_items,
                                  num_classes=5, raw_frames=frames,
                                  raw_size=(size, size), seed=0)
        loader = ClipLoader(src, global_batch_size=4, shuffle=False,
                            num_workers=4, transport=transport)
        try:
            for _ in loader.epoch(0):  # warm, fully drained
                pass
            t0 = time.perf_counter()
            clips = 0
            for ep in (1, 2):
                for batch in loader.epoch(ep):
                    clips += batch["label"].shape[0]
            out[f"{transport}_clips_per_sec"] = round(
                clips / (time.perf_counter() - t0), 2)
            if loader.transport != transport:
                out[f"{transport}_note"] = f"fell back to {loader.transport}"
        finally:
            loader.close()
    t, p = out.get("thread_clips_per_sec"), out.get("process_clips_per_sec")
    # no verdict when the process run silently fell back to threads — a
    # thread-vs-thread comparison would answer the crossover question wrong
    if t and p and "process_note" not in out:
        out["winner"] = "process" if p > t else "thread"
        out["ratio_process_over_thread"] = round(p / t, 3)
    log(f"[transport] {out}")
    return out


# SERVE_FLEET smoke sizing: (replicas, forced CPU devices for the child,
# offered rps, arrival window s, p99 SLO ms, head-sampling rate for the
# lane's distributed traces). Module-level so the contract test can shrink
# it; the SLO is generous for a CPU child that compiles tiny3d buckets
# while serving — the lane proves the fleet machinery, the absolute
# numbers are honest smoke numbers.
FLEET_SMOKE = dict(replicas=2, devices=2, rate_rps=20.0, duration_s=4.0,
                   slo_p99_ms=2500.0, trace_sample=0.5)
FLEET_FULL = dict(replicas=2, devices=0, rate_rps=100.0, duration_s=10.0,
                  slo_p99_ms=500.0, trace_sample=0.1)

# subprocess body for the fleet lane's TRACED process replica: the shared
# stub engine (host-side forward, no model compile) behind the real
# Scheduler + InferenceServer with tracing armed, so a request routed here
# crosses a REAL process boundary (router -> traceparent HTTP hop ->
# replica scheduler -> engine dispatch) and its trace ring lands in
# {outdir}/trace_ring.json on SIGTERM-drain — the multi-process half of
# the merged fleet timeline. One JSON line {{"url": ...}} once bound.
_TRACE_SRV_CODE = """
import json
from pytorchvideo_accelerate_tpu.obs import trace as obstrace
obstrace.configure_tracing(1.0, seed=0, capacity=8192, output_dir={outdir!r})
from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
from pytorchvideo_accelerate_tpu.serving.server import InferenceServer
from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
from pytorchvideo_accelerate_tpu.serving.stub import StubEngine

engine = StubEngine(forward_s=0.002, num_classes=16)
engine.model_name = "trace-stub"
stats = ServingStats(window=512)
sched = Scheduler(engine, stats=stats, max_queue=256,
                  realtime_deadline_ms=30000.0)
srv = InferenceServer(engine, sched, stats, host="127.0.0.1", port=0,
                      request_timeout_s=30.0)
host, port = srv.address
print(json.dumps({{"url": "http://%s:%d" % (host, port)}}), flush=True)
srv.serve_forever(drain_on_sigterm=True)
"""


def _spawn_traced_replica(outdir: str, startup_timeout_s: float = 120.0):
    """Start the traced stub serving process; returns (Popen, HttpReplica).
    Uses the shared wedge-safe bind-line reader (fleet/pool.py) — a child
    that wedges before binding fails the lane, never hangs it."""
    import atexit
    import shutil

    from pytorchvideo_accelerate_tpu.fleet.pool import (
        HttpReplica,
        read_line_with_deadline,
    )

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", _TRACE_SRV_CODE.format(outdir=outdir)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)

    def reap():
        # a lane failure between spawn and the trace-collection teardown
        # propagates straight out of bench_fleet; the bench child then
        # exits, but this SUBPROCESS would be reparented to init and serve
        # forever — reap it (idempotent: the normal path already waited)
        if proc.poll() is None:
            try:
                proc.kill()
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 - best-effort at interpreter exit
                pass
        shutil.rmtree(outdir, ignore_errors=True)

    atexit.register(reap)
    # match on the URL payload so a stray library line on the child's
    # stdout (a warning, a banner) can't be mistaken for the bind line;
    # ANY failure from here kills the child — it must not idle on its
    # port until the atexit reaper while the lane runs degraded
    try:
        line, eof = read_line_with_deadline(proc, startup_timeout_s,
                                            match='"url"',
                                            name="fleet-trace-read")
        if not (line or "").strip():
            raise RuntimeError(
                f"traced replica "
                f"{'closed stdout' if eof else 'produced no URL'} within "
                f"{startup_timeout_s}s (exit={proc.poll()})")
        url = json.loads(line)["url"]
    except Exception:
        proc.kill()
        raise
    return proc, HttpReplica("trace-proc", url, pid=proc.pid,
                             timeout_s=30.0)


def bench_fleet(args) -> dict:
    """The SERVE_FLEET lane: ≥2 `InferenceEngine` replicas on disjoint
    meshes behind the fleet router, driven OPEN-loop (Poisson arrivals,
    heavy-tail view mix) while a blue/green checkpoint hot-swap lands
    mid-load. Headlines `serve_rps` / `serve_p99_ms_under_load` /
    `swap_blackout_ms` / `fleet_shed_frac`; a non-smoke run that fell back
    to CPU refuses to headline (suspect), per the standing bench rule.

    Proof obligations baked into the record (asserted by --smoke):
    - the open-loop schedule was KEPT (`open_loop_ok`) — otherwise the
      harness degraded to closed-loop and the rps/p99 numbers are fiction;
    - zero failed (non-shed) requests across the whole run, INCLUDING the
      mid-load swap — sheds are policy, failures are bugs;
    - the swap measurably cut over: post-swap logits differ from pre-swap
      logits for the same probe clip (params are scaled on export);
    - distributed tracing (obs/trace.py) is ARMED for the lane: in smoke
      a third, traced stub-engine replica runs as a REAL separate process,
      the lane merges its trace ring with the child's into
      fleet_trace.json (`pva-tpu-trace` machinery), and ≥1 sampled request
      demonstrably spans router → HTTP hop → replica scheduler → engine
      dispatch across the process boundary (`trace_linked`), with the
      tracer's self-measured overhead under 2% of the run
      (`trace_overhead_frac`).
    """
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np
    import optax

    from pytorchvideo_accelerate_tpu.config import (
        DataConfig, MeshConfig, ModelConfig, TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.fleet import (
        LoadGen, LocalReplica, ReplicaPool, Router, Scheduler,
        heavy_tail_clip_factory, hot_swap,
    )
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh
    from pytorchvideo_accelerate_tpu.serving import (
        InferenceEngine, ServingStats,
    )
    from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
        export_inference,
    )
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    from pytorchvideo_accelerate_tpu.obs import trace as obstrace
    from pytorchvideo_accelerate_tpu.obs import tracetool

    shape = FLEET_SMOKE if args.smoke else FLEET_FULL
    frames, crop = (4, 32) if args.smoke else (8, 64)
    num_classes = 16
    devices = jax.devices()
    platform = devices[0].platform
    # tracing ARMED for the whole lane (head-sampled requests + forced
    # probes); the ring merges with the traced process replica's below
    tracer = obstrace.configure_tracing(shape["trace_sample"], seed=0,
                                        capacity=16384)
    # the acceptance bar is >= 2 replicas; on a 1-device host they share
    # the device (distinct engines/executables), on the forced-host slice
    # and real multi-chip they land on disjoint single-device meshes
    n_rep = shape["replicas"]
    cfg = TrainConfig(
        mesh=MeshConfig(data=1),
        model=ModelConfig(name="tiny3d", num_classes=num_classes,
                          dropout_rate=0.0),
        data=DataConfig(num_frames=frames, crop_size=crop),
    )
    model = create_model(cfg.model, "bf16")
    variables = model.init(
        jax.random.key(0),
        np.zeros((1, frames, crop, crop, 3), np.float32))
    params, bstats = variables["params"], variables.get("batch_stats", {})

    rng = np.random.default_rng(0)
    base_clip = {"video": rng.standard_normal(
        (frames, crop, crop, 3)).astype(np.float32)}
    two_view = {"video": np.stack([base_clip["video"]] * 2)}

    replicas = []
    for i in range(n_rep):
        # one device per replica when the slice allows (the forced-host
        # multi-device CI path); engines share weights, not executables
        dev = devices[i % len(devices)]
        mesh = make_mesh(MeshConfig(data=1), devices=[dev])
        stats = ServingStats(window=2048)
        engine = InferenceEngine(model, params, bstats, mesh,
                                 num_classes=num_classes, max_batch_size=4,
                                 stats=stats, model_name="tiny3d")
        log(f"[fleet] replica {i} on {dev}: warming buckets "
            f"{engine.buckets} (1- and 2-view)")
        engine.warmup(base_clip)
        engine.warmup(two_view)
        sched = Scheduler(engine, max_queue=256, stats=stats,
                          realtime_deadline_ms=shape["slo_p99_ms"] * 4,
                          batch_max_wait_ms=5.0, name=f"r{i}")
        replicas.append(LocalReplica(f"r{i}", sched))
    # in smoke, a third replica is a REAL traced serving process (stub
    # engine, no compile): requests routed there cross the traceparent
    # HTTP hop, making the merged trace genuinely multi-process. It joins
    # the pool only AFTER the open-loop window — its JSON serialization
    # would otherwise contend with the arrival thread and slip the
    # schedule (open_loop_ok) the lane exists to keep honest — and the
    # weight-swap probes pin to replicas[0] (a LocalReplica the hot-swap
    # actually cuts over), so the stub cannot contaminate them either.
    trace_proc = None
    trace_dir = None
    trace_replica = None
    if args.smoke:
        trace_dir = tempfile.mkdtemp(prefix="pva_fleet_trace_")
        try:
            trace_proc, trace_replica = _spawn_traced_replica(trace_dir)
            log(f"[fleet] traced process replica at {trace_replica.url} "
                f"(pid {trace_proc.pid})")
        except Exception as e:  # noqa: BLE001 - lane degrades, smoke asserts catch it
            log(f"[fleet] traced process replica failed to start: {e}")
    pool = ReplicaPool(replicas, health_interval_s=0.25)
    router = Router(pool)

    # the green checkpoint: same model, deterministically different weights
    # (scaled), exported through the REAL artifact path so the swap
    # exercises from_artifact -> pre-warm -> cutover end to end
    art_dir = tempfile.mkdtemp(prefix="pva_fleet_swap_")
    green_params = jax.tree.map(lambda x: x * 1.25, params)
    export_inference(
        art_dir, TrainState.create(green_params, bstats, optax.sgd(0.1)),
        config=cfg, meta={"num_classes": num_classes, "model": "tiny3d"})

    pre_logits = np.asarray(
        replicas[0].submit(base_clip).result(timeout=60), np.float32)

    swap_out: dict = {}
    gen = LoadGen(router.submit, rate_rps=shape["rate_rps"],
                  duration_s=shape["duration_s"],
                  clip_factory=heavy_tail_clip_factory(
                      base_clip, view_mix=((1, 0.9), (2, 0.1))),
                  seed=0, priority="realtime")

    def swapper():
        time.sleep(shape["duration_s"] * 0.4)  # mid-load, by construction
        try:
            swap_out.update(hot_swap(replicas, art_dir))
        except Exception as e:  # noqa: BLE001 - a failed swap IS the result
            swap_out["error"] = f"{type(e).__name__}: {e}"

    st = threading.Thread(target=swapper, daemon=True)
    st.start()
    run_wall = None
    try:
        t_run0 = time.perf_counter()
        report = gen.run()
        load_wall = time.perf_counter() - t_run0
        st.join(timeout=300.0)
        # the traced process replica joins the rotation now (post-load):
        # list append is safe against the poller's iteration, and the
        # fresh member is routable immediately (never marked down)
        if trace_replica is not None:
            pool.replicas.append(trace_replica)
        # forced-sample probes (head sampling bypassed — debug traces):
        # the idle router rotates ties round-robin, so 4 probes guarantee
        # every pool member, INCLUDING the traced process replica, serves
        # at least one fully-sampled request
        t_probe0 = time.perf_counter()
        for i in range(4):
            h = tracer.start("trace_probe", force=True, seq=i)
            try:
                with h:
                    router.submit(base_clip).result(timeout=60)
            except Exception as e:  # noqa: BLE001 - probe failure is lane evidence
                log(f"[fleet] trace probe {i} failed: {e}")
        # overhead denominator: the phases that actually carried traced
        # traffic (load window + probe burst) — including the idle
        # swap-join wait would deflate the fraction the smoke gate checks
        run_wall = max(load_wall + time.perf_counter() - t_probe0, 1e-6)
        post_logits = np.asarray(
            replicas[0].submit(base_clip).result(timeout=60), np.float32)
    finally:
        router.close()
        shutil.rmtree(art_dir, ignore_errors=True)
    # --- trace collection: SIGTERM-drain the process replica (its ring
    # dumps to trace_dir/trace_ring.json), merge with this process's ring
    # into one timeline, and verify the cross-process linkage ------------
    trace_out: dict = {}
    try:
        payloads = [tracer.export()]
        if trace_proc is not None:
            try:
                trace_proc.send_signal(signal.SIGTERM)
                trace_proc.wait(timeout=60)
            except Exception:  # noqa: BLE001 - a wedged drain must not hang the lane
                trace_proc.kill()
                trace_proc.wait()
            ring_path = os.path.join(trace_dir, "trace_ring.json")
            try:
                with open(ring_path) as f:
                    payloads.append(json.load(f))
            except (OSError, ValueError) as e:
                log(f"[fleet] traced replica ring unreadable: {e}")
        merged = tracetool.merge_exports(payloads)
        merged_path = os.path.join(HERE, "fleet_trace.json")
        with open(merged_path, "w") as f:
            json.dump(merged, f)
        summary = tracetool.summarize(merged)
        tstats = tracer.stats()
        # ≥1 sampled request spanning router->replica->engine ACROSS the
        # process boundary: a trace with events from >=2 pids that reaches
        # an engine-side device_dispatch
        linked = tracetool.linked_traces(
            merged, require_names=("device_dispatch",), min_pids=2)
        trace_out = {
            "trace_sampled": int(tstats["sampled"]),
            # head-sampled = sampled minus forced debug probes: the number
            # that proves the obs.trace_sample_rate decision stream works
            # (the probes alone would trivially satisfy a >=1 assert)
            "trace_head_sampled": int(tstats["sampled"]
                                      - tstats["forced"]),
            "trace_overhead_frac": round(
                tstats["overhead_s"] / run_wall, 5) if run_wall else None,
            "trace_linked": bool(linked) if args.smoke else None,
            "trace_events": summary["events"],
            "trace_multiprocess": summary["traces_multiprocess"],
            "trace_export": merged_path,
        }
        log(f"[fleet] trace: {summary['events']} events over "
            f"{summary['traces']} traces from pids {summary['pids']}, "
            f"{len(linked)} cross-process linked")
    except Exception as e:  # noqa: BLE001 - trace plumbing must not sink the lane
        log(f"[fleet] trace collection failed: {type(e).__name__}: {e}")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        obstrace.disable_tracing()
    fleet_snap = router.fleet_snapshot()
    swapped = not np.allclose(pre_logits, post_logits, atol=1e-6)
    out = {
        "serve_rps": report["achieved_rps"],
        "serve_p99_ms_under_load": report["p99_ms"],
        "swap_blackout_ms": swap_out.get("swap_blackout_ms"),
        "fleet_shed_frac": report["shed_frac"],
        "fleet_failed": int(report["failed"]),
        "offered_rps": report["offered_rps"],
        "open_loop_ok": report["open_loop_ok"],
        "weights_cut_over": bool(swapped),
        "replicas": n_rep,
        "slo_p99_ms": shape["slo_p99_ms"],
        "fleet_requests": fleet_snap["requests"],
        "router_retries": fleet_snap["router_retries"],
        "swap": {k: v for k, v in swap_out.items()},
        "platform": platform,
        "smoke": bool(args.smoke),
    }
    out.update(trace_out)
    if "error" in swap_out:
        out["error"] = f"hot-swap failed: {swap_out['error']}"
    log(f"[fleet] {json.dumps(out)}")
    return out


# FLEET_AUTO sizing: the control-loop lane runs entirely in-process on
# stub engines (the controllers are host-side control code; the subprocess
# spawn actuator is chaos leg `autoscale_kill`'s job), so smoke and full
# differ only in traffic volume and SLO tightness. Module-level so the
# contract test can shrink it. The x3d stub serves buckets (1, 2) at
# `forward_s` per launch, capping one replica near 2/forward_s rps — the
# step rate is sized to genuinely overload the single starting replica.
FLEET_AUTO_SMOKE = dict(base_rps=6.0, step_rps=60.0, base_s=1.0,
                        step_s=3.0, forward_s=0.05, probe_s=1.5,
                        slo_p99_ms=2500.0, converge_deadline_s=8.0,
                        sessions=4, advances=6, budget_mb=3000.0,
                        canary_rps=30.0, canary_burst_s=1.2)
FLEET_AUTO_FULL = dict(base_rps=10.0, step_rps=120.0, base_s=2.0,
                       step_s=6.0, forward_s=0.05, probe_s=3.0,
                       slo_p99_ms=1000.0, converge_deadline_s=15.0,
                       sessions=8, advances=8, budget_mb=3000.0,
                       canary_rps=60.0, canary_burst_s=2.5)


def bench_fleet_auto(args) -> dict:
    """The FLEET_AUTO lane: the fleet-intelligence control loops
    (fleet/control/, docs/SERVING.md § fleet intelligence) closed-loop
    against real traffic. Headlines `autoscale_converge_s` /
    `fleet_scaledown_shed_frac` / `canary_rollback` /
    `fleet_models_served`; the verdict keys (`canary_promoted`,
    `fleet_session_failures`) ride even on a refused round.

    Proof obligations baked into the record (asserted by --smoke):
    - CONVERGENCE: an open-loop traffic STEP (loadgen piecewise profile)
      overloads the starting fleet; the damped autoscaler grows it, the
      last scaling action lands within `converge_deadline_s` of the step,
      and a steady-state probe at the FULL stepped rate then holds the
      p99 SLO with zero non-shed failures at the size the controller
      chose — the step run's own p99 includes the pre-scale backlog by
      construction and is recorded, never asserted;
    - SCALE-DOWN SAFETY: draining a victim re-homes every live streaming
      session (affinity dropped -> deterministic re-establish from the
      client's resendable window on a survivor); every advance across
      the drain verifies `stub_stream_logits` equality against the
      client's own window, zero non-shed failures, and the controller
      never drains the last routable replica;
    - MULTI-MODEL: >=2 model families (x3d_s + videomae_t) serve off ONE
      pool under a shared `ModelBudget`; pushing a third family past the
      budget sheds THAT family at the fleet door while the in-budget
      families keep serving untouched;
    - CANARY: a seeded-regression artifact (12x slower by construction)
      is auto-rolled-back by the escalation ladder with direction-aware
      perfdiff evidence, the blue engines restored; an equal-cost clean
      artifact under the SAME controller knobs evaluates clean and is
      promoted fleet-wide.
    """
    import jax
    import numpy as np

    from pytorchvideo_accelerate_tpu.fleet.control import (
        Autoscaler,
        CanaryController,
        ModelBudget,
        MultiModelFleet,
    )
    from pytorchvideo_accelerate_tpu.fleet.loadgen import (
        LoadGen,
        step_profile,
    )
    from pytorchvideo_accelerate_tpu.fleet.pool import (
        LocalReplica,
        ReplicaPool,
    )
    from pytorchvideo_accelerate_tpu.fleet.router import Router
    from pytorchvideo_accelerate_tpu.fleet.scheduler import Scheduler
    from pytorchvideo_accelerate_tpu.serving.batcher import QueueFullError
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.serving.stub import (
        StubEngine,
        StubStreamEngine,
        stub_stream_logits,
    )

    from pytorchvideo_accelerate_tpu.obs import memory as obs_memory

    shape = FLEET_AUTO_SMOKE if args.smoke else FLEET_AUTO_FULL
    platform = jax.devices()[0].platform
    fwd = shape["forward_s"]
    # arm the memory ledger for the lane's hbm_* triple (stub engines pin
    # no device arrays, so the attribution is trivially honest here —
    # backend peak where measured, zero-attributed estimate elsewhere)
    obs_memory.configure()

    def mk_replica(name, model, engine):
        stats = ServingStats(window=1024)
        # deadline effectively off: convergence must be driven by the
        # controller's queue/p99 signals, not masked by deadline sheds
        sched = Scheduler(engine, stats=stats, max_queue=512,
                          realtime_deadline_ms=30000.0,
                          name=f"auto-{name}")
        return LocalReplica(name, sched, model=model)

    def mk_x3d(name, tag=0.0, forward_s=None):
        return mk_replica(name, "x3d_s",
                          StubEngine(tag=tag, buckets=(1, 2),
                                     forward_s=(fwd if forward_s is None
                                                else forward_s)))

    # one pool, two families: a single x3d_s request replica (the one the
    # traffic step overloads) + two videomae_t stream replicas
    replicas = [mk_x3d("x3d-0"),
                mk_replica("vm-0", "videomae_t",
                           StubStreamEngine(forward_s=0.002)),
                mk_replica("vm-1", "videomae_t",
                           StubStreamEngine(forward_s=0.002))]
    pool = ReplicaPool(replicas, health_interval_s=0.1, name="auto")
    router = Router(pool)
    budget = ModelBudget(shape["budget_mb"])
    mmf = MultiModelFleet(router, budget)
    mmf.register_model("x3d_s", 1200.0,
                       latency_buckets_ms=(50, 100, 250, 1000, 2500))
    mmf.register_model("videomae_t", 1400.0,
                       latency_buckets_ms=(100, 500, 2000))
    base = {"video": np.zeros((2, 4, 4, 3), np.float32)}

    def x3d_submit(clip, **kw):
        return mmf.submit(clip, model="x3d_s", **kw)

    spawn_n = [0]

    def spawn():
        spawn_n[0] += 1
        return mk_x3d(f"x3d-auto-{spawn_n[0]}")

    out: dict = {}
    try:
        # --- phase A: convergence under an open-loop traffic step -------
        asc = Autoscaler(router, spawn_fn=spawn,
                         min_replicas=len(replicas),
                         max_replicas=len(replicas) + 4,
                         slo_p99_ms=shape["slo_p99_ms"],
                         queue_high=3.0, queue_low=0.3,
                         downscale_frac=0.1, cooldown_s=0.4,
                         interval_s=0.08, ewma_alpha=0.6,
                         drain_grace_s=2.0)
        replicas_start = len(pool.routable())
        asc.start()
        t0 = time.monotonic()
        step_report = LoadGen(
            x3d_submit,
            profile=step_profile((shape["base_s"], shape["base_rps"]),
                                 (shape["step_s"], shape["step_rps"])),
            clip_factory=lambda rng: dict(base), seed=0).run()
        t_step = t0 + shape["base_s"]
        post = [e for e in asc.actions_since(t_step)
                if e["action"] in ("up", "down", "replace")]
        converge_s = (round(max(e["t"] for e in post) - t_step, 3)
                      if post else 0.0)
        asc.close()
        scaled_to = len(pool.routable())
        probe = LoadGen(x3d_submit, rate_rps=shape["step_rps"],
                        duration_s=shape["probe_s"],
                        clip_factory=lambda rng: dict(base), seed=1).run()
        converged = bool(post) and scaled_to > replicas_start \
            and probe["p99_ms"] <= shape["slo_p99_ms"] \
            and probe["failed"] == 0 \
            and converge_s <= shape["converge_deadline_s"]
        log(f"[fleet_auto] converge: {replicas_start}->{scaled_to} "
            f"replicas in {converge_s}s, steady p99 {probe['p99_ms']} ms "
            f"(SLO {shape['slo_p99_ms']})")

        # --- multi-model budget: the third family sheds, the pool serves
        models_served = len(mmf.models())
        mmf.register_model("mvit_b", shape["budget_mb"])  # guaranteed over
        budget_shed = False
        try:
            mmf.submit(dict(base), model="mvit_b")
        except QueueFullError:
            budget_shed = True
        in_budget_ok = True
        try:
            mmf.submit(dict(base), model="x3d_s").result(timeout=30)
        except Exception:  # noqa: BLE001 - any failure breaks the claim
            in_budget_ok = False

        # --- phase B: scale-down re-homes every live streaming session -
        window, stride, fshape = 8, 2, (4, 4, 3)
        rng = np.random.default_rng(7)
        windows: dict = {}
        counts = {"advances": 0, "shed": 0, "failed": 0}

        def advance(sid, k, end):
            frames = rng.standard_normal(
                (stride,) + fshape).astype(np.float32)
            if k == 0:
                windows[sid] = rng.standard_normal(
                    (window,) + fshape).astype(np.float32)
            windows[sid] = np.concatenate(
                [windows[sid][stride:], frames], 0)
            counts["advances"] += 1
            try:
                # window attached on every advance (the resendable-window
                # client contract): a re-homed session re-establishes on
                # the survivor transparently, and the logits stay a pure
                # function of the client's own window — checkable
                res = mmf.submit(
                    {"video": frames}, model="videomae_t",
                    session={"sid": sid, "stride": stride, "end": end,
                             "window": windows[sid]}).result(timeout=30)
            except QueueFullError:
                counts["shed"] += 1
                return
            except Exception:  # noqa: BLE001 - any other failure is a bug
                counts["failed"] += 1
                return
            want = stub_stream_logits(windows[sid], 4)
            if not np.allclose(np.asarray(res).ravel(), want.ravel(),
                               atol=1e-5):
                counts["failed"] += 1

        n_sessions = int(shape["sessions"])
        for i in range(n_sessions):
            advance(f"fa-{i}", 0, False)
        # both stream replicas must hold >=1 pinned session before the
        # drain (the re-home target must outlive the victim); affinity
        # ties round-robin, so a few extra establishes always balance it
        for _ in range(8):
            if all(router.sessions_on(r.name) for r in pool.routable()
                   if getattr(r, "model", None) == "videomae_t"):
                break
            advance(f"fa-{n_sessions}", 0, False)
            n_sessions += 1
        for i in range(n_sessions):
            advance(f"fa-{i}", 1, False)
        # a second controller parameterized for the drain leg: idle is
        # queue-driven (the SLO term effectively off), so with traffic
        # gone it steps the target down once per cooldown; victims are
        # fewest-sessions-first, so the spawned x3d replicas reap first
        # and the first session-carrying victim proves the re-home
        asc2 = Autoscaler(router, spawn_fn=spawn, min_replicas=1,
                          max_replicas=len(pool.replicas) + 1,
                          slo_p99_ms=1e9, queue_high=3.0, queue_low=0.3,
                          downscale_frac=0.5, cooldown_s=0.05,
                          interval_s=0.05, ewma_alpha=1.0,
                          drain_grace_s=2.0)
        rehomed = 0
        for _ in range(64):
            before = {r.name: router.sessions_on(r.name)
                      for r in pool.routable()}
            if asc2.step() == "down":
                names = {r.name for r in pool.replicas}
                rehomed += sum(len(sids) for n, sids in before.items()
                               if n not in names)
            if rehomed or len(pool.routable()) <= 1:
                break
            time.sleep(0.06)
        asc2.close()
        for k in range(2, int(shape["advances"])):
            for i in range(n_sessions):
                advance(f"fa-{i}", k, k == int(shape["advances"]) - 1)
        shed_frac = (round(counts["shed"] / counts["advances"], 4)
                     if counts["advances"] else 0.0)
        log(f"[fleet_auto] scale-down: {rehomed} session(s) re-homed, "
            f"{counts['failed']} failure(s), shed_frac {shed_frac} over "
            f"{counts['advances']} advances")
    finally:
        router.close()

    # --- phase C: canary rollout — seeded regression, then a clean one -
    creps = [mk_x3d(f"cn-{i}", forward_s=0.004) for i in range(4)]
    pool2 = ReplicaPool(creps, health_interval_s=0.2, name="canary")
    router2 = Router(pool2)
    try:
        def burst(seed):
            return LoadGen(router2.submit, rate_rps=shape["canary_rps"],
                           duration_s=shape["canary_burst_s"],
                           clip_factory=lambda rng: dict(base),
                           seed=seed).run()

        cc = CanaryController(router2, fraction=0.25, threshold=0.5,
                              rollback_after=2)
        cc.start_rollout(lambda r: StubEngine(tag=7.0, forward_s=0.05,
                                              buckets=(1, 2)),
                         label="seeded-regression")
        verdict: dict = {}
        rollbacks = 0
        for i in range(cc.rollback_after):
            burst(10 + i)
            verdict = cc.evaluate()
            if verdict.get("rolled_back"):
                rollbacks += 1
                break
        restored = all(r.scheduler.current_engine().tag == 0.0
                       for r in creps)
        cc2 = CanaryController(router2, fraction=0.25, threshold=0.5,
                               rollback_after=2)
        cc2.start_rollout(lambda r: StubEngine(tag=5.0, forward_s=0.004,
                                               buckets=(1, 2)),
                          label="clean")
        burst(20)
        clean = cc2.evaluate()
        promoted = False
        if clean["action"] == "observe" and clean["strikes"] == 0:
            cc2.promote()
            promoted = all(r.scheduler.current_engine().tag == 5.0
                           for r in creps)
        log(f"[fleet_auto] canary: seeded regressions "
            f"{verdict.get('regressions')} -> {rollbacks} rollback(s); "
            f"clean -> promoted={promoted}")
    finally:
        router2.close()

    # the lane's own hbm triple, read BEFORE phase D swaps the process
    # ledger for its fake-stats probe (a probe's injected backend must
    # never color the lane's provenance label)
    hbm = hbm_headline()

    # --- phase D: burn-rate alert discipline + the budget-lies probe ---
    # D1: a seeded SLO breach must fire its multi-window burn-rate rule
    # EXACTLY once and clear on recovery (obs/alerts.py hysteresis) —
    # zero fires during the calm phases is the false-positive gate
    # scripts/analyze.sh reads off this record. Synthetic clock: the
    # windows are seconds-denominated, the probe must not be wall-paced.
    from pytorchvideo_accelerate_tpu.obs.alerts import AlertEngine, AlertRule
    from pytorchvideo_accelerate_tpu.obs.history import MetricsHistory
    from pytorchvideo_accelerate_tpu.obs.registry import Registry

    areg = Registry()
    g_p99 = areg.gauge("pva_probe_p99_ms",
                       "seeded SLO-breach driver (bench fleet_auto)")
    eng = AlertEngine(
        MetricsHistory(registry=areg, capacity=128),
        [AlertRule(name="p99_burn", kind="gauge", key="pva_probe_p99_ms",
                   objective=float(shape["slo_p99_ms"]),
                   fast_s=2.0, slow_s=8.0, hold_clear=2)],
        registry=areg)
    slo = float(shape["slo_p99_ms"])
    t_sim, fires = 1000.0, []
    for factor, ticks in ((0.25, 20), (4.0, 12), (0.25, 20)):
        g_p99.set(factor * slo)
        for _ in range(ticks):
            eng.tick(now=t_sim)
            t_sim += 1.0
        fires.append(eng.fires("p99_burn"))
    alert_fired_once = fires[0] == 0 and fires[1] == 1
    alert_cleared = not eng.active()
    # fires outside the seeded excursion: calm-phase fires + flap re-fires
    alert_false_positives = fires[0] + (fires[2] - fires[1])

    # D2: the budget-lies probe — a family that under-declares its
    # footprint must be refused where the ledger can measure it. Injected
    # backend stats flip ModelBudget onto its measured path; the liar
    # declares 10 MB (fits), the ledger sees the 90 MB weight pin it
    # actually made (sheds). Declared-vs-measured admission flipping on
    # the same state IS the acceptance criterion (ISSUE 18).
    obs_memory.configure(stats_fn=lambda: {
        "bytes_in_use": 200 * 10**6, "peak_bytes_in_use": 220 * 10**6,
        "bytes_limit": 10**9})
    lies = ModelBudget(100.0)
    lies.register("honest", 60.0)
    lies.register("liar", 10.0)
    admitted_declared = "liar" not in lies.over_budget()
    obs_memory.register("model_weights:liar", 90 * 10**6,
                        declared=10 * 10**6)
    refused_measured = "liar" in lies.over_budget()
    led = obs_memory.get_ledger()
    liar_drift = round(led.drift().get("model_weights:liar", 0.0), 2)
    # disarm: the fake stats_fn must not outlive the probe
    obs_memory.configure(enabled=False)
    budget_lies_refused = bool(admitted_declared and refused_measured)
    log(f"[fleet_auto] alerts: fires per phase {fires} "
        f"(cleared={alert_cleared}); budget-lies refused="
        f"{budget_lies_refused} (liar drift {liar_drift})")

    out = {
        "autoscale_converge_s": converge_s,
        "fleet_scaledown_shed_frac": shed_frac,
        "canary_rollback": rollbacks,
        "fleet_models_served": models_served,
        "canary_promoted": bool(promoted),
        "fleet_session_failures": int(counts["failed"]),
        "fleet_sessions_rehomed": int(rehomed),
        "autoscale_converged": bool(converged),
        "converge_deadline_s": shape["converge_deadline_s"],
        "replicas_start": replicas_start,
        "scaled_up_to": scaled_to,
        "steady_p99_ms": probe["p99_ms"],
        "steady_failed": int(probe["failed"]),
        "step_p99_ms": step_report["p99_ms"],
        "step_shed_frac": step_report["shed_frac"],
        "open_loop_ok": bool(step_report["open_loop_ok"]
                             and probe["open_loop_ok"]),
        "slo_p99_ms": shape["slo_p99_ms"],
        "budget_shed_ok": bool(budget_shed and in_budget_ok),
        # phase D verdicts (pva-tpu-hbm): burn-rate alert discipline —
        # the seeded breach fired once and cleared, zero calm-phase or
        # flap fires — and the measured-byte admission flip
        "alert_false_positives": int(alert_false_positives),
        "alert_fired_once": bool(alert_fired_once),
        "alert_cleared": bool(alert_cleared),
        "budget_lies_refused": budget_lies_refused,
        "budget_liar_drift": liar_drift,
        **hbm,
        "canary_regressions": sorted(verdict.get("regressions", [])),
        "canary_strikes": verdict.get("strikes"),
        "canary_blue_restored": bool(restored),
        "sessions": n_sessions,
        "advances": counts["advances"],
        "platform": platform,
        "smoke": bool(args.smoke),
    }
    log(f"[fleet_auto] {json.dumps(out)}")
    return out


# forced-host slice for the smoke-mode PIPELINE lane (same 8 fake CPU
# devices as the multichip lane); module-level so tests can shrink it
PIPELINE_FORCED_DEVICES = 8
# fp32 loss-parity tolerance across pipeline layouts (the lane runs fp32
# by construction — the parity probe is the acceptance gate, and bf16
# summation-order noise would compound across update steps)
PIPELINE_PARITY_RTOL = 2e-2


def bench_pipeline(args) -> dict:
    """The PIPELINE lane (parallel/pipeline.py; docs/PARALLELISM.md §
    pipeline): pipeline-parallel VideoMAE pretrain on the 2-D (data,
    model) train mesh, with self-verifying numerics.

    Probes, one honest record:
    - PARITY (the acceptance gate): the same fixed global batch stepped K
      times unpipelined (P=1) and through P=2 / P=4 stage pipelines at
      fp32 must produce the same per-step loss trajectory — the stage
      schedule changes WHEN each microbatch's blocks run, never the math;
    - BUBBLE: the analytic fill/drain fraction (P-1)/(M+P-1) next to a
      MEASURED one from a two-point (M, 2M) timing fit at fixed
      microbatch size — t_tick = (T(2M) - T(M)) / M, bubble =
      (P-1)*t_tick / T(M) — because a single run cannot separate
      fill/drain idle from per-tick compute;
    - THROUGHPUT: pipelined clips/s/chip at the P-stage point
      (`pipeline_cps_per_chip`, perfdiff HIGHER_BETTER);
    - DONATION: graphcheck's donation pass over the pipelined step —
      declared donations must alias through the stage shard_map + scan;
    - plus one short Trainer.fit() under the pipelined layout so the
      steady-state-zero recompile contract holds there too
      (`train_recompiles == 0`), with the pipeline perf keys present.

    Smoke runs the whole lane on the forced-host CPU slice (honest
    parity, never device numbers — the multichip convention)."""
    import jax

    from pytorchvideo_accelerate_tpu.config import (
        CheckpointConfig, DataConfig, MeshConfig, ModelConfig,
        OptimConfig, ParallelConfig, TrainConfig,
    )
    from pytorchvideo_accelerate_tpu.parallel.pipeline import (
        analytic_bubble_frac,
    )
    from pytorchvideo_accelerate_tpu.utils.bench_setup import (
        build_step_setup, fetch_loss,
    )

    devices = jax.devices()
    n = len(devices)
    platform = devices[0].platform
    out: dict = {
        "n_devices": n,
        "platform": platform,
        "forced_host": bool(args.smoke),
        "smoke": bool(args.smoke),
    }
    if n < 2:
        out["error"] = f"pipeline lane needs >= 2 devices, have {n}"
        return out
    model_name = "videomae_t_pretrain"
    frames, crop = (4, 32) if args.smoke else (16, 224)
    # stage counts this slice supports: P must divide the trunk depth (4)
    # AND the device count must split as (data, P)
    stage_points = [p for p in (2, 4) if n % p == 0 and n // p >= 1]
    if not stage_points:
        # an odd slice (3/5/7 devices) fits no (data, P) split: refuse
        # loudly rather than report a vacuously-true parity verdict for
        # a sweep that never ran
        out["error"] = (f"pipeline lane needs a device count divisible "
                        f"by 2 or 4 for its (data, P) points, have {n}")
        return out
    # every layout must divide the SAME global batch: P=1 needs its n data
    # shards, each P-stage point needs data_shards x microbatches
    # = (n/p) x 2p = 2n — one fixed batch for the whole parity sweep
    GB = math.lcm(n, *(2 * p * (n // p) for p in stage_points))
    k_parity = 3
    k_timed = args.steps if not args.smoke else 3
    out.update(model=model_name, frames=frames, crop=crop, global_batch=GB,
               mixed_precision="fp32", stage_points=stage_points)

    def make_point(stages: int, micro: int = 0):
        mesh_cfg = (MeshConfig(data=n // stages, model=stages)
                    if stages > 1 else MeshConfig(data=n, model=1))
        return build_step_setup(
            model_name, frames=frames, crop=crop, batch_per_chip=1,
            num_classes=16, global_batch=GB, devices=list(devices),
            mesh_cfg=mesh_cfg, total_steps=k_parity + k_timed + 4,
            mixed_precision="fp32", overrides={"dropout_rate": 0.0},
            pipeline_stages=stages, pipeline_microbatches=micro,
        )

    def run_point(setup, label, timed=True):
        losses = []
        state = setup.state
        gbs = [setup.device_batch(0), setup.device_batch(1)]
        for i in range(k_parity):
            state, metrics = setup.step(state, gbs[i % 2], jax.random.key(i))
            losses.append(fetch_loss(metrics))
        cps = dt = None
        if timed:
            t0 = time.perf_counter()
            for i in range(k_timed):
                state, metrics = setup.step(state, gbs[i % 2],
                                            jax.random.key(100 + i))
            fetch_loss(metrics)
            dt = time.perf_counter() - t0
            cps = GB * k_timed / dt
        log(f"[pipeline] {label}: losses {[round(v, 4) for v in losses]}"
            + (f", {cps:.2f} clips/s ({cps / n:.2f}/chip)" if cps else ""))
        return losses, cps, dt

    ref_losses, ref_cps, _ = run_point(make_point(1), "P=1 baseline")
    parity_max_rel = 0.0
    cps_points = {"1": round(ref_cps / n, 3)}
    top_p = stage_points[-1] if stage_points else 1
    for p in stage_points:
        m = 2 * p  # fixed default schedule for the parity points
        setup = make_point(p, m)
        losses, cps, dt_m = run_point(setup, f"P={p} M={m}")
        cps_points[str(p)] = round(cps / n, 3)
        parity_max_rel = max(parity_max_rel, max(
            abs(a - b) / max(abs(b), 1e-9)
            for a, b in zip(losses, ref_losses)))
        if p == top_p:
            out["pipeline_cps_per_chip"] = round(cps / n, 3)
            out["pipeline_stages"] = p
            out["pipeline_microbatches"] = m
            out["pipeline_bubble_frac_analytic"] = round(
                analytic_bubble_frac(p, m), 4)
            # two-point (M, 2M) fit at FIXED microbatch size: double the
            # global batch with the microbatch count so each tick does
            # identical work, then the timing difference isolates t_tick
            setup2 = build_step_setup(
                model_name, frames=frames, crop=crop, batch_per_chip=1,
                num_classes=16, global_batch=2 * GB, devices=list(devices),
                mesh_cfg=MeshConfig(data=n // p, model=p),
                total_steps=k_timed + 4, mixed_precision="fp32",
                overrides={"dropout_rate": 0.0},
                pipeline_stages=p, pipeline_microbatches=2 * m,
            )
            _, _, dt_2m = run_point(setup2, f"P={p} M={2 * m} (fit point)")
            t_m, t_2m = dt_m / k_timed, dt_2m / k_timed
            t_tick = max((t_2m - t_m) / m, 0.0)
            measured = ((p - 1) * t_tick / t_m) if t_m > 0 else None
            out["pipeline_bubble_frac"] = (round(min(measured, 1.0), 4)
                                           if measured is not None else None)
            log(f"[pipeline] P={p}: bubble analytic "
                f"{out['pipeline_bubble_frac_analytic']} measured "
                f"{out['pipeline_bubble_frac']} "
                f"(t_tick {t_tick * 1e3:.1f} ms)")
            # donation through the stage scan, verified on the REAL lane
            # step (the parent's graphcheck gate runs single-device and
            # skips the pipelined target; this child has the mesh)
            try:
                from pytorchvideo_accelerate_tpu.analysis.gc_donation import (
                    check_donation,
                )

                gb0 = setup.device_batch(0)
                findings, summary = check_donation(
                    setup.step, (setup.state, gb0, jax.random.key(0)))
                out["pipeline_donation_verified"] = (
                    summary.get("declared_unaliased") == 0
                    and summary.get("undeclared_donatable") == 0
                    and summary.get("aliased", 0) > 0)
                log(f"[pipeline] donation: {summary}")
            except Exception as e:  # noqa: BLE001 - verdict, not a crash
                log(f"[pipeline] donation check failed: "
                    f"{type(e).__name__}: {e}")
                out["pipeline_donation_verified"] = None
    out["cps_per_chip_by_stages"] = cps_points
    out["parity_max_rel"] = round(parity_max_rel, 6)
    out["pipeline_parity"] = bool(parity_max_rel <= PIPELINE_PARITY_RTOL)

    # Trainer.fit() under the pipelined layout: the recompile contract and
    # the pipeline perf keys must hold end to end, guard composition incl.
    if stage_points:
        p = stage_points[0]
        tcfg = TrainConfig(
            mesh=MeshConfig(data=n // p, model=p),
            # flight records land under the lane's scratch dir, never "."
            checkpoint=CheckpointConfig(
                output_dir=_scratch_outdir("pipeline")),
            parallel=ParallelConfig(pipeline_stages=p),
            model=ModelConfig(name=model_name, num_classes=16,
                              dropout_rate=0.0),
            data=DataConfig(synthetic=True,
                            synthetic_num_videos=max(2 * (n // p) * p, 8),
                            num_frames=frames, crop_size=crop,
                            batch_size=GB // (n // p), num_workers=1,
                            limit_val_batches=1),
            optim=OptimConfig(num_epochs=1, lr=0.01),
            mixed_precision="fp32",
        )
        from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

        res = Trainer(tcfg).fit()
        out["train_recompiles"] = res.get("train_recompiles")
        out["trainer_bubble_frac_analytic"] = res.get(
            "pipeline_bubble_frac_analytic")
        out["trainer_pipeline_cps_per_chip"] = res.get(
            "pipeline_cps_per_chip")
    log(f"[pipeline] {json.dumps(out)}")
    return out


# --- parent orchestration ---------------------------------------------------

def bench_kbench(args) -> dict:
    """Kernel-microbench lane (`pva-tpu-kbench`, ops/kbench.py): each
    fused Pallas/folded kernel vs its XLA reference at the real
    slowfast/x3d hot-path shapes. Speedups are SAME-BACKEND ratios —
    honest on any host — but only a TPU run is a device claim; the
    record carries platform/device labels and raw ms stay here in
    bench_partial.json, never on the headline (the standing
    no-CPU-numbers-as-device-numbers rule)."""
    import jax

    from pytorchvideo_accelerate_tpu.ops.kbench import run_kbench

    res = run_kbench(smoke=args.smoke, log=log)
    res["n_chips"] = len(jax.devices())
    return res


# STREAM lane shapes: `cam` is the simulated camera resolution the client
# decodes at (frames are resized to `crop` for the model — real stream
# clients decode at source resolution); stride <= window/4 per the
# acceptance bar, so the per-advance H2D payload is <= 1/4 of a full
# window by construction
STREAM_SMOKE = dict(window=16, stride=2, crop=32, cam=96, sessions=4,
                    rounds=10, warmup=3, lg_rate_sps=3.0, lg_duration_s=3.0,
                    slo_label_p99_ms=2000.0,
                    trunk_window=32, trunk_crop=64, trunk_rounds=6,
                    trunk_warmup=2, trunk_eval=32)
STREAM_FULL = dict(window=16, stride=2, crop=64, cam=160, sessions=8,
                   rounds=40, warmup=5, lg_rate_sps=8.0, lg_duration_s=8.0,
                   slo_label_p99_ms=1000.0,
                   trunk_window=32, trunk_crop=64, trunk_rounds=20,
                   trunk_warmup=3, trunk_eval=64)
# incremental-vs-full parity tolerance: the two paths run the same ops on
# the same values through DIFFERENT executables, so fp32 fusion-order
# noise is the only allowed difference
STREAM_PARITY_TOL = 2e-4
# trunk-reuse quality gate (docs/SERVING.md § trunk-reuse): the banded
# trunk changes the math, so its speedup may only headline with an
# evaluate() top-1 accuracy delta vs the bidirectional baseline under
# this bound on the lane's fixed-seed synthetic eval — past it, the lane
# refuses the speedup (stream_trunk_refused) and headlines the delta
STREAM_TRUNK_TOP1_TOL = 0.15


def _write_stream_fixture(path: str, size: int, n_frames: int) -> None:
    """Tiny MJPG fixture the lane 'monitors': intra-only codec, so both
    the seeked window decode (full path) and the sequential read
    (streaming path) are frame-exact and byte-identical."""
    import cv2
    import numpy as np

    wr = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"MJPG"), 30.0,
                         (size, size))
    if not wr.isOpened():
        raise RuntimeError("cv2 VideoWriter (MJPG) unavailable")
    rng = np.random.default_rng(7)
    base = rng.integers(0, 255, (size, size, 3), np.uint8)
    for i in range(n_frames):
        wr.write(np.roll(base, 3 * i, axis=1))
    wr.release()


def bench_stream(args) -> dict:
    """The STREAM lane (streaming/; docs/SERVING.md § streaming):
    incremental streaming inference vs the one-shot full-recompute
    baseline, per emitted label, on a live-stream monitoring workload.

    What each path pays PER LABEL (the issue the subsystem exists for):
    the full-recompute baseline re-decodes the whole T-frame window from
    the stream (the reference one-shot serving shape: every request is an
    independent clip), re-preprocesses it, ships it host->device, and
    recomputes the whole backbone; the incremental path reads only the
    *s* new frames from the open capture, ships those, and advances the
    device-resident ring (token families skip re-embedding the cached
    window too). Both paths are measured end to end on this host and
    decomposed (decode/serve ms) in the record.

    Proof obligations baked into the record (asserted by --smoke):
    - PARITY: incremental advance logits match the full-clip recompute
      over the same window, every measured round;
    - zero post-warmup recompiles across session advances (the
      per-compiled-step jit cache sizes stay flat at 1);
    - `stream_incremental_speedup` >= 1.5 at stride <= T/4 with the
      per-advance H2D payload cut >= 4x (exact byte ratio);
    - `stream_p99_ms` from an open-loop STREAM load run (heavy-tail
      durations, per-session label-latency honesty) through the
      continuous-batching scheduler, zero non-shed failures;
    - trunk-reuse sub-lane (docs/SERVING.md § trunk-reuse): a causal-
      masked backbone served with trunk=full vs trunk=causal KV rings,
      `stream_trunk_speedup` >= 2x decode-inclusive per label, KV parity
      <= tol against the full-recompute-under-the-same-mask replay, flat
      caches, and the evaluate() top-1 delta gate vs the bidirectional
      baseline — past the gate the lane REFUSES the speedup and
      headlines the delta + refusal instead.

    A non-smoke run that fell back to CPU refuses to headline (suspect),
    per the standing bench rule; CPU smoke numbers are plumbing
    verdicts, never device claims."""
    import shutil
    import tempfile

    import cv2
    import jax
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.data.decode import decode_span
    from pytorchvideo_accelerate_tpu.fleet import Scheduler, StreamLoadGen
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.obs import memory as obs_memory
    from pytorchvideo_accelerate_tpu.serving.engine import InferenceEngine
    from pytorchvideo_accelerate_tpu.serving.stats import ServingStats
    from pytorchvideo_accelerate_tpu.streaming import StreamingEngine

    # arm the memory ledger BEFORE the engines are built: weight pins,
    # compiled-bucket caches, and session ring pools register as they
    # allocate, so the lane's hbm_* keys attribute real lane bytes (and
    # SessionTable admission consumes measured bytes where the backend
    # exposes memory_stats — the declared estimate elsewhere)
    obs_memory.configure()

    shape = STREAM_SMOKE if args.smoke else STREAM_FULL
    T, S = shape["window"], shape["stride"]
    crop, cam, n_sess = shape["crop"], shape["cam"], shape["sessions"]
    rounds, warmup = shape["rounds"], shape["warmup"]
    platform = jax.devices()[0].platform
    num_classes = 16

    cfg = ModelConfig(name="videomae_t", num_classes=num_classes,
                      dropout_rate=0.0)
    model = create_model(cfg, "fp32")
    variables = model.init(
        jax.random.key(0), np.zeros((1, T, crop, crop, 3), np.float32))
    engine = InferenceEngine(model, variables["params"],
                             variables.get("batch_stats", {}),
                             num_classes=num_classes,
                             max_batch_size=n_sess,
                             model_name="videomae_t")
    stream = StreamingEngine(engine, session_budget_mb=64.0,
                             session_ttl_s=120.0, name="bench")

    workdir = tempfile.mkdtemp(prefix="pva_stream_")
    try:
        n_frames = max(
            T + (rounds + warmup + 2) * S,
            shape["trunk_window"]
            + (shape["trunk_rounds"] + shape["trunk_warmup"] + 2) * S,
        ) + 8
        fixture = os.path.join(workdir, "stream.avi")
        _write_stream_fixture(fixture, cam, n_frames)
        # pre-compile every (op, bucket) stream step for the lane's
        # geometry + stride up front: a compile must never ride a
        # measured round OR a loadgen arrival (the first lone session at
        # a fresh bucket would otherwise stall the flush thread)
        n_warm = stream.warmup_stream(T, crop, crop, 3, S)
        log(f"[stream] warmed {n_warm} compiled stream steps over "
            f"buckets {engine.buckets}")

        def prep(frames_u8, size=crop):
            # the real client-side preprocess: camera-res -> model-res
            # resize + [0,1] float staging, per frame
            out = np.empty((frames_u8.shape[0], size, size, 3), np.float32)
            for i, f in enumerate(frames_u8):
                out[i] = cv2.resize(f, (size, size),
                                    interpolation=cv2.INTER_AREA)
            return out / 255.0

        # per-session streaming clients: one OPEN capture each (sequential
        # reads — a live stream never re-decodes delivered frames), offset
        # start positions so windows differ across sessions
        sids = [f"cam{i}" for i in range(n_sess)]
        caps, heads, windows = {}, {}, {}
        for i, sid in enumerate(sids):
            caps[sid] = cv2.VideoCapture(fixture)
            start = i  # phase offset
            if start:
                caps[sid].set(cv2.CAP_PROP_POS_FRAMES, start)
            frames = []
            for _ in range(T):
                ok, f = caps[sid].read()
                if not ok:
                    raise RuntimeError(
                        f"fixture unreadable at session setup ({sid})")
                frames.append(f[:, :, ::-1])
            heads[sid] = start + T  # index one past the newest frame
            windows[sid] = prep(np.stack(frames))

        # establish every session + warm the full-path bucket BEFORE
        # timing: compiles must never ride a measured round
        est = stream.advance_batch(
            [{"sid": sid, "window": windows[sid], "stride": S}
             for sid in sids])
        full0 = stream.full_recompute(
            np.stack([windows[s] for s in sids]))
        parity_max = float(max(
            np.max(np.abs(np.asarray(est[i]) - full0[i]))
            for i in range(n_sess)))

        def advance_round():
            """One label per session, both paths; returns per-path ms +
            parity delta."""
            t0 = time.perf_counter()
            items = []
            for sid in sids:
                fr = []
                for _ in range(S):
                    ok, f = caps[sid].read()
                    if not ok:
                        raise RuntimeError("fixture exhausted")
                    fr.append(f[:, :, ::-1])
                new = prep(np.stack(fr))
                # the resendable window: client-maintained, part of the
                # streaming client's honest per-label work
                windows[sid] = np.concatenate([windows[sid][S:], new], 0)
                heads[sid] += S
                items.append({"sid": sid, "frames": new})
            t_dec_inc = time.perf_counter() - t0
            out = stream.advance_batch(items)
            t_inc = time.perf_counter() - t0

            t0 = time.perf_counter()
            decoded = {}
            for sid in sids:
                # the one-shot baseline decodes its whole window per
                # label (seeked span decode — the stateless-request shape)
                u8 = decode_span(fixture, (heads[sid] - T) / 30.0,
                                 heads[sid] / 30.0, max_frames=T)
                decoded[sid] = prep(u8)
            t_dec_full = time.perf_counter() - t0
            stacked = np.stack([decoded[s] for s in sids])
            full = stream.full_recompute(stacked)
            t_full = time.perf_counter() - t0
            # the seeked decode must reproduce the sequential stream
            # exactly (intra-only codec) — this doubles as the stream-
            # position bookkeeping check
            for i, sid in enumerate(sids):
                if not np.array_equal(decoded[sid], windows[sid]):
                    raise RuntimeError(
                        f"seeked window decode diverged from the "
                        f"sequential stream for {sid} at head "
                        f"{heads[sid]} (position bookkeeping broken?)")
            delta = float(max(
                np.max(np.abs(np.asarray(out[i]) - full[i]))
                for i in range(n_sess)))
            return (t_inc * 1e3, t_full * 1e3, t_dec_inc * 1e3,
                    t_dec_full * 1e3, delta)

        for _ in range(warmup):
            advance_round()
        cache_before = stream.compiled_stream_cache_sizes()
        keys_before = set(stream.compiled_stream_keys())

        inc_ms, full_ms, dec_inc_ms, dec_full_ms = [], [], [], []
        for _ in range(rounds):
            ti, tf, di, df, delta = advance_round()
            inc_ms.append(ti)
            full_ms.append(tf)
            dec_inc_ms.append(di)
            dec_full_ms.append(df)
            parity_max = max(parity_max, delta)

        cache_after = stream.compiled_stream_cache_sizes()
        recompiles = sum(
            (cache_after.get(k) or 1) - (cache_before.get(k) or 1)
            for k in cache_before) + len(
                set(stream.compiled_stream_keys()) - keys_before)
        for cap in caps.values():
            cap.release()

        med_inc = statistics.median(inc_ms)
        med_full = statistics.median(full_ms)
        geom = stream.geom_key(T, crop, crop, 3, engine.input_dtype)
        h2d_frac = (stream.advance_h2d_bytes(geom, S)
                    / stream.full_h2d_bytes(geom))

        # open-loop STREAM load through the continuous-batching scheduler
        # (heavy-tail durations, windows attached = the re-establish-
        # anywhere contract), label p99 over completions
        stats = ServingStats(window=2048)
        sched = Scheduler(stream, max_queue=256, stats=stats,
                          realtime_deadline_ms=shape["slo_label_p99_ms"] * 4,
                          batch_max_wait_ms=2.0, name="stream-bench")
        try:
            gen = StreamLoadGen(
                sched.submit, stream_rate_sps=shape["lg_rate_sps"],
                duration_s=shape["lg_duration_s"], window=T, stride=S,
                frame_shape=(crop, crop, 3), advance_interval_s=S / 30.0,
                seed=0, mean_advances=6.0, max_advances=24)
            lg = gen.run()
        finally:
            sched.close()

        # ---- trunk-reuse sub-lane (docs/SERVING.md § trunk-reuse) ----
        # The KV-ring question, at a shape where the trunk dominates the
        # per-label cost (the main lane's tiny geometry is dispatch-bound
        # on the smoke host — a ratio there measures launch overhead, not
        # trunk compute): ONE causal-masked backbone (the shape a
        # `--model.attn_mask causal` finetune produces), served twice
        # over one engine. trunk=full re-runs the masked trunk over the
        # whole cached token window per advance; trunk=causal advances
        # the device-resident KV ring with only the new tubelets'
        # queries. Same decode, same H2D, same embed — the ratio is the
        # trunk-reuse win and nothing else.
        Tt, cropt = shape["trunk_window"], shape["trunk_crop"]
        tr_rounds, tr_warm = shape["trunk_rounds"], shape["trunk_warmup"]
        cfg_m = ModelConfig(name="videomae_t", num_classes=num_classes,
                            dropout_rate=0.0, attn_mask="causal")
        model_m = create_model(cfg_m, "fp32")
        vars_m = model_m.init(
            jax.random.key(0),
            np.zeros((1, Tt, cropt, cropt, 3), np.float32))
        eng_m = InferenceEngine(model_m, vars_m["params"],
                                vars_m.get("batch_stats", {}),
                                num_classes=num_classes,
                                max_batch_size=n_sess,
                                model_name="videomae_t")
        tr_full = StreamingEngine(eng_m, session_budget_mb=96.0,
                                  session_ttl_s=120.0,
                                  name="bench-trunk-full", trunk="full")
        tr_kv = StreamingEngine(eng_m, session_budget_mb=96.0,
                                session_ttl_s=120.0,
                                name="bench-trunk-kv", trunk="causal")
        n_tw = tr_full.warmup_stream(Tt, cropt, cropt, 3, S)
        n_tw += tr_kv.warmup_stream(Tt, cropt, cropt, 3, S)
        log(f"[stream] trunk sub-lane: warmed {n_tw} compiled steps at "
            f"window={Tt} crop={cropt}")

        tcaps, twin, thist = {}, {}, {}
        for i, sid in enumerate(sids):
            tcaps[sid] = cv2.VideoCapture(fixture)
            if i:
                tcaps[sid].set(cv2.CAP_PROP_POS_FRAMES, i)
            frames = []
            for _ in range(Tt):
                ok, f = tcaps[sid].read()
                if not ok:
                    raise RuntimeError("fixture exhausted at trunk "
                                       "sub-lane establish")
                frames.append(f[:, :, ::-1])
            twin[sid] = prep(np.stack(frames), cropt)
            thist[sid] = twin[sid]
        est_f = tr_full.advance_batch(
            [{"sid": s, "window": twin[s], "stride": S} for s in sids])
        est_k = tr_kv.advance_batch(
            [{"sid": s, "window": twin[s], "stride": S} for s in sids])
        # at establish the two trunks are the same banded function over
        # the same positions — a free cross-executable parity anchor
        trunk_par = float(max(
            np.max(np.abs(np.asarray(est_k[i]) - np.asarray(est_f[i])))
            for i in range(n_sess)))

        def trunk_round():
            """One label per session through BOTH trunks; decode once
            (both paths ship the same s new frames) and count it in each
            path's per-label cost — decode-inclusive end to end."""
            t0 = time.perf_counter()
            new = {}
            for sid in sids:
                fr = []
                for _ in range(S):
                    ok, f = tcaps[sid].read()
                    if not ok:
                        raise RuntimeError("fixture exhausted at trunk "
                                           "sub-lane rounds")
                    fr.append(f[:, :, ::-1])
                new[sid] = prep(np.stack(fr), cropt)
                twin[sid] = np.concatenate([twin[sid][S:], new[sid]], 0)
                thist[sid] = np.concatenate([thist[sid], new[sid]], 0)
            t_dec = time.perf_counter() - t0
            t0 = time.perf_counter()
            tr_full.advance_batch(
                [{"sid": s, "frames": new[s]} for s in sids])
            t_f = time.perf_counter() - t0
            t0 = time.perf_counter()
            out_k = tr_kv.advance_batch(
                [{"sid": s, "frames": new[s]} for s in sids])
            t_k = time.perf_counter() - t0
            return (t_dec + t_f) * 1e3, (t_dec + t_k) * 1e3, out_k

        for _ in range(tr_warm):
            trunk_round()
        tcaches = [(se, se.compiled_stream_cache_sizes(),
                    set(se.compiled_stream_keys()))
                   for se in (tr_full, tr_kv)]
        cost_f, cost_k, last_k = [], [], None
        for _ in range(tr_rounds):
            cf, ck, last_k = trunk_round()
            cost_f.append(cf)
            cost_k.append(ck)
        trunk_rec = sum(
            (se.compiled_stream_cache_sizes().get(k) or 1)
            - (before.get(k) or 1)
            for se, before, _ in tcaches for k in before) + sum(
            len(set(se.compiled_stream_keys()) - keys)
            for se, _, keys in tcaches)
        # the KV-trunk parity oracle is the full recompute UNDER THE
        # SAME MASK over the whole per-session history — cached K/V
        # legitimately attended context that has since left the ring, so
        # the trailing-window one-shot is not equivalent (engine.py
        # full_recompute_history)
        replay = tr_kv.full_recompute_history(
            np.stack([thist[s] for s in sids]), Tt)
        trunk_par = max(trunk_par, float(max(
            np.max(np.abs(np.asarray(last_k[i]) - replay[i]))
            for i in range(n_sess))))
        for cap in tcaps.values():
            cap.release()

        # evaluate() quality gate: top-1 accuracy DELTA vs the
        # bidirectional baseline on a fixed-seed synthetic eval, served
        # path included (establish + KV advances). The baseline is the
        # SAME weights with the mask off — the main lane's engine (same
        # init key; the mask adds no params), i.e. exactly what the
        # backbone answered before the banded-trunk finetune recipe.
        rng = np.random.default_rng(16)
        n_eval = shape["trunk_eval"]
        clips = rng.random((n_eval, Tt, cropt, cropt, 3)).astype(np.float32)
        steps = rng.random((n_eval, 2, S, cropt, cropt, 3)).astype(np.float32)
        labels = rng.integers(0, num_classes, n_eval)
        hits_base, hits_kv = 0, 0
        for lo in range(0, n_eval, n_sess):
            idx = list(range(lo, min(lo + n_sess, n_eval)))
            evs = [f"ev{i}" for i in idx]
            tr_kv.advance_batch(
                [{"sid": s, "window": clips[i], "stride": S}
                 for s, i in zip(evs, idx)])
            win = {i: clips[i] for i in idx}
            out_k = None
            for a in range(2):
                out_k = tr_kv.advance_batch(
                    [{"sid": s, "frames": steps[i, a]}
                     for s, i in zip(evs, idx)])
                for i in idx:
                    win[i] = np.concatenate([win[i][S:], steps[i, a]], 0)
            for s in evs:
                tr_kv.end_session(s)
            base = engine.predict(
                {"video": np.stack([win[i] for i in idx])})
            for j, i in enumerate(idx):
                hits_kv += int(np.argmax(np.asarray(out_k[j]))
                               == labels[i])
                hits_base += int(np.argmax(np.asarray(base[j]))
                                 == labels[i])
        trunk_delta = round(abs(hits_base - hits_kv) / n_eval, 4)

        med_tf = statistics.median(cost_f)
        med_tk = statistics.median(cost_k)
        out = {
            "stream_incremental_speedup": round(med_full / med_inc, 3),
            "stream_h2d_bytes_frac": round(h2d_frac, 4),
            "stream_p99_ms": lg["label_p99_ms"],
            "stream_parity_max_abs": round(parity_max, 6),
            "stream_parity": bool(parity_max <= STREAM_PARITY_TOL),
            "stream_recompiles": int(recompiles),
            # trunk-reuse sub-lane verdicts (docs/SERVING.md
            # § trunk-reuse): KV-ring advance vs the full-recompute-
            # under-the-same-mask replay, flat caches, and the
            # evaluate() top-1 delta vs the bidirectional baseline
            "stream_trunk_parity_max_abs": round(trunk_par, 6),
            "stream_trunk_parity": bool(trunk_par <= STREAM_PARITY_TOL),
            "stream_trunk_recompiles": int(trunk_rec),
            "stream_trunk_top1_delta": trunk_delta,
            "stream_trunk_top1_tol": STREAM_TRUNK_TOP1_TOL,
            "trunk_window": Tt,
            "trunk_crop": cropt,
            "trunk_eval_clips": int(n_eval),
            "label_ms_trunk_full": round(med_tf, 3),
            "label_ms_trunk_kv": round(med_tk, 3),
            # memory-ledger triple: the streaming ring pools + engine
            # weight pins registered above make this lane's attribution
            # meaningful on any host (estimate-labeled off device)
            **hbm_headline(),
            "stream_sessions": n_sess,
            "window": T,
            "stride": S,
            "label_ms_full": round(med_full, 3),
            "label_ms_incremental": round(med_inc, 3),
            "decode_ms_full": round(statistics.median(dec_full_ms), 3),
            "decode_ms_incremental": round(statistics.median(dec_inc_ms), 3),
            "loadgen": {k: lg[k] for k in
                        ("streams", "advances_offered", "completed",
                         "failed", "shed", "label_p50_ms", "label_p99_ms",
                         "max_arrival_lag_ms", "open_loop_ok")},
            "stream_failed": int(lg["failed"]),
            "open_loop_ok": lg["open_loop_ok"],
            "slo_label_p99_ms": shape["slo_label_p99_ms"],
            "platform": platform,
            "smoke": bool(args.smoke),
        }
        # the refusal half of the quality gate: a masked trunk whose
        # top-1 drifted past the gate headlines the delta and the
        # refusal INSTEAD of the speedup — a faster wrong answer is not
        # a win (docs/SERVING.md § trunk-reuse)
        if trunk_delta <= STREAM_TRUNK_TOP1_TOL:
            out["stream_trunk_speedup"] = round(med_tf / med_tk, 3)
        else:
            out["stream_trunk_refused"] = (
                f"top-1 delta {trunk_delta} vs the bidirectional "
                f"baseline breaches the {STREAM_TRUNK_TOP1_TOL} quality "
                "gate; speedup refused — finetune with the matching "
                "--model.attn_mask (docs/SERVING.md § trunk-reuse)")
        log(f"[stream] {json.dumps(out)}")
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


# exit code of a non-smoke child that JAX gave no TPU: the parent ends
# the run on it, without a headline
NO_TPU_EXIT = 3


def _model_timeout(args):
    """0 = no limit (matches the historical --per_model_timeout contract)."""
    return args.per_model_timeout if args.per_model_timeout > 0 else None


def run_child(target: str, args, smoke: bool, timeout) -> dict:
    """One bench in a disposable subprocess (own process group; killed
    wholesale on timeout so a stuck backend can't hang the round);
    `timeout=None` = no limit."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", target,
           "--steps", str(args.steps), "--warmup", str(args.warmup),
           "--alpha", str(args.alpha), "--inputs", args.inputs]
    if smoke:
        cmd.append("--smoke")
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=sys.stderr,
                         text=True, start_new_session=True)
    try:
        out, _ = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except OSError:
            pass
        p.wait()
        log(f"[{target}] child killed after {timeout}s")
        return {"error": f"child timeout after {timeout}s", "smoke": smoke}
    if p.returncode != 0:
        return {"error": f"child exited {p.returncode}", "smoke": smoke,
                "returncode": p.returncode}
    from pytorchvideo_accelerate_tpu.utils.forcehost import last_json_line

    res = last_json_line(out)
    return res if res is not None else {"error": "no JSON from child",
                                        "smoke": smoke}


def child_main(args) -> None:
    """--child entry: run ONE bench and print its JSON as the last line."""
    if args.child == "__multichip__" and args.smoke:
        # forced-host slice: must land in XLA_FLAGS before the first device
        # touch (jax is imported, but the backend only latches the flag at
        # client init — the dryrun_multichip pattern)
        from pytorchvideo_accelerate_tpu.utils.forcehost import forced_host_env

        os.environ["XLA_FLAGS"] = forced_host_env(
            MULTICHIP_FORCED_DEVICES)["XLA_FLAGS"]
    if args.child == "__pipeline__" and args.smoke:
        # forced-host slice for the PIPELINE lane (same latching rule)
        from pytorchvideo_accelerate_tpu.utils.forcehost import forced_host_env

        os.environ["XLA_FLAGS"] = forced_host_env(
            PIPELINE_FORCED_DEVICES)["XLA_FLAGS"]
    if args.child == "__fleet__" and args.smoke and FLEET_SMOKE["devices"]:
        # SERVE_FLEET multi-device CI: each replica gets its own forced
        # CPU device, so routing/swap run against genuinely disjoint
        # meshes (utils/forcehost.py, same latching rule as multichip)
        from pytorchvideo_accelerate_tpu.utils.forcehost import forced_host_env

        os.environ["XLA_FLAGS"] = forced_host_env(
            FLEET_SMOKE["devices"])["XLA_FLAGS"]
    jax = _setup_jax(args.smoke, child=args.child)
    if args.smoke:
        args.steps, args.warmup = min(args.steps, 3), 1
    else:
        device = device_summary()
        log(f"[{args.child}] device: {json.dumps(device)}")
        if device["platform"] != "tpu":
            # no CPU stand-in for a device measurement: fail the run
            log(f"[{args.child}] JAX found no TPU; a non-smoke bench child "
                "measures the chip or nothing")
            sys.exit(NO_TPU_EXIT)

    if args.child == "__trainer__":
        res = bench_trainer(args)
    elif args.child == "__multichip__":
        res = bench_multichip(args)
    elif args.child == "__pipeline__":
        res = bench_pipeline(args)
    elif args.child == "__fleet__":
        res = bench_fleet(args)
    elif args.child == "__fleet_auto__":
        res = bench_fleet_auto(args)
    elif args.child == "__kbench__":
        res = bench_kbench(args)
    elif args.child == "__stream__":
        res = bench_stream(args)
    else:
        devices = jax.devices()
        n_chips = len(devices)
        peak = peak_tflops(devices[0])
        log(f"devices: {n_chips} x {devices[0].device_kind} "
            f"({devices[0].platform}), bf16 peak "
            f"{f'{peak:.0f} TFLOP/s/chip' if peak else 'unknown'}")
        res = bench_model(args.child, WORKLOADS[args.child], args, n_chips)
        res["n_chips"] = n_chips
    print("\n" + json.dumps(res))
    sys.stdout.flush()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="default",
                    help="comma list of " + ",".join(WORKLOADS)
                         + "; 'default' = the BASELINE four ("
                         + ",".join(DEFAULT_MODELS) + "); 'all' = every "
                         "workload incl. the r5 zoo additions")
    ap.add_argument("--alpha", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--inputs", choices=("u8", "f32"), default="u8",
                    help="synthetic batch staging: raw uint8 + in-graph "
                         "normalize (the host_cast=u8 production path, 4x "
                         "less transfer) or float32 (r1-r4 staging)")
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--trainer", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="also run Trainer.fit() on synthetic data and report "
                         "its throughput vs the raw step (hot-loop overhead)")
    ap.add_argument("--multichip", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="MULTICHIP scaling lane: 1->N clips/s/chip through "
                         "the 2-D (data, model) train mesh, with loss-parity "
                         "and mesh-reshape checkpoint probes; forced-host "
                         "CPU devices in smoke mode (never device numbers)")
    ap.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="PIPELINE lane: pipeline-parallel VideoMAE "
                         "pretrain — P=1 vs P=2/4 fp32 loss-parity at a "
                         "fixed global batch, analytic + measured "
                         "fill/drain bubble fraction, pipelined clips/s/"
                         "chip, donation through the stage scan; forced-"
                         "host CPU devices in smoke mode (--no-pipeline "
                         "skips)")
    ap.add_argument("--data", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="host input-pipeline microbench (decode vs cache vs "
                         "loader clips/sec; CPU-real numbers regardless of "
                         "device-timing trustworthiness); --no-data skips")
    ap.add_argument("--dataplane", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="DATA_PLANE lane: local loader vs N remote decode-"
                         "worker processes on the same source/seed; "
                         "headlines dataplane_cps / "
                         "dataplane_input_wait_frac / dataplane_workers, "
                         "parity-gated byte-identical (--no-dataplane "
                         "skips)")
    ap.add_argument("--serve-smoke", dest="serve_smoke",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="serving-lane smoke: engine + micro-batcher under "
                         "a synthetic client; p50/p99 request latency and "
                         "batch-fill ratio on the headline line "
                         "(--no-serve-smoke skips)")
    ap.add_argument("--fleet", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="SERVE_FLEET lane: >=2 engine replicas behind the "
                         "fleet router under open-loop load with a "
                         "mid-load checkpoint hot-swap; headlines "
                         "serve_rps / serve_p99_ms_under_load / "
                         "swap_blackout_ms / fleet_shed_frac "
                         "(--no-fleet skips)")
    ap.add_argument("--fleet-auto", dest="fleet_auto",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="FLEET_AUTO lane: the fleet-intelligence control "
                         "loops — SLO-driven autoscaling under a traffic "
                         "step, session-safe scale-down, multi-model "
                         "budget shed, canary auto-rollback; headlines "
                         "autoscale_converge_s / fleet_scaledown_shed_frac "
                         "/ canary_rollback / fleet_models_served "
                         "(--no-fleet-auto skips)")
    ap.add_argument("--stream", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="STREAM lane: incremental streaming inference "
                         "(device-resident session rings) vs the one-shot "
                         "full-recompute baseline per emitted label; "
                         "headlines stream_incremental_speedup / "
                         "stream_h2d_bytes_frac / stream_p99_ms, "
                         "parity-gated (--no-stream skips)")
    ap.add_argument("--kbench", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="kernel-microbench lane (pva-tpu-kbench): fused "
                         "Pallas/folded kernels vs their XLA references "
                         "at real slowfast/x3d shapes; per-kernel "
                         "same-backend speedup keys on the headline, "
                         "parity gated (--no-kbench skips)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-safe shapes for harness verification")
    ap.add_argument("--per_model_timeout", type=int, default=900,
                    help="seconds before a model's child bench is killed "
                         "(0 = no limit)")
    ap.add_argument("--child", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        child_main(args)
        return

    # The parent must NEVER touch devices: the chip belongs to one process
    # at a time, and a parent holding it would starve every child. All
    # device work happens in children; the parent pins itself to CPU.
    _setup_jax(smoke=True)

    if args.smoke:
        # bench-contract guarantee (fails fast, before any child spends
        # minutes): the package tree must be pva-tpu-lint clean — the
        # static half of the hazard contract whose runtime half is the
        # train_recompiles == 0 assert below. docs/STATIC_ANALYSIS.md.
        from pytorchvideo_accelerate_tpu.analysis import run_lint

        lint_findings = run_lint(
            [os.path.join(HERE, "pytorchvideo_accelerate_tpu")])
        assert not lint_findings, (
            "bench --smoke requires a lint-clean tree; pva-tpu-lint found:\n"
            + "\n".join(f.format() for f in lint_findings[:20]))
        log(f"[lint] pva-tpu-lint clean ({len(lint_findings)} findings)")
        # the dynamic half of the same contract: one short pva-tpu-tsan
        # stress pass (lockset races + lock-order cycles over the threaded
        # layers) must come back clean before any child spends minutes.
        # Runs in the parent (CPU-pinned, like the serving lane).
        from pytorchvideo_accelerate_tpu.analysis.tsan_report import (
            finding_count,
            format_report,
            publish,
            run_stress,
        )

        tsan_report = run_stress(smoke=True, log=log)
        publish(tsan_report)
        tsan_findings = finding_count(tsan_report)
        log(f"[tsan] pva-tpu-tsan: {tsan_findings} finding(s) "
            f"in {tsan_report['elapsed_s']}s")
        if tsan_findings:
            log(format_report(tsan_report))
        assert tsan_findings == 0, (
            "bench --smoke requires a tsan-clean stress pass; pva-tpu-tsan "
            f"found {tsan_findings} race/lock-cycle finding(s) (report "
            "logged above; see docs/STATIC_ANALYSIS.md)")
        # the resilience leg of the same contract (docs/RELIABILITY.md):
        # the pva-tpu-chaos seeded fault-injection scenario — decode
        # faults, a mid-write checkpoint failure, a tracker outage, a
        # mid-epoch SIGTERM, serving overload — must RECOVER everywhere.
        # Gated here, before any child spends minutes (the lint/tsan
        # pattern). Runs in the parent: CPU-pinned, like the tsan pass.
        from pytorchvideo_accelerate_tpu.reliability.chaos import (
            finding_count as chaos_finding_count,
            format_report as chaos_format,
            publish as chaos_publish,
            run_scenario as run_chaos,
        )

        chaos_report = run_chaos(smoke=True, log=log)
        chaos_publish(chaos_report)
        chaos_findings = chaos_finding_count(chaos_report)
        log(f"[chaos] pva-tpu-chaos: {chaos_findings} finding(s) "
            f"in {chaos_report['elapsed_s']}s")
        if chaos_findings:
            log(chaos_format(chaos_report))
        assert chaos_findings == 0, (
            "bench --smoke requires a chaos-clean scenario; pva-tpu-chaos "
            f"found {chaos_findings} unrecovered fault(s) (report logged "
            "above; see docs/RELIABILITY.md)")
        # the compiled-graph leg of the same contract (docs/
        # STATIC_ANALYSIS.md § graphcheck): the four jaxpr/HLO passes —
        # donation aliasing, dtype policy, sharding propagation,
        # analytic-vs-costmodel FLOPs — over the REAL train/eval/serve
        # steps must come back clean, and the train step must be
        # VERIFIED donated (every declared donation aliased, zero
        # donatable state leaves undeclared). Gated here, before any
        # child spends minutes (the lint/tsan/chaos pattern).
        from pytorchvideo_accelerate_tpu.analysis.graphcheck import (
            finding_count as graphcheck_finding_count,
            format_report as graphcheck_format,
            run_graphcheck,
        )

        graphcheck_report = run_graphcheck(smoke=True, log=log)
        graphcheck_findings = graphcheck_finding_count(graphcheck_report)
        log(f"[graphcheck] pva-tpu-graphcheck: {graphcheck_findings} "
            f"finding(s) in {graphcheck_report['elapsed_s']}s "
            f"(donation_verified="
            f"{graphcheck_report['donation_verified']})")
        if graphcheck_findings:
            log(graphcheck_format(graphcheck_report))
        assert graphcheck_findings == 0, (
            "bench --smoke requires a graphcheck-clean tree; "
            f"pva-tpu-graphcheck found {graphcheck_findings} finding(s) "
            "(report logged above; see docs/STATIC_ANALYSIS.md)")
        assert graphcheck_report["donation_verified"] is True, (
            "bench --smoke requires a VERIFIED-donated train step: the "
            "donation pass reports declared-but-unaliased or "
            "undeclared-donatable state leaves (see "
            "docs/STATIC_ANALYSIS.md § donation)")
        # collective-schedule divergence gate (docs/STATIC_ANALYSIS.md
        # § spmdcheck): the static pass over the hot modules — collectives
        # under host-divergent predicates, asymmetric branch arms, skip
        # paths past a later collective, checkpoint-write discipline, and
        # the collective_section coverage audit — must come back clean
        # before any child spends minutes. The multi-host pod runtime's
        # precondition rides the same lint/tsan/chaos/graphcheck pattern.
        from pytorchvideo_accelerate_tpu.analysis.spmdcheck import (
            finding_count as spmdcheck_finding_count,
            format_report as spmdcheck_format,
            run_spmdcheck,
        )

        spmdcheck_report = run_spmdcheck(log=log)
        spmdcheck_findings = spmdcheck_finding_count(spmdcheck_report)
        log(f"[spmdcheck] pva-tpu-spmdcheck: {spmdcheck_findings} "
            f"finding(s) in {spmdcheck_report['elapsed_s']}s")
        if spmdcheck_findings:
            log(spmdcheck_format(spmdcheck_report))
        assert spmdcheck_findings == 0, (
            "bench --smoke requires an spmdcheck-clean tree; "
            f"pva-tpu-spmdcheck found {spmdcheck_findings} finding(s) "
            "(report logged above; see docs/STATIC_ANALYSIS.md "
            "§ spmdcheck)")

    user_smoke = args.smoke
    partial_path = os.path.join(HERE, "bench_partial.json")
    results: dict = {}
    extras: dict = {}
    if user_smoke:
        extras["tsan_findings"] = tsan_findings
        extras["chaos_findings"] = chaos_findings
        extras["graphcheck_findings"] = graphcheck_findings
        extras["spmdcheck_findings"] = spmdcheck_findings

    def flush_partial():
        try:
            with open(partial_path, "w") as f:
                json.dump({"results": results, **extras}, f, indent=1)
        except OSError:
            pass

    def lane_child(target):
        """A lane's child, in the run's mode. A lane that fails for its
        own reasons headlines its `<lane>_error`; one that found no TPU
        ends the run (the no-CPU-fallback rule)."""
        res = run_child(target, args, user_smoke, _model_timeout(args))
        if res.get("returncode") == NO_TPU_EXIT:
            extras["error"] = f"{target}: no TPU"
            flush_partial()
            sys.exit(f"[{target}] child found no TPU; no headline")
        return res

    if args.models == "default":
        names = list(DEFAULT_MODELS)
    elif args.models == "all":
        names = list(WORKLOADS)
    else:
        names = args.models.split(",")

    for name in names:
        # smoke children are capped tighter (tiny shapes) but still honor
        # the user's limit, including 0 = no limit
        timeout = (_model_timeout(args) if not user_smoke
                   else (min(args.per_model_timeout, 600)
                         if args.per_model_timeout > 0 else None))
        res = run_child(name, args, user_smoke, timeout)
        results[name] = res
        flush_partial()
        if not user_smoke and ("error" in res
                               or res.get("platform") != "tpu"):
            # a device model bench that failed, or ran anywhere but on a
            # TPU, is recorded and ends the run: no smoke stand-in, no
            # retry ladder, no headline
            sys.exit(f"[{name}] device bench failed or missed the TPU "
                     f"({res.get('error') or res.get('platform')}); see "
                     f"{partial_path}")

    if args.trainer:
        tr = lane_child("__trainer__")
        if "trainer_cps_chip" in tr:
            extras["trainer_cps_chip"] = round(tr["trainer_cps_chip"], 3)
            if tr.get("input_wait_frac") is not None:
                # time fit()'s step loop spent blocked on input: the proof
                # (or refutation) that device prefetch overlaps H2D with
                # compute — << 1 is the healthy reading
                extras["trainer_input_wait_frac"] = round(
                    tr["input_wait_frac"], 4)
            if tr.get("mfu") is not None:
                extras["trainer_mfu"] = round(tr["mfu"], 4)
            if tr.get("mfu_analytic") is not None:
                # the analytic-counter MFU + its provenance labels — the
                # headline keys the --smoke gate asserts non-null (the
                # "honest MFU" leg of ROADMAP item 1). The peak-source
                # label rides too: a "measured" denominator is a matmul-
                # rate proxy, and a round must never read as a datasheet
                # fraction (utils/hw.resolve_peak's contract)
                extras["mfu_analytic"] = round(tr["mfu_analytic"], 4)
                if tr.get("mfu_source"):
                    extras["mfu_source"] = tr["mfu_source"]
                if tr.get("mfu_peak_source"):
                    extras["mfu_peak_source"] = tr["mfu_peak_source"]
            # registry-sourced step-time breakdown (obs/): per-step wall
            # time, input-blocked fraction, and H2D copy time — the
            # telemetry-spine successors of the ad-hoc perf dict
            for key in ("obs_step_s", "obs_input_wait_frac", "obs_h2d_s"):
                if tr.get(key) is not None:
                    extras[key] = round(tr[key], 6)
            if "train_recompiles" in tr:
                # steady-state recompiles seen by fit()'s hot loop —
                # asserted zero in --smoke (the recompile-hazard
                # contract); None = the jit cache probe is unavailable
                # on this jax (reported as unknown, never a lying 0)
                r = tr["train_recompiles"]
                extras["train_recompiles"] = None if r is None else int(r)
            for key in ("guard_rollbacks", "quarantined_clips"):
                # self-healing-guard verdicts (reliability/guard.py) —
                # asserted 0 in --smoke: a clean synthetic run that rolls
                # back or quarantines is a guard false positive
                if tr.get(key) is not None:
                    extras[key] = int(tr[key])
            # memory-ledger triple (pva-tpu-hbm): the trainer lane is the
            # flagship device process, so ITS ledger read headlines; the
            # provenance label always rides with the bytes — an
            # "estimate" peak is a CPU-host attribution sum, never a
            # device claim (perfdiff refuses suspect rounds wholesale)
            for key in ("hbm_peak_bytes", "hbm_attributed_frac",
                        "hbm_source"):
                if tr.get(key) is not None:
                    extras[key] = tr[key]
            raw = (results.get("slowfast_r50") or {}).get(
                "clips_per_sec_per_chip")
            # only a same-mode comparison is meaningful
            if raw and (results["slowfast_r50"].get("smoke")
                        == tr.get("smoke")):
                extras["trainer_vs_rawstep"] = round(
                    tr["trainer_cps_chip"] / raw, 3)
        else:
            extras["trainer_error"] = tr.get("error", "unknown")
        flush_partial()

    if args.multichip:
        # MULTICHIP lane: same child-isolation rules as the model benches
        # (a stuck 8-way compile loses the lane, not the round). A smoke
        # round runs it forced-host (honest CPU parity, never headlined
        # as device numbers).
        mc = lane_child("__multichip__")
        extras["multichip"] = mc  # full record -> bench_partial.json
        if "error" in mc:
            extras["multichip_error"] = str(mc["error"])[:120]
        else:
            # numerics verdicts always ride the headline
            extras["mesh_parity"] = mc.get("mesh_parity")
            if "mesh_ckpt_portable" in mc:
                extras["mesh_ckpt_portable"] = mc["mesh_ckpt_portable"]
            if mc.get("train_recompiles") is not None:
                extras["multichip_train_recompiles"] = int(
                    mc["train_recompiles"])
            # spmdcheck dynamic verdicts ride like the numerics ones
            # (verdicts, not perf — the suspect refusal never hides them)
            if mc.get("spmd_schedule_divergence") is not None:
                extras["spmd_schedule_divergence"] = int(
                    mc["spmd_schedule_divergence"])
            if mc.get("spmd_divergence_detected") is not None:
                extras["spmd_divergence_detected"] = bool(
                    mc["spmd_divergence_detected"])
            extras["multichip_cps_per_chip"] = mc.get("cps_per_chip")
            extras["multichip_forced_host"] = bool(mc.get("forced_host"))
            if mc.get("multichip_mfu") is not None:
                extras["multichip_mfu"] = mc["multichip_mfu"]
            if mc.get("multichip_mfu_analytic") is not None:
                extras["multichip_mfu_analytic"] = mc[
                    "multichip_mfu_analytic"]
            if mc.get("multichip_mfu_peak_source"):
                extras["multichip_mfu_peak_source"] = mc[
                    "multichip_mfu_peak_source"]
        flush_partial()

    if args.pipeline:
        # PIPELINE lane: child-isolated like the multichip lane (a stuck
        # stage compile loses the lane, not the round); forced-host in
        # smoke
        pc = lane_child("__pipeline__")
        extras["pipeline"] = pc  # full record -> bench_partial.json
        if "error" in pc:
            extras["pipeline_error"] = str(pc["error"])[:120]
        else:
            extras["pipeline_parity"] = pc.get("pipeline_parity")
            if pc.get("pipeline_donation_verified") is not None:
                extras["pipeline_donation_verified"] = bool(
                    pc["pipeline_donation_verified"])
            if pc.get("train_recompiles") is not None:
                extras["pipeline_train_recompiles"] = int(
                    pc["train_recompiles"])
            for key in ("pipeline_cps_per_chip", "pipeline_bubble_frac",
                        "pipeline_bubble_frac_analytic", "pipeline_stages"):
                if pc.get(key) is not None:
                    extras[key] = pc[key]
        flush_partial()

    if args.data:
        # host-side benches run in the parent but bounded: a wedged decode
        # or forked worker must not break the one-JSON-line contract (the
        # final os._exit below reaps any stuck daemon thread)
        from concurrent.futures import ThreadPoolExecutor
        from concurrent.futures import TimeoutError as FutTimeout

        pool = ThreadPoolExecutor(max_workers=1)
        for key, fn in (("data_pipeline", bench_data),
                        ("transport_crossover", bench_transport_crossover)):
            try:
                extras[key] = pool.submit(fn, args).result(timeout=900)
            except FutTimeout:
                log(f"[{key}] timed out after 900s")
                extras[key] = {"error": "timeout after 900s"}
                pool.shutdown(wait=False)
                pool = ThreadPoolExecutor(max_workers=1)
            except Exception as e:
                log(f"[{key}] FAILED: {type(e).__name__}: {e}")
                extras[key] = {"error": f"{type(e).__name__}: {e}"}
        pool.shutdown(wait=False)
        # how many host cores feed one chip at the measured device rate?
        dp = extras.get("data_pipeline", {})
        flag = results.get("slowfast_r50", {})
        loader_cps = dp.get("loader_thread_clips_per_sec")
        chip_cps = flag.get("clips_per_sec_per_chip")
        if loader_cps and chip_cps and dp.get("num_workers"):
            per_worker = loader_cps / dp["num_workers"]
            dp["loader_clips_per_sec_per_worker"] = round(per_worker, 2)
            dp["workers_to_feed_one_chip"] = round(chip_cps / per_worker, 1)
            dp["chip_demand_clips_per_sec"] = chip_cps
            dp["chip_demand_is_smoke"] = bool(flag.get("smoke"))
        if loader_cps and dp.get("num_workers"):
            dp["feed_projection"] = feed_projection(dp)
        flush_partial()

    if args.dataplane:
        # DATA_PLANE lane (dataplane/bench.py): local loader vs N remote
        # decode workers — host-CPU-real numbers in the bench_data
        # tradition (trustworthy on any box, never device claims), run in
        # the parent but bounded so a wedged worker process can't break
        # the one-JSON-line contract. A DAEMON thread, not an executor:
        # concurrent.futures' atexit hook joins non-daemon workers, so an
        # abandoned-but-wedged lane would block interpreter exit on any
        # non-os._exit path (a failed smoke assert) and lose the round to
        # the driver's kill. The refusal rule mirrors the fleet lane: a
        # failed or parity-broken lane headlines dataplane_error INSTEAD
        # of the perf keys.
        import threading as _dp_threading

        from pytorchvideo_accelerate_tpu.dataplane.bench import (
            run_dataplane_bench,
        )

        _dp_out: dict = {}

        def _dp_lane():
            try:
                # deadline_s < the join timeout: the lane self-bounds (it
                # stops spawning worker processes between trials) BEFORE
                # this thread is abandoned — nothing can cancel it from
                # outside, and an abandoned lane would keep spawning
                _dp_out["result"] = run_dataplane_bench(
                    smoke=args.smoke, workers=2, deadline_s=480, log=log)
            except Exception as e:  # noqa: BLE001 - lane isolation
                _dp_out["result"] = {
                    "error": f"{type(e).__name__}: {e}"}

        _dp_thread = _dp_threading.Thread(target=_dp_lane, daemon=True,
                                          name="bench-dataplane")
        _dp_thread.start()
        _dp_thread.join(timeout=600)
        dpl = _dp_out.get("result") or {"error": "timeout after 600s"}
        extras["dataplane"] = dpl
        if "error" in dpl:
            log(f"[dataplane] lane failed: {dpl['error']}")
            # an abandoned lane must not leave decode-worker PROCESSES
            # burning CPU under the fleet/serving lanes measured next —
            # the exact cross-lane distortion this lane documents
            from pytorchvideo_accelerate_tpu.dataplane.feed import (
                reap_spawned_workers,
            )

            reaped = reap_spawned_workers()
            if reaped:
                log(f"[dataplane] reaped {reaped} orphaned worker "
                    "process(es) after lane failure")
            extras["dataplane_error"] = str(dpl["error"])[:120]
        elif not dpl.get("parity"):
            extras["dataplane_error"] = (
                "remote batch stream diverged from the local loader "
                "(see bench_partial.json dataplane record)")
        else:
            extras["dataplane_cps"] = dpl["dataplane_cps"]
            extras["dataplane_input_wait_frac"] = dpl[
                "dataplane_input_wait_frac"]
            extras["dataplane_workers"] = dpl["dataplane_workers"]
        flush_partial()

    if args.fleet:
        # SERVE_FLEET lane: child-isolated like the model benches (a
        # stuck warmup compile loses the lane, not the round); smoke mode
        # runs on a forced-host slice so the two replicas get disjoint
        # devices.
        fl = lane_child("__fleet__")
        extras["fleet"] = fl  # full record -> bench_partial.json
        if "error" in fl:
            extras["fleet_error"] = str(fl["error"])[:120]
        else:
            for key in ("serve_rps", "serve_p99_ms_under_load",
                        "swap_blackout_ms", "fleet_shed_frac",
                        "trace_sampled", "trace_overhead_frac"):
                if fl.get(key) is not None:
                    extras[key] = fl[key]
        flush_partial()

    if args.fleet_auto:
        # FLEET_AUTO lane: child-isolated like the fleet lane; the same
        # refusal rule — a failed lane headlines
        # fleet_auto_error INSTEAD of the control-loop perf keys, and the
        # verdict keys (canary_promoted / fleet_session_failures) ride
        # regardless: a refused round must still say whether the rollback
        # machinery and the re-home path held
        fa = lane_child("__fleet_auto__")
        extras["fleet_auto"] = fa  # full record -> bench_partial.json
        if "error" in fa:
            extras["fleet_auto_error"] = str(fa["error"])[:120]
        else:
            for key in ("autoscale_converge_s", "fleet_scaledown_shed_frac",
                        "canary_rollback", "fleet_models_served"):
                if fa.get(key) is not None:
                    extras[key] = fa[key]
        for key in ("canary_promoted", "fleet_session_failures",
                    # pva-tpu-hbm verdicts ride regardless too: a refused
                    # round must still say whether the burn-rate rule
                    # flapped and whether measured admission held
                    "alert_false_positives", "budget_lies_refused"):
            if fa.get(key) is not None:
                extras[key] = fa[key]
        flush_partial()

    if args.stream:
        # STREAM lane: child-isolated like the fleet lane (a stuck
        # compile loses the lane, not the round). The refusal rule
        # mirrors fleet/dataplane: a failed or parity-broken lane
        # headlines stream_error INSTEAD of the numbers; the verdict
        # keys (parity/recompiles) ride regardless.
        st = lane_child("__stream__")
        extras["stream"] = st  # full record -> bench_partial.json
        if "error" in st:
            extras["stream_error"] = str(st["error"])[:120]
        elif not st.get("stream_parity"):
            extras["stream_error"] = (
                "incremental advance logits diverged from the full-clip "
                "recompute (see bench_partial.json stream record)")
        else:
            for key in ("stream_incremental_speedup",
                        "stream_h2d_bytes_frac", "stream_p99_ms",
                        "stream_trunk_speedup", "stream_trunk_top1_delta"):
                if st.get(key) is not None:
                    extras[key] = st[key]
            if st.get("stream_trunk_refused"):
                # quality-gate refusal: the top-1 delta headlines (just
                # above) but the speedup does not — the refusal reason
                # rides so the round is self-explaining
                extras["stream_trunk_error"] = str(
                    st["stream_trunk_refused"])[:120]
        for key in ("stream_parity", "stream_recompiles",
                    "stream_trunk_parity", "stream_trunk_recompiles"):
            if st.get(key) is not None:
                extras[key] = st[key]
        flush_partial()

    if args.kbench:
        # kernel-microbench lane: child-isolated like the model benches.
        # The speedups are same-backend ratios (platform-labeled; only a
        # TPU run is a device claim, and raw ms never leave
        # bench_partial.json)
        kb = lane_child("__kbench__")
        extras["kbench"] = kb  # full record -> bench_partial.json
        if "error" in kb:
            extras["kbench_error"] = str(kb["error"])[:120]
        elif not kb.get("parity_ok", False):
            # a fused kernel that diverged from its reference must
            # headline the violation INSTEAD of any speedup
            extras["kbench_error"] = ("kernel parity violation (see "
                                      "bench_partial.json kbench record)")
        else:
            from pytorchvideo_accelerate_tpu.ops.kbench import (
                headline_keys,
            )

            extras.update(headline_keys(kb))
        flush_partial()

    if args.serve_smoke:
        # serving lane runs in the parent (CPU-pinned, tiny model) but
        # bounded like the host benches: a wedged compile or stuck batcher
        # thread must not break the one-JSON-line contract
        from concurrent.futures import ThreadPoolExecutor as _TPE
        from concurrent.futures import TimeoutError as _FutTimeout

        _pool = _TPE(max_workers=1)
        try:
            extras["serving"] = _pool.submit(
                bench_serving, args).result(timeout=600)
        except _FutTimeout:
            log("[serving] timed out after 600s")
            extras["serving"] = {"error": "timeout after 600s"}
        except Exception as e:
            log(f"[serving] FAILED: {type(e).__name__}: {e}")
            extras["serving"] = {"error": f"{type(e).__name__}: {e}"}
        _pool.shutdown(wait=False)
        flush_partial()

    headline = finalize(results, extras, user_smoke)
    if user_smoke and args.trainer:
        # CI contract (same spirit as the serving lane below): the obs
        # step-time breakdown must come out of the trainer lane. Asserted
        # on extras, not the headline — finalize() may legitimately shed
        # these keys to fit the driver's line budget, and a successful run
        # must not fail over size shedding (test_bench_contract covers the
        # passthrough itself).
        for key in ("obs_step_s", "obs_input_wait_frac", "obs_h2d_s",
                    "train_recompiles"):
            assert key in extras, (
                f"trainer smoke ran but produced no {key!r}: "
                f"{extras.get('trainer_error') or sorted(extras)}")
        # honest-MFU contract (ROADMAP item 1): the trainer lane must
        # headline a NON-NULL mfu_analytic with its provenance label even
        # on CPU smoke — the analytic FLOPs counter traces everywhere and
        # utils/hw.resolve_peak calibrates a measured denominator where
        # no datasheet peak exists. A null here means the honest-MFU
        # plumbing silently fell out of fit().
        assert extras.get("mfu_analytic") is not None, (
            f"trainer smoke produced no mfu_analytic: "
            f"{extras.get('trainer_error') or sorted(extras)}")
        assert extras.get("mfu_source") in ("costmodel", "analytic"), (
            f"mfu_analytic lacks a provenance label: "
            f"{extras.get('mfu_source')!r}")
        # steady-state-zero recompile contract: after the first step's
        # legitimate compile, the train step's jit cache must not grow
        # (pva_train_recompiles gauge; the recompile rule's runtime
        # teeth). None = probe unavailable on this jax — degrade to
        # "unknown" rather than failing the bench over a missing API.
        assert extras["train_recompiles"] in (0, None), (
            f"steady-state recompiles detected: {extras['train_recompiles']} "
            "jit cache entries compiled after warmup (see "
            "docs/STATIC_ANALYSIS.md, rule `recompile`)")
        # self-healing contract (docs/RELIABILITY.md § divergence
        # runbook): the guard runs ARMED in the trainer lane; on a clean
        # synthetic run it must report zero rollbacks and zero
        # quarantined clips — anything else is a guard false positive
        for key in ("guard_rollbacks", "quarantined_clips"):
            assert key in extras, (
                f"trainer smoke ran with the guard armed but produced no "
                f"{key!r}: "
                f"{extras.get('trainer_error') or sorted(extras)}")
            assert extras[key] == 0, (
                f"guard reported {key}={extras[key]} on a clean smoke "
                "run (false positive; see docs/RELIABILITY.md)")
        # memory-ledger contract (pva-tpu-hbm, docs/OBSERVABILITY.md §
        # memory ledger): the hbm triple must come out of the trainer
        # lane, and on the forced-host smoke child (CPU pinned, no
        # backend memory_stats) the source MUST read "estimate" — a
        # "measured" label here would mean the ledger fabricated device
        # bytes, the exact lie the ledger exists to prevent
        for key in ("hbm_peak_bytes", "hbm_attributed_frac", "hbm_source"):
            assert extras.get(key) is not None, (
                f"trainer smoke ran but produced no {key!r}: "
                f"{extras.get('trainer_error') or sorted(extras)}")
        assert extras["hbm_source"] == "estimate", (
            f"CPU smoke host reported hbm_source="
            f"{extras['hbm_source']!r} — estimate-only hosts must never "
            "claim measured device bytes")
    if user_smoke:
        # dynamic-sanitizer contract, the third leg alongside lint-clean
        # and train_recompiles == 0: the bundled pva-tpu-tsan stress pass
        # over the threaded layers must report zero races / lock cycles
        # (docs/STATIC_ANALYSIS.md § dynamic sanitizer)
        assert extras.get("tsan_findings") == 0, (
            f"pva-tpu-tsan found {extras.get('tsan_findings')} race/"
            "lock-cycle finding(s) on the stress scenario (report logged "
            "above; see docs/STATIC_ANALYSIS.md)")
        # resilience contract, fourth leg: the chaos scenario already
        # gated at the top; the headline must carry its verdict too
        assert extras.get("chaos_findings") == 0, (
            f"pva-tpu-chaos found {extras.get('chaos_findings')} "
            "unrecovered fault(s) (see docs/RELIABILITY.md)")
        # compiled-graph contract, fifth leg: graphcheck already gated at
        # the top; the headline must carry its verdict too
        assert extras.get("graphcheck_findings") == 0, (
            f"pva-tpu-graphcheck found {extras.get('graphcheck_findings')} "
            "finding(s) (see docs/STATIC_ANALYSIS.md)")
        # collective-schedule contract: spmdcheck already gated at the
        # top; the headline must carry its verdict too
        assert extras.get("spmdcheck_findings") == 0, (
            f"pva-tpu-spmdcheck found {extras.get('spmdcheck_findings')} "
            "finding(s) (see docs/STATIC_ANALYSIS.md § spmdcheck)")
    if user_smoke and args.multichip:
        # 2-D-mesh contract (docs/PARALLELISM.md): the scaling lane must
        # produce its parity verdict and curve, parity must HOLD, and the
        # steady-state-zero recompile contract must survive the (data,
        # model) layout — not just the 1-D DP path the trainer lane runs
        for key in ("mesh_parity", "multichip_cps_per_chip"):
            assert key in extras, (
                f"multichip smoke ran but produced no {key!r}: "
                f"{extras.get('multichip_error') or sorted(extras)}")
        assert extras["mesh_parity"] is True, (
            "N-device (data, model) mesh diverged from the 1-device loss "
            f"trajectory: {extras.get('multichip')}")
        assert extras.get("mesh_ckpt_portable") in (True, None), (
            f"mesh-reshape checkpoint restore failed: "
            f"{extras.get('multichip')}")
        assert extras.get("multichip_train_recompiles") in (0, None), (
            "steady-state recompiles under the 2-D mesh layout: "
            f"{extras.get('multichip_train_recompiles')}")
        # collective-schedule contract (docs/STATIC_ANALYSIS.md
        # § spmdcheck): the lane's emulated-host probe must replay an
        # identical schedule on every host (zero divergence), and the
        # seeded-divergence leg must PROVE the differ catches a real
        # skew — a clean report from a blind recorder gates nothing
        assert extras.get("spmd_schedule_divergence") == 0, (
            "MULTICHIP collective schedules diverged across emulated "
            f"hosts: {extras.get('spmd_schedule_divergence')} "
            f"({extras.get('multichip')})")
        assert extras.get("spmd_divergence_detected") is True, (
            "seeded schedule divergence was NOT detected by the "
            "recorder/differ — the divergence gate is blind "
            f"({extras.get('multichip')})")
    if user_smoke and args.pipeline:
        # PIPELINE acceptance (docs/PARALLELISM.md § pipeline): the P=2/4
        # stage pipelines hold the P=1 fp32 loss trajectory at identical
        # steps, the bubble fraction is headlined (analytic AND measured),
        # donation survives the stage scan, and the steady-state-zero
        # recompile contract holds under the pipelined layout
        pc = extras.get("pipeline", {})
        assert "pipeline_error" not in extras, (
            f"PIPELINE lane failed: {extras['pipeline_error']}: {pc}")
        assert extras.get("pipeline_parity") is True, (
            "pipelined VideoMAE pretrain diverged from the P=1 loss "
            f"trajectory: {pc}")
        for key in ("pipeline_cps_per_chip", "pipeline_bubble_frac",
                    "pipeline_bubble_frac_analytic"):
            assert extras.get(key) is not None, (
                f"pipeline smoke ran but produced no {key!r}: {pc}")
        assert extras.get("pipeline_donation_verified") is True, (
            f"pipelined step donation not verified by graphcheck: {pc}")
        assert extras.get("pipeline_train_recompiles") in (0, None), (
            "steady-state recompiles under the pipelined layout: "
            f"{extras.get('pipeline_train_recompiles')}")
    if user_smoke and args.serve_smoke:
        # smoke mode doubles as the CI check that the serving lane's
        # headline keys didn't silently fall out (same contract as the
        # trainer lane's input_wait_frac assert)
        for key in ("serve_p50_ms", "serve_p99_ms", "serve_fill_ratio"):
            assert key in headline, (
                f"serving smoke ran but headline misses {key!r}: "
                f"{extras.get('serving')}")
    if user_smoke and args.kbench:
        # kernel-lane acceptance (docs/KERNELS.md): every fused kernel
        # holds parity with its XLA reference (benched shape AND
        # interpret-mode Pallas), every per-kernel speedup key made the
        # headline, and at least one fused kernel shows a real win over
        # the reference on this host — the folded depthwise beats XLA's
        # grouped conv by orders of magnitude even on the CPU smoke host
        kb = extras.get("kbench", {})
        assert "kbench_error" not in extras, (
            f"kbench lane failed: {extras['kbench_error']}: {kb}")
        assert extras.get("kbench_parity_ok") is True, (
            f"kbench parity keys missing/false: {kb}")
        for name in kb.get("kernels", {}):
            assert f"kbench_{name}_speedup" in extras, (
                f"kbench ran but headline misses kbench_{name}_speedup")
        assert kb.get("best_speedup", 0) >= 1.15, (
            "no fused kernel beat its XLA reference by >=1.15x on the "
            f"smoke host: {kb}")
    if user_smoke and args.fleet:
        # SERVE_FLEET acceptance (docs/SERVING.md § fleet): the open-loop
        # harness sustained its arrival rate against >=2 replicas, p99
        # held the configured SLO, the mid-load hot-swap completed with a
        # measured blackout, and NOTHING failed non-shed — sheds are the
        # admission/deadline machinery working, failures are bugs
        fl = extras.get("fleet", {})
        assert "fleet_error" not in extras, (
            f"SERVE_FLEET lane failed: {extras['fleet_error']}: {fl}")
        for key in ("serve_rps", "serve_p99_ms_under_load",
                    "swap_blackout_ms", "fleet_shed_frac"):
            assert extras.get(key) is not None, (
                f"fleet smoke ran but produced no {key!r}: {fl}")
        assert fl.get("replicas", 0) >= 2, f"fleet ran <2 replicas: {fl}"
        assert fl.get("open_loop_ok") is True, (
            f"loadgen degraded toward closed-loop (schedule slipped): {fl}")
        assert fl.get("fleet_failed") == 0, (
            f"fleet load run had non-shed failures: {fl}")
        assert fl.get("weights_cut_over") is True, (
            f"mid-load hot-swap did not change served weights: {fl}")
        assert extras["serve_p99_ms_under_load"] <= fl.get(
            "slo_p99_ms", float("inf")), (
            f"serve_p99_ms_under_load {extras['serve_p99_ms_under_load']} "
            f"ms breaches the {fl.get('slo_p99_ms')} ms SLO: {fl}")
        # distributed-tracing acceptance (docs/OBSERVABILITY.md § tracing):
        # the lane ran traced, at least one request was head-sampled, the
        # merged multi-process timeline links router->replica->engine
        # across the process boundary, and the tracer's self-measured
        # bookkeeping stayed under 2% of the run's wall time
        assert fl.get("trace_sampled", 0) >= 1, (
            f"fleet lane sampled no traces: {fl}")
        assert fl.get("trace_head_sampled", 0) >= 1, (
            "head-based sampling produced no traces (only forced probes "
            f"recorded — the obs.trace_sample_rate path is broken): {fl}")
        assert fl.get("trace_linked") is True, (
            "no sampled request spans router->replica->engine across "
            f"processes in the merged trace: {fl}")
        overhead = fl.get("trace_overhead_frac")
        assert overhead is not None and overhead < 0.02, (
            f"tracing overhead {overhead} is not under 2% of run wall "
            f"time: {fl}")
    if user_smoke and args.fleet_auto:
        # FLEET_AUTO acceptance (docs/SERVING.md § fleet intelligence):
        # the autoscaler CONVERGED on the traffic step — it grew the
        # fleet, the last scaling action landed within the deadline, and
        # a steady probe at the full stepped rate held the p99 SLO; the
        # scale-down drained a victim without losing a single live
        # streaming session; the seeded-regression canary auto-rolled-
        # back (blues restored) while the clean artifact promoted; and
        # >=2 model families served off one pool with the over-budget
        # family shed at the door
        fa = extras.get("fleet_auto", {})
        assert "fleet_auto_error" not in extras, (
            f"FLEET_AUTO lane failed: {extras['fleet_auto_error']}: {fa}")
        for key in ("autoscale_converge_s", "fleet_scaledown_shed_frac",
                    "canary_rollback", "fleet_models_served"):
            assert extras.get(key) is not None, (
                f"fleet-auto smoke ran but produced no {key!r}: {fa}")
        assert fa.get("autoscale_converged") is True, (
            f"autoscaler did not converge on the traffic step: {fa}")
        assert extras["autoscale_converge_s"] <= fa.get(
            "converge_deadline_s", float("inf")), (
            f"autoscaler converged too slowly: {fa}")
        assert fa.get("scaled_up_to", 0) > fa.get("replicas_start", 99), (
            f"traffic step did not grow the fleet: {fa}")
        assert fa.get("open_loop_ok") is True, (
            f"fleet-auto loadgen degraded toward closed-loop: {fa}")
        assert extras.get("fleet_session_failures") == 0, (
            f"scale-down lost live streaming session work: {fa}")
        assert fa.get("fleet_sessions_rehomed", 0) >= 1, (
            f"scale-down drained no session-carrying replica: {fa}")
        assert extras.get("canary_rollback") == 1, (
            f"seeded-regression canary did not auto-rollback: {fa}")
        assert fa.get("canary_blue_restored") is True, (
            f"rollback did not restore the blue engines: {fa}")
        assert extras.get("canary_promoted") is True, (
            f"clean canary was not promoted fleet-wide: {fa}")
        assert extras.get("fleet_models_served", 0) >= 2, (
            f"fewer than 2 model families served off the pool: {fa}")
        assert fa.get("budget_shed_ok") is True, (
            "over-budget family did not shed (or the in-budget family "
            f"stopped serving): {fa}")
        # pva-tpu-hbm acceptance (docs/OBSERVABILITY.md § burn-rate
        # alerts): the seeded SLO breach fired its multi-window rule
        # EXACTLY once and cleared on recovery — zero calm-phase fires,
        # zero flap re-fires — and the budget-lies probe proved the
        # admission flip: the under-declaring family the declared
        # estimate admitted is refused where the ledger measures it
        assert extras.get("alert_false_positives") == 0, (
            f"burn-rate rule fired outside the seeded breach: {fa}")
        assert fa.get("alert_fired_once") is True, (
            f"seeded SLO breach did not fire exactly one alert: {fa}")
        assert fa.get("alert_cleared") is True, (
            f"burn-rate alert did not clear on recovery: {fa}")
        assert extras.get("budget_lies_refused") is True, (
            "measured-byte admission did not refuse the under-declaring "
            f"family the declared estimate admitted: {fa}")
    if user_smoke and args.stream:
        # STREAM acceptance (docs/SERVING.md § streaming): incremental
        # advance logits matched the full-clip recompute every measured
        # round, the incremental path is >= 1.5x cheaper per label at
        # stride <= T/4 with the per-advance H2D payload cut >= 4x,
        # steady-state streaming compiled NOTHING after warmup, and the
        # open-loop stream load finished with zero non-shed failures
        # under its label-latency SLO
        st = extras.get("stream", {})
        assert "stream_error" not in extras, (
            f"STREAM lane failed: {extras['stream_error']}: {st}")
        assert extras.get("stream_parity") is True, (
            f"incremental/full-recompute parity gate failed: {st}")
        assert extras.get("stream_recompiles") == 0, (
            "steady-state session advances recompiled "
            f"{extras.get('stream_recompiles')} stream step(s) after "
            f"warmup: {st}")
        for key in ("stream_incremental_speedup", "stream_h2d_bytes_frac",
                    "stream_p99_ms"):
            assert extras.get(key) is not None, (
                f"stream smoke ran but produced no {key!r}: {st}")
        assert st.get("stride", 1) * 4 <= st.get("window", 0), (
            f"stream lane ran at stride > window/4: {st}")
        assert extras["stream_incremental_speedup"] >= 1.5, (
            f"incremental advance is not >=1.5x cheaper per label: {st}")
        assert extras["stream_h2d_bytes_frac"] <= 0.25, (
            f"per-advance H2D payload not cut >=4x: {st}")
        assert st.get("stream_failed") == 0, (
            f"stream load run had non-shed failures: {st}")
        assert st.get("open_loop_ok") is True, (
            f"stream loadgen degraded toward closed-loop: {st}")
        assert extras["stream_p99_ms"] <= st.get(
            "slo_label_p99_ms", float("inf")), (
            f"stream_p99_ms {extras['stream_p99_ms']} breaches the "
            f"{st.get('slo_label_p99_ms')} ms label SLO: {st}")
        # trunk-reuse acceptance (docs/SERVING.md § trunk-reuse): the
        # KV-ring advance matched the full-recompute-under-the-same-mask
        # replay, compiled nothing after warmup, cleared the evaluate()
        # top-1 gate vs the bidirectional baseline, and is >= 2x cheaper
        # per label decode-inclusive than re-running the masked trunk
        assert extras.get("stream_trunk_parity") is True, (
            f"KV-trunk parity vs the same-mask replay failed: {st}")
        assert extras.get("stream_trunk_recompiles") == 0, (
            "steady-state KV-trunk advances recompiled "
            f"{extras.get('stream_trunk_recompiles')} step(s) after "
            f"warmup: {st}")
        assert "stream_trunk_error" not in extras, (
            f"trunk quality gate refused the speedup: "
            f"{extras['stream_trunk_error']}: {st}")
        delta = extras.get("stream_trunk_top1_delta")
        assert delta is not None and delta <= st.get(
            "stream_trunk_top1_tol", 0.0), (
            f"trunk top-1 delta {delta} breaches the quality gate: {st}")
        assert extras.get("stream_trunk_speedup", 0.0) >= 2.0, (
            "KV-ring trunk advance is not >=2x cheaper per label "
            f"(decode-inclusive): {st}")
        # memory-ledger contract (pva-tpu-hbm): the streaming ring pools
        # + weight pins registered with the armed ledger, so the lane's
        # record must carry a non-trivial attribution with the honest
        # provenance label (estimate on the CPU-pinned smoke child)
        assert st.get("hbm_attributed_frac") is not None, (
            f"stream smoke ran but produced no hbm_attributed_frac: {st}")
        assert st.get("hbm_source") == "estimate", (
            f"CPU smoke stream lane reported hbm_source="
            f"{st.get('hbm_source')!r} — estimate-only hosts must never "
            "claim measured device bytes")
        assert st.get("hbm_peak_bytes", 0) > 0, (
            "stream lane attributed zero peak bytes with ring pools and "
            f"weight pins armed — ledger registration fell out: {st}")
    if user_smoke and args.dataplane:
        # DATA_PLANE acceptance (docs/INPUT_PIPELINE.md § disaggregated
        # data plane): N>=2 remote decode workers produced a byte-
        # identical batch stream to the local loader on the same source/
        # seed, and the remote input-wait fraction is no worse than the
        # local loader's on this host — decode scale-out must never cost
        # the trainer wait time, or the whole lever is fake
        dpl = extras.get("dataplane", {})
        assert "dataplane_error" not in extras, (
            f"DATA_PLANE lane failed: {extras['dataplane_error']}: {dpl}")
        assert dpl.get("parity") is True, (
            f"remote batch stream diverged from the local loader: {dpl}")
        assert extras.get("dataplane_workers", 0) >= 2, (
            f"dataplane lane ran <2 remote workers: {dpl}")
        for key in ("dataplane_cps", "dataplane_input_wait_frac"):
            assert extras.get(key) is not None, (
                f"dataplane smoke ran but produced no {key!r}: {dpl}")
        from pytorchvideo_accelerate_tpu.dataplane.bench import (
            WAIT_FRAC_TOLERANCE,
        )

        assert (extras["dataplane_input_wait_frac"]
                <= dpl["local_input_wait_frac"] + WAIT_FRAC_TOLERANCE), (
            f"remote input_wait_frac {extras['dataplane_input_wait_frac']} "
            f"worse than local {dpl['local_input_wait_frac']}: {dpl}")
    extras["headline"] = headline  # full record keeps the compact line too
    flush_partial()
    print(json.dumps(headline))
    sys.stdout.flush()
    sys.stderr.flush()
    # hard exit: stuck host-bench threads or lingering forked loader workers
    # must not keep the process alive after the JSON line is out
    os._exit(0)


def feed_projection(dp: dict) -> dict:
    """The design consequence of the measured host-feed rates (VERDICT r4
    weak 3): at plausible DEVICE training rates, how many decode workers /
    host cores must feed ONE chip, on the live-decode path vs the
    pre-decoded cache path?

    Projected from this host's measured per-core loader throughput, not a
    guess. Workers and cores are different resources: the per-worker rate
    reflects GIL/core sharing at the measured worker:core ratio, while the
    per-core rate assumes each core saturated — cores are the buyable
    unit. The conclusion: live cv2 decode at reference geometry costs
    multiple host cores per chip (scaling linearly with device rate)
    where the cache read path costs well under one, so the pre-decoded
    frame cache (data/cache.py) is MANDATORY at scale, not an
    optimization. The cache-path number carries its own caveat: measured
    on a page-cache-resident fixture, so it bounds CPU cost only, not
    cold-storage bandwidth."""
    cores = os.cpu_count() or 1
    loader_cps = dp["loader_thread_clips_per_sec"]
    cores_used = min(dp["num_workers"], cores)  # thread workers share cores
    loader_cps_per_core = loader_cps / cores_used
    cache_cps = dp.get("cache_clips_per_sec")
    # cache bench runs 2 reader threads (cache.bench_decode_vs_cache)
    cache_cps_per_core = cache_cps / min(2, cores) if cache_cps else None
    # storage-bound companion (pread over an evicted page cache)
    cold_cps = dp.get("cache_cold_clips_per_sec")
    # u8-through loader (host_cast=u8): no normalize + quarter-size
    # batching (measured ratio lives in the data block / docs/PERF.md)
    u8_cps = dp.get("loader_thread_u8_clips_per_sec")
    u8_per_core = (u8_cps / cores_used) if u8_cps else None
    per_worker = loader_cps / dp["num_workers"]
    rows = []
    for rate in (100, 200, 400):
        row = {"device_clips_per_sec": rate,
               "decode_workers_per_chip": math.ceil(rate / per_worker),
               "decode_cores_per_chip": round(rate / loader_cps_per_core, 1)}
        if u8_per_core:
            row["decode_u8_cores_per_chip"] = round(rate / u8_per_core, 1)
        if cache_cps_per_core:
            row["cache_cores_per_chip"] = round(rate / cache_cps_per_core, 2)
        if cold_cps:
            # storage, not CPU: fraction of one cold-read stream's
            # bandwidth a chip's appetite consumes
            row["cache_cold_streams_per_chip"] = round(rate / cold_cps, 2)
        rows.append(row)
    out = {
        "basis": {"loader_clips_per_sec_per_core":
                  round(loader_cps_per_core, 2),
                  "loader_u8_clips_per_sec_per_core":
                  round(u8_per_core, 2) if u8_per_core else None,
                  "measured_on_cores": cores,
                  "cache_is_page_cache_resident": True,
                  "cache_cold_clips_per_sec": cold_cps,
                  "cache_cold_mb_per_sec": dp.get("cache_cold_mb_per_sec")},
        "rows": rows,
        "conclusion": ("live decode costs multiple host cores per chip, "
                       "linear in device rate; the cache path costs <0.1 — "
                       "pre-decoded cache (data/cache.py build + ClipLoader "
                       "cache path) is mandatory at scale"),
    }
    return out


# The driver captures only the trailing ~2000 bytes of stdout; a headline
# line longer than that arrives truncated mid-line and parses as null
# (round 4 arrived that way). Hard budget with headroom; enforced in
# finalize() and locked by tests/test_bench_contract.py.
MAX_LINE_BYTES = 1500


def finalize(results: dict, extras: dict, user_smoke: bool) -> dict:
    """Assemble the single compact JSON line from per-model results + extras.

    The line carries headline numbers only (metric/value/mfu/suspect/error,
    one scalar per model, probe counts); everything else — full per-model
    dicts, probe timestamps, data-pipeline and transport blocks — lives in
    bench_partial.json, which main() flushes throughout the run."""
    flag_name = "slowfast_r50"
    flag = results.get(flag_name, {})
    if "clips_per_sec_per_chip" not in flag:  # flagship failed: next best
        flag_name, flag = next(
            ((n, r) for n, r in results.items()
             if "clips_per_sec_per_chip" in r), ("none", {}))

    baseline = None
    try:
        published = json.load(
            open(os.path.join(HERE, "BASELINE.json"))).get("published", {})
        baseline = published.get("clips_per_sec_per_chip")
    except Exception:
        pass
    value = flag.get("clips_per_sec_per_chip", 0.0)
    vs = value / baseline if baseline else 1.0

    smoke_tag = ", smoke" if flag.get("smoke") else ""
    out = {
        "metric": f"train clips/sec/chip ({flag_name}, "
                  f"{flag.get('frames', '?')}f, {flag.get('crop', '?')}px, "
                  f"bf16{smoke_tag})",
        "value": value,
        "unit": "clips/sec/chip",
        "vs_baseline": round(vs, 3),
        "step_ms_blocked": flag.get("step_ms_blocked"),
        "tflops_per_sec": flag.get("tflops_per_sec_per_chip"),
        "mfu": flag.get("mfu"),
        "suspect": flag.get("suspect"),
        # one scalar per model: clips/s/chip, or its error head
        "models": {
            n: (r["clips_per_sec_per_chip"]
                if "clips_per_sec_per_chip" in r
                else "err: " + str(r.get("error", "?"))[:40])
            for n, r in results.items()
        },
        "detail": "bench_partial.json",
    }
    # a multichip lane that failed headlines
    # its error INSTEAD of the perf keys — verdicts (parity/portability/
    # recompiles) still ride; error strings truncate on entry
    mc_perf = ("multichip_cps_per_chip", "multichip_forced_host",
               "multichip_mfu", "multichip_mfu_analytic",
               "multichip_mfu_peak_source")
    # fleet-lane perf keys obey the same refusal rule: a fleet_error (cpu
    # fallback or a failed lane) headlines INSTEAD of the numbers; the
    # trace verdicts (sampled count + tracer overhead fraction) ride with
    # them — they come from the same lane and are meaningless without it
    fleet_perf = ("serve_rps", "serve_p99_ms_under_load",
                  "swap_blackout_ms", "fleet_shed_frac",
                  "trace_sampled", "trace_overhead_frac")
    # FLEET_AUTO control-loop perf keys under the same refusal rule: a
    # fleet_auto_error headlines INSTEAD of the numbers; the verdicts
    # (canary_promoted / fleet_session_failures) ride regardless
    fleet_auto_perf = ("autoscale_converge_s", "fleet_scaledown_shed_frac",
                       "canary_rollback", "fleet_models_served")
    # DATA_PLANE lane perf keys under the same refusal rule: a
    # dataplane_error (failed lane or broken byte parity) headlines
    # INSTEAD of the numbers
    dataplane_perf = ("dataplane_cps", "dataplane_input_wait_frac",
                      "dataplane_workers")
    # PIPELINE lane perf keys under the same refusal rule; the parity /
    # donation / recompile verdicts ride regardless
    pipeline_perf = ("pipeline_cps_per_chip", "pipeline_bubble_frac",
                     "pipeline_bubble_frac_analytic", "pipeline_stages")
    # STREAM lane perf keys under the same refusal rule: a stream_error
    # (failed lane, broken parity) headlines INSTEAD of the
    # numbers; the parity/recompile verdicts ride regardless. The trunk
    # sub-lane's top-1 delta counts as a perf key here on purpose: it is
    # a measured eval number, meaningless on a refused round
    stream_perf = ("stream_incremental_speedup", "stream_h2d_bytes_frac",
                   "stream_p99_ms", "stream_trunk_speedup",
                   "stream_trunk_top1_delta")
    for key in ("trainer_vs_rawstep", "trainer_cps_chip", "trainer_mfu",
                "mfu_analytic", "mfu_source", "mfu_peak_source",
                "trainer_input_wait_frac", "obs_step_s",
                "obs_input_wait_frac", "obs_h2d_s", "train_recompiles",
                "guard_rollbacks", "quarantined_clips",
                "tsan_findings", "chaos_findings", "graphcheck_findings",
                "spmdcheck_findings",
                "mesh_parity",
                "mesh_ckpt_portable", "multichip_train_recompiles",
                "spmd_schedule_divergence", "spmd_divergence_detected",
                "pipeline_parity", "pipeline_donation_verified",
                "pipeline_train_recompiles",
                "stream_parity", "stream_recompiles",
                "stream_trunk_parity", "stream_trunk_recompiles",
                "canary_promoted", "fleet_session_failures",
                # pva-tpu-hbm: the ledger triple (trainer lane) + the
                # burn-rate/admission verdicts (fleet_auto lane) —
                # hbm_source is the provenance label that keeps an
                # "estimate" peak from ever reading as a device claim
                "hbm_peak_bytes", "hbm_attributed_frac", "hbm_source",
                "alert_false_positives", "budget_lies_refused",
                *mc_perf, *fleet_perf, *fleet_auto_perf, *dataplane_perf,
                *pipeline_perf, *stream_perf):
        if key in extras and not (
                (key in mc_perf and "multichip_error" in extras)
                or (key in fleet_perf and "fleet_error" in extras)
                or (key in fleet_auto_perf
                    and "fleet_auto_error" in extras)
                or (key in dataplane_perf and "dataplane_error" in extras)
                or (key in pipeline_perf and "pipeline_error" in extras)
                or (key in stream_perf and "stream_error" in extras)):
            out[key] = extras[key]
    if "stream_error" in extras:
        out["stream_error"] = str(extras["stream_error"])[:120]
    if "stream_trunk_error" in extras:
        out["stream_trunk_error"] = str(extras["stream_trunk_error"])[:120]
    if "pipeline_error" in extras:
        out["pipeline_error"] = str(extras["pipeline_error"])[:120]
    if "multichip_error" in extras:
        out["multichip_error"] = str(extras["multichip_error"])[:120]
    if "fleet_error" in extras:
        out["fleet_error"] = str(extras["fleet_error"])[:120]
    if "fleet_auto_error" in extras:
        out["fleet_auto_error"] = str(extras["fleet_auto_error"])[:120]
    if "dataplane_error" in extras:
        out["dataplane_error"] = str(extras["dataplane_error"])[:120]
    # kernel-microbench keys (pva-tpu-kbench): dimensionless same-backend
    # speedup ratios + platform label (never raw ms — those live in
    # bench_partial.json); a failed or parity-broken lane headlined
    # kbench_error INSTEAD of speedups at the lane site above
    for key in sorted(extras):
        if key.startswith("kbench_"):
            out[key] = extras[key]
    # serving lane: request-latency percentiles + batcher fill ratio
    serving = extras.get("serving", {})
    if "error" in serving:
        out["serve_error"] = str(serving["error"])[:120]
    else:
        for key in ("serve_p50_ms", "serve_p99_ms", "serve_fill_ratio"):
            if key in serving:
                out[key] = serving[key]
    # error strings can be whole tracebacks: truncate on entry, every one
    if "trainer_error" in extras:
        out["trainer_error"] = str(extras["trainer_error"])[:200]
    if "error" in extras:
        out["error"] = str(extras["error"])[:280]
    # missing platform covers error-only and empty flagship results too:
    # the driver must never read a silent zero as a real measurement
    if flag.get("platform", "cpu") == "cpu" and not user_smoke:
        out["suspect"] = True
        out["error"] = ("no trustworthy device number for the flagship "
                        "(failed bench; see bench_partial.json); CPU/smoke "
                        "values are not device numbers")
    if out.get("suspect"):
        # refusal rule for the flagship's own device-shaped perf keys: a
        # suspect round was headlining a literal `"tflops_per_sec": 0.0`
        # (round 5) — a zero pva-tpu-perfdiff could one day diff against
        # a real device number. Shed them like the lane perf keys above;
        # `value` stays (its metric string carries the smoke tag and the
        # suspect flag rides beside it, and perfdiff refuses suspect
        # rounds wholesale).
        out.pop("tflops_per_sec", None)
        out.pop("step_ms_blocked", None)
    # hard size guarantee: shed optional detail one key at a time before
    # ever exceeding the driver's capture window; the per-model map and
    # the truncations are LAST resorts (dropping a lane's optional extras
    # must never cost the models summary)
    for k in ("trace_overhead_frac", "trace_sampled",
              "multichip_mfu_peak_source", "multichip_mfu_analytic",
              "multichip_mfu", "multichip_forced_host",
              "multichip_train_recompiles", "multichip_error",
              "multichip_cps_per_chip",
              # spmd schedule verdicts shed just before the mesh verdicts
              # (the divergence gate is this arc's acceptance metric)
              "spmd_divergence_detected", "spmd_schedule_divergence",
              "mesh_ckpt_portable", "mesh_parity",
              # the PIPELINE lane sheds after the multichip curve (its
              # bubble-frac headline is this arc's acceptance metric) but
              # before the fleet/dataplane/kbench groups
              "pipeline_error", "pipeline_train_recompiles",
              "pipeline_donation_verified", "pipeline_stages",
              "pipeline_bubble_frac_analytic", "pipeline_parity",
              "pipeline_bubble_frac", "pipeline_cps_per_chip",
              "fleet_error", "fleet_shed_frac", "swap_blackout_ms",
              "serve_p99_ms_under_load", "serve_rps",
              # the FLEET_AUTO control lane sheds after the fleet group
              # (convergence is this arc's acceptance metric, so it goes
              # last of the group); verdicts shed before perf keys
              "fleet_auto_error", "canary_promoted",
              "fleet_session_failures", "budget_lies_refused",
              "alert_false_positives", "fleet_models_served",
              "fleet_scaledown_shed_frac", "canary_rollback",
              "autoscale_converge_s",
              # the STREAM lane sheds after the fleet group but before
              # dataplane/kbench (its speedup is this arc's headline);
              # the trunk SPEEDUP sheds before its top-1 delta on purpose
              # — a speedup must never outlive its quality verdict
              "stream_trunk_error", "stream_error", "stream_recompiles",
              "stream_parity", "stream_trunk_recompiles",
              "stream_trunk_parity",
              "stream_p99_ms", "stream_h2d_bytes_frac",
              "stream_trunk_speedup", "stream_trunk_top1_delta",
              "stream_incremental_speedup",
              "dataplane_error", "dataplane_workers",
              "dataplane_input_wait_frac", "dataplane_cps",
              "kbench_conv311_sf_res4_speedup",
              "kbench_conv133_sf_res4_speedup",
              "kbench_pw_x3d_res3_speedup", "kbench_platform",
              "kbench_dw_x3d_res3_speedup", "kbench_parity_ok",
              "kbench_error", "kbench_best",
              "serve_error", "serve_fill_ratio", "serve_p99_ms",
              "serve_p50_ms", "guard_rollbacks", "quarantined_clips",
              "train_recompiles", "obs_h2d_s",
              "mfu_peak_source", "mfu_source", "mfu_analytic",
              "obs_input_wait_frac",
              "obs_step_s", "trainer_error", "trainer_input_wait_frac",
              "trainer_mfu", "trainer_cps_chip",
              # the hbm triple sheds late (this arc's headline) and as a
              # unit-in-reverse: the source label must outlive the bytes
              # it qualifies, so the bytes drop first
              "hbm_attributed_frac", "hbm_peak_bytes", "hbm_source",
              "trainer_vs_rawstep", "detail", "step_ms_blocked",
              "tflops_per_sec"):  # drop one by one until it fits
        if len(json.dumps(out)) <= MAX_LINE_BYTES:
            break
        out.pop(k, None)
    if len(json.dumps(out)) > MAX_LINE_BYTES:
        out["models"] = {"dropped": "see bench_partial.json"}
    if len(json.dumps(out)) > MAX_LINE_BYTES:
        out["metric"] = out["metric"][:100]
        for k in ("error", "trainer_error"):
            if k in out:
                out[k] = out[k][:120]
    if len(json.dumps(out)) > MAX_LINE_BYTES:  # unconditional last resort
        out.pop("models", None)
    return out


if __name__ == "__main__":
    main()

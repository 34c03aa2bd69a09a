"""The training application: epoch loop, eval, resume, logging, teardown.

TPU-native re-design of the reference `training_function` (run.py:121-325),
preserving its control-surface semantics — checkpointing_steps int|"epoch",
resume-from-checkpoint (plus a working "auto"), limit_train/val_batches,
log_every, freeze_backbone, with_tracking, main-process progress bar — over
the pure-step runtime: mesh + sharded batches + compiled steps + orbax.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import Dict, Optional

import jax
import numpy as np

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.obs import memory as obs_memory
from pytorchvideo_accelerate_tpu.analysis.recompile_guard import RecompileGuard
from pytorchvideo_accelerate_tpu.config import TrainConfig
from pytorchvideo_accelerate_tpu.data.manifest import from_list, scan_directory
from pytorchvideo_accelerate_tpu.data.pipeline import (
    ClipLoader,
    LoaderRowCounts,
    LoaderState,
    SyntheticClipSource,
    SyntheticTokenSource,
    VideoClipSource,
)
from pytorchvideo_accelerate_tpu.data.device_prefetch import DevicePrefetcher
from pytorchvideo_accelerate_tpu.data.transforms import make_transform
from pytorchvideo_accelerate_tpu.models import (
    create_model,
    model_input_spec,
    model_task,
)
from pytorchvideo_accelerate_tpu.parallel.distributed import (
    initialize_distributed,
    is_main_process,
    main_print,
)
from pytorchvideo_accelerate_tpu.parallel.mesh import (
    cp_axis,
    data_shard_count,
    make_train_mesh,
    model_axis,
)
from pytorchvideo_accelerate_tpu.parallel.pipeline import (
    analytic_bubble_frac,
    make_plan as make_pipeline_plan,
    stage_tag,
)
from pytorchvideo_accelerate_tpu.parallel.sharding import (
    family_uses_tp,
    shard_params,
    shard_state,
)
from pytorchvideo_accelerate_tpu.parallel.hangcheck import (
    collective_section,
    host_tag as hangcheck_host_tag,
    install_collective_watch,
    uninstall_collective_watch,
)
from pytorchvideo_accelerate_tpu.reliability.faults import fault_point
from pytorchvideo_accelerate_tpu.reliability.guard import (
    TrainGuard,
    poison_batch,
)
from pytorchvideo_accelerate_tpu.reliability.preemption import (
    get_guard,
    record_emergency,
)
from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
    Checkpointer,
    resolve_resume_path,
)
from pytorchvideo_accelerate_tpu.trainer.metrics import MeanLoss, SumMetrics
from pytorchvideo_accelerate_tpu.trainer.optim import build_lr_schedule, build_optimizer
from pytorchvideo_accelerate_tpu.trainer.steps import (
    lm_log_values,
    make_eval_step,
    make_lm_eval_step,
    make_lm_step,
    make_pretrain_eval_step,
    make_pretrain_step,
    make_train_step,
)
from pytorchvideo_accelerate_tpu.trainer.tracking import (
    DeferredStepLogger,
    TrackerHub,
)
from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState
from pytorchvideo_accelerate_tpu.utils.bench_setup import fetch_loss
from pytorchvideo_accelerate_tpu.utils.hw import device_summary
from pytorchvideo_accelerate_tpu.utils.logging import get_logger
from pytorchvideo_accelerate_tpu.utils.rng import RngManager, set_seed

logger = get_logger("pva_tpu")

STEP_RECORDS = 8192  # fit()'s per-iteration records kept (a bounded ring)
STEP_RECORDS_FILE = "step_records.jsonl"  # beside flight_record.json


def _parse_checkpointing_steps(value: str):
    """Reference parsing semantics (run.py:123-133): "" -> None, "epoch" ->
    "epoch", digits -> int, else error. "0" normalizes to None (disabled)
    here at parse time — the reference would crash on `step % 0`."""
    if not value:
        return None
    if value == "epoch":
        return "epoch"
    if value.isdigit():
        return int(value) or None
    raise ValueError(
        f"checkpointing_steps must be a number or 'epoch', got {value!r}"
    )


class Trainer:
    """Builds the whole stack from a TrainConfig and runs fit()."""

    def __init__(self, cfg: TrainConfig):
        self.cfg = cfg
        # what the model is trained to do, as its registry entry declares it
        # (models/__init__.py TASKS): "classify" is the reference's only
        # mode; "reconstruct" (VideoMAE) has no labels and the model computes
        # its own loss; "next_token" trains on token sequences
        self.task = model_task(cfg.model.name)
        self.checkpointing_steps = _parse_checkpointing_steps(
            cfg.checkpoint.checkpointing_steps
        )
        # telemetry spine (obs/): configured FIRST so construction-time
        # spans land in the window and the flight recorder catches
        # init-time crashes; the watchdog (opt-in deadline) is created
        # before the data stack so the prefetchers can ping it
        self.obs_on = cfg.obs.enabled
        obs.configure(enabled=cfg.obs.enabled,
                      capacity=cfg.obs.flight_recorder_events)
        # pva-tpu-hbm: arm the device-memory ledger (allocation sites in the
        # prefetch ring / engines / this file start accounting), the
        # scrape-tick history ring, and — only when a window is requested —
        # the on-demand profiler. All three are disarmed no-ops otherwise
        # (one module-global read per hook, the sync.py discipline).
        if self.obs_on and cfg.obs.memory_ledger:
            obs.memory.configure(recorder=obs.get_recorder())
        if self.obs_on and cfg.obs.history_ticks > 0:
            obs.history.configure(capacity=cfg.obs.history_ticks)
        # validate --obs.profile_steps at construction (a typo'd window must
        # fail now, not 3 epochs in); run-relative step offsets A..B.
        # `--profile` is the shorthand for "2..6" published to profile_dir:
        # one capture path (obs/profiler.py), whichever flag asked
        self.profile_steps = obs.profiler.parse_steps(
            cfg.obs.profile_steps or ("2..6" if cfg.profile else ""))
        if self.profile_steps is not None:
            obs.profiler.configure(
                output_dir=(cfg.checkpoint.output_dir
                            if cfg.obs.profile_steps else cfg.profile_dir),
                recorder=obs.get_recorder())
        # step observers: callables (gstep, trainer) -> bool, run at the
        # bottom of every fit() iteration (inside its `iter` span); a true
        # value ends the epoch exactly as data.limit_train_batches does
        self.step_observers: list = []
        # one record per fit() iteration, the last STEP_RECORDS of them
        self.step_records: deque = deque(maxlen=STEP_RECORDS)
        self.watchdog: Optional[obs.Watchdog] = None
        if self.obs_on and cfg.obs.trace_sample_rate > 0:
            # distributed tracing (obs/trace.py): head-sample train steps;
            # each sampled step becomes a root span tagged (epoch, gstep)
            # whose child spans (step/log/h2d) correlate with the
            # jax.profiler.StepTraceAnnotation window of the same gstep.
            # The ring dumps to <output_dir>/trace_ring.json at fit() exit.
            obs.trace.configure_tracing(
                cfg.obs.trace_sample_rate, seed=cfg.seed,
                capacity=cfg.obs.trace_ring_events,
                output_dir=cfg.checkpoint.output_dir)
        if self.obs_on:
            obs.get_recorder().install(cfg.checkpoint.output_dir)
            if cfg.obs.watchdog_timeout_s > 0:
                self.watchdog = obs.Watchdog(
                    cfg.obs.watchdog_timeout_s,
                    output_dir=cfg.checkpoint.output_dir,
                    recorder=obs.get_recorder(),
                    collector=obs.get_collector(),
                ).start()
                # collective-hang detection (parallel/hangcheck.py): every
                # watched mesh-collective boundary — step dispatch under
                # queue push-back, epoch-end value fetch, host collectives
                # — reports through an attributed watchdog section, so a
                # wedged psum dumps per-host evidence instead of silence
                install_collective_watch(self.watchdog)
        if cfg.cpu:
            jax.config.update("jax_platforms", "cpu")
        if cfg.device_init_timeout > 0 and not cfg.cpu:
            # fail loudly instead of wedging: device backend init can hang
            # forever (a PJRT client-create that never returns while the
            # process shows no error, e.g. while another process holds
            # the chip). Probe in a disposable subprocess first — before
            # this process touches JAX, since the chip belongs to one
            # process at a time; raises with the diagnosis recipe if init
            # can't complete in time. (SURVEY §5 failure
            # detection — the reference's torch/NCCL stack fails loudly
            # on a bad device; jax would just sit there.)
            from pytorchvideo_accelerate_tpu.utils import device_doctor

            device_doctor.assert_device_reachable(
                cfg.device_init_timeout, log=logger.info)
        if cfg.debug_nans:
            jax.config.update("jax_debug_nans", True)

        initialize_distributed(
            cfg.coordinator_address, cfg.num_processes, cfg.process_id
        )
        set_seed(cfg.seed)
        self.rng = RngManager(cfg.seed)
        # the 2-D (data, model) GSPMD backbone (parallel/mesh.py;
        # docs/PARALLELISM.md). A legacy fsdp/tensor/context MeshConfig
        # still resolves to the 4-axis library mesh — every consumer below
        # resolves axes from the mesh itself.
        self.mesh = make_train_mesh(cfg.mesh)
        # how the model axis is spent is a per-family decision
        # (parallel/sharding.py): transformer families split heads/MLP
        # widths over it (Megatron TP) — UNLESS the context-parallel lane
        # is on AND spends that same axis on token sharding (the 2-D train
        # mesh, where "model" is the CP axis; params replicated) — and
        # conv families replicate over it. On the library mesh CP has its
        # own "context" axis, so TP over "tensor" composes with it.
        self._cp = cfg.model.attention in ("ring", "ulysses")
        m_axis = model_axis(self.mesh)
        cp_spends_model_axis = (
            self._cp and m_axis is not None and cp_axis(self.mesh) == m_axis
        )
        # pipeline parallelism (parallel/pipeline.py): stages SPEND the
        # model axis — params stay replicated over it (no Megatron TP),
        # and on the 2-D train mesh CP is excluded too (make_plan raises);
        # on the library mesh CP keeps its own "context" axis and composes
        self.pipeline_plan = None
        if cfg.parallel.pipeline_stages > 1:
            self.pipeline_plan = make_pipeline_plan(
                self.mesh, cfg.parallel.pipeline_stages,
                microbatches=cfg.parallel.pipeline_microbatches,
                accum_steps=cfg.optim.gradient_accumulation_steps,
                cp_axis_name=cp_axis(self.mesh) if self._cp else None,
            )
        pipelined = self.pipeline_plan is not None
        self._tp = (family_uses_tp(cfg.model.name)
                    and not cp_spends_model_axis and not pipelined)
        m_size = self.mesh.shape[m_axis] if m_axis else 1
        mode = (f"pipelined ({self.pipeline_plan.stages} stages, "
                f"{self.pipeline_plan.microbatches} microbatches)"
                if pipelined
                else "context-parallel" if cp_spends_model_axis
                else "tensor-parallel" if self._tp else "replicated")
        main_print(f"device: {json.dumps(device_summary())}")
        main_print(
            f"mesh: {dict(self.mesh.shape)} over {self.mesh.size} "
            f"{jax.devices()[0].platform} devices, "
            f"{jax.process_count()} process(es)"
            + (f"; model axis ({m_size}): {mode}" if m_size > 1 else "")
        )

        self._build_data()
        self._build_model_and_steps()

        self.checkpointer: Optional[Checkpointer] = None
        if self.checkpointing_steps is not None or cfg.checkpoint.resume_from_checkpoint:
            ckpt_dir = os.path.join(cfg.checkpoint.output_dir, "checkpoints")
            resume_dir = resolve_resume_path(
                cfg.checkpoint.resume_from_checkpoint, ckpt_dir
            )
            self.checkpointer = Checkpointer(
                resume_dir or ckpt_dir,
                max_to_keep=cfg.checkpoint.max_to_keep,
                use_async=cfg.checkpoint.async_checkpoint,
                retries=cfg.reliability.ckpt_retries,
                retry_base_delay_s=cfg.reliability.retry_base_delay_s,
                retry_max_delay_s=cfg.reliability.retry_max_delay_s,
                retry_deadline_s=cfg.reliability.retry_deadline_s,
            )

        # self-healing guard (reliability/guard.py; docs/RELIABILITY.md
        # § divergence runbook): LKG ring + anomaly rollback + replay
        # bundles. None when disarmed — the step loop then does one
        # `is None` check (structural zero overhead).
        # Deliberately NOT a MemoryLedger component: the LKG ring is an
        # orbax checkpoint ring on DISK (<output_dir>/guard_lkg), and a
        # rollback's transient restore buffer replaces the live TrainState
        # already accounted above — registering either as HBM would fake
        # device bytes (docs/OBSERVABILITY.md § memory ledger).
        self.train_guard: Optional[TrainGuard] = None
        if cfg.guard.enabled:
            self.train_guard = TrainGuard(
                cfg.guard, output_dir=cfg.checkpoint.output_dir,
                mesh=self.mesh, tp=self._tp, config_dict=cfg.to_dict(),
                seed=cfg.seed)
            self.train_guard.quarantine = self.quarantine

        # user-registered checkpoint participants (reference
        # `accelerator.register_for_checkpointing`, run.py:199)
        self._registered: dict = {}

        self.trackers: Optional[TrackerHub] = None
        if cfg.tracking.with_tracking and is_main_process():
            run_name = (
                str(cfg.tracking.logging_dir)
                .replace(".", "").replace("/", "").replace("\\", "")
            )  # reference run-name derivation (run.py:229)
            self.trackers = TrackerHub(cfg.tracking.trackers,
                                       cfg.tracking.logging_dir,
                                       retries=cfg.reliability.tracker_retries)
            self.trackers.start(run_name, cfg.to_dict())

    # --- construction -----------------------------------------------------

    def _build_data(self) -> None:
        cfg = self.cfg
        d = cfg.data
        # bad-sample quarantine sidecar (data/manifest.Quarantine): built
        # for the real-video path when the guard is on; train and val
        # sources share it so a clip that corrupts either split is
        # sidelined for both
        self.quarantine = None
        is_slowfast = cfg.model.name.startswith("slowfast")
        # host-side cast to the compute dtype: halves clip bytes end to end
        # (worker -> shm ring -> host RAM -> HBM). For the supervised models
        # this is value-preserving (they cast inputs to bf16 on device
        # anyway); VideoMAE pretraining is excluded — its regression target
        # is computed in fp32 from the raw clip (videomae.py patchify), so a
        # host cast would quantize the objective itself.
        # "u8" goes further: clips stay raw uint8 through the geometric
        # transforms (4x less than fp32 everywhere host-side and over the
        # host->HBM link) and the jitted step applies the normalize affine
        # in-graph (steps.device_normalize_batch), where XLA fuses it into
        # the first conv. Supervised-only, same pretraining rationale.
        if d.host_cast not in ("auto", "fp32", "u8"):
            raise ValueError(
                f"data.host_cast must be 'auto', 'fp32' or 'u8', "
                f"got {d.host_cast!r}"
            )
        reconstructs = self.task == "reconstruct"
        if d.host_cast == "u8" and reconstructs:
            raise ValueError(
                "data.host_cast='u8' is supervised-only: the MAE target is "
                "computed from the raw clip in fp32 (videomae.py patchify)"
            )
        u8 = d.host_cast == "u8"
        bf16 = (cfg.mixed_precision in ("bf16", "fp16")
                and d.host_cast == "auto" and not reconstructs)
        common = dict(
            num_frames=d.num_frames,
            is_slowfast=is_slowfast,
            slowfast_alpha=cfg.model.slowfast_alpha,
            min_short_side_scale=d.min_short_side_scale,
            max_short_side_scale=d.max_short_side_scale,
            crop_size=d.crop_size,
            mean=d.mean,
            std=d.std,
            horizontal_flip_p=d.horizontal_flip_p,
            output_dtype=("uint8" if u8
                          else "bfloat16" if bf16 else "float32"),
        )
        train_tf = make_transform(training=True, **common)
        self._device_normalize = train_tf.device_normalize

        # multi-view eval is supervised-only: the pretrain eval step scores
        # reconstructions clip-by-clip, so a view axis would just crash it
        eval_clips = 1 if reconstructs else d.eval_num_clips
        eval_spatial = 1 if reconstructs else d.eval_num_spatial_crops
        if reconstructs and (d.eval_num_clips > 1
                             or d.eval_num_spatial_crops > 1):
            main_print("multi-view eval options ignored for self-supervised "
                       "pretraining")
        val_tf = make_transform(training=False,
                                num_spatial_crops=eval_spatial, **common)

        train_manifest = None  # set by the real-video branch (dataplane spec)
        if self.task == "next_token":
            # token sequences: one source so far, ids uniform over the held
            # vocabulary slice (docs/TOKENS.md); the loader, the prefetcher
            # and everything behind them are the clips' own
            if not d.synthetic:
                raise ValueError(
                    f"model {cfg.model.name!r} trains on token sequences, "
                    "and the synthetic source is the only token source so "
                    "far: pass --synthetic (docs/TOKENS.md)")
            num_classes = create_model(cfg.model, cfg.mixed_precision,
                                       mesh=self.mesh).arch.vocab_size
            self.train_source = SyntheticTokenSource(
                d.seq_len, num_classes, d.synthetic_num_videos, seed=cfg.seed)
            self.val_source = SyntheticTokenSource(
                d.seq_len, num_classes, max(d.synthetic_num_videos // 4, 4),
                seed=cfg.seed + 1)
        elif d.synthetic:
            num_classes = cfg.model.num_classes or 4
            self.train_source = SyntheticClipSource(
                train_tf, num_videos=d.synthetic_num_videos,
                num_classes=num_classes, seed=cfg.seed,
            )
            self.val_source = SyntheticClipSource(
                val_tf, num_videos=max(d.synthetic_num_videos // 4, 4),
                num_classes=num_classes, seed=cfg.seed + 1,
                num_clips=eval_clips,
            )
        elif d.cache_dir:
            from pytorchvideo_accelerate_tpu.data.cache import CachedClipSource

            self.train_source = CachedClipSource(
                os.path.join(d.cache_dir, "train"), train_tf,
                cfg.clip_duration, training=True, seed=cfg.seed,
            )
            self.val_source = CachedClipSource(
                os.path.join(d.cache_dir, "val"), val_tf,
                cfg.clip_duration, training=False, seed=cfg.seed,
                num_clips=eval_clips,
            )
            num_classes = self.train_source.num_classes
        else:
            if cfg.guard.enabled and cfg.guard.quarantine_budget > 0:
                from pytorchvideo_accelerate_tpu.data.manifest import (
                    Quarantine,
                )

                self.quarantine = Quarantine(
                    os.path.join(cfg.checkpoint.output_dir,
                                 "quarantine.json"),
                    budget=cfg.guard.quarantine_budget)
            video_retry_kw = dict(
                decode_retries=cfg.reliability.decode_retries,
                retry_base_delay_s=cfg.reliability.retry_base_delay_s,
                quarantine=self.quarantine,
            )
            if d.train_list or d.val_list:
                if not (d.train_list and d.val_list):
                    raise ValueError(
                        "train_list and val_list must be set together "
                        "(mixing a list split with a scanned split would "
                        "give the two splits different label id spaces)")
                train_manifest = from_list(d.train_list, root=d.data_dir)
                val_manifest = from_list(d.val_list, root=d.data_dir)
                val_max = max(e.label for e in val_manifest.entries)
                if val_max >= train_manifest.num_classes:
                    raise ValueError(
                        f"val_list label {val_max} is outside the train "
                        f"list's class space (num_classes="
                        f"{train_manifest.num_classes}): out-of-range "
                        "labels would silently corrupt eval metrics")
            else:
                train_manifest = scan_directory(
                    os.path.join(d.data_dir, "train"))
                val_manifest = scan_directory(os.path.join(d.data_dir, "val"))
            num_classes = train_manifest.num_classes  # replaces run.py:185
            self.train_source = VideoClipSource(
                train_manifest, train_tf, cfg.clip_duration, training=True,
                seed=cfg.seed, **video_retry_kw,
            )
            self.val_source = VideoClipSource(
                val_manifest, val_tf, cfg.clip_duration, training=False,
                seed=cfg.seed, num_clips=eval_clips, **video_retry_kw,
            )
        self.num_classes = num_classes

        shards = data_shard_count(self.mesh)
        global_batch = d.batch_size * shards  # per-shard batch_size, DP-scaled
        loader_kw = dict(
            seed=cfg.seed,
            num_workers=d.num_workers,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            transport=d.transport,
        )
        self.train_loader = ClipLoader(
            self.train_source, global_batch,
            accum_steps=cfg.optim.gradient_accumulation_steps,
            shuffle=True, drop_last=True,
            prefetch_batches=d.prefetch_batches, **loader_kw,
        )
        self.val_loader = ClipLoader(
            self.val_source, global_batch, accum_steps=1,
            shuffle=False, drop_last=False,
            prefetch_batches=d.prefetch_batches, **loader_kw,
        )
        # disaggregated data plane (dataplane/; docs/INPUT_PIPELINE.md):
        # when configured, the train loader's decode moves to N worker
        # PROCESSES and a RemoteClipFeed slots in where the local iterator
        # feeds the device prefetcher — same epoch_items contract, same
        # LoaderState in checkpoints, byte-identical batches. The feed
        # records remote quarantine verdicts into the same sidecar.
        self.train_feed = None
        if d.dataplane_workers > 0:
            from pytorchvideo_accelerate_tpu.dataplane import spec as dpspec
            from pytorchvideo_accelerate_tpu.dataplane.feed import (
                RemoteClipFeed,
            )

            if d.cache_dir:
                raise ValueError(
                    "data.dataplane_workers is incompatible with "
                    "data.cache_dir: memmap cache reads are already cheaper "
                    "than the wire — disaggregate the decode path only")
            tspec = {**common, "training": True}
            if d.synthetic:
                src_spec = dpspec.synthetic_spec(
                    tspec, num_videos=d.synthetic_num_videos,
                    num_classes=num_classes, seed=cfg.seed)
            else:
                src_spec = dpspec.video_spec(
                    train_manifest, tspec, clip_duration=cfg.clip_duration,
                    training=True, seed=cfg.seed,
                    decode_retries=cfg.reliability.decode_retries,
                    retry_base_delay_s=cfg.reliability.retry_base_delay_s)
            from pytorchvideo_accelerate_tpu.dataplane.wire import (
                parse_address,
            )

            try:
                listen = parse_address(d.dataplane_listen)
            except ValueError as e:
                raise ValueError(f"--data.dataplane_listen: {e}") from None
            self.train_feed = RemoteClipFeed(
                self.train_loader, src_spec, spawn=d.dataplane_workers,
                listen=listen,
                credits=d.dataplane_credits, quarantine=self.quarantine,
                trace_config={"sample_rate": cfg.obs.trace_sample_rate,
                              "seed": cfg.seed},
            )
            main_print(
                f"dataplane: {d.dataplane_workers} decode worker(s) on "
                f"{self.train_feed.address[0]}:{self.train_feed.address[1]} "
                f"(credits={d.dataplane_credits})")
        # device-side prefetch: the step loops consume pre-placed mesh
        # batches; the H2D copy of batch N+1 overlaps compute of batch N
        # (depth 0 = synchronous placement, the A/B baseline)
        self.train_prefetch = DevicePrefetcher(
            self.train_feed or self.train_loader, self.mesh,
            depth=d.device_prefetch_depth,
            micro_dim=cfg.optim.gradient_accumulation_steps > 1,
            watchdog=self.watchdog, watchdog_name="prefetch_train",
        )
        # the val prefetcher's consumer wait nests inside the "eval" span,
        # so it gets a background-classed name (no double count in sums)
        self.val_prefetch = DevicePrefetcher(
            self.val_loader, self.mesh, depth=d.device_prefetch_depth,
            watchdog=self.watchdog, watchdog_name="prefetch_val",
            wait_name="eval_input_wait", h2d_name="eval_h2d",
        )

    def _build_model_and_steps(self) -> None:
        cfg = self.cfg
        if not cfg.model.num_classes:
            cfg.model.num_classes = self.num_classes
        self.model = create_model(cfg.model, cfg.mixed_precision,
                                  mesh=self.mesh,
                                  pipeline=self.pipeline_plan)
        # eval scores through an UNPIPELINED twin (identical param tree —
        # the plan is a lowering choice, not a param-tree one): eval is
        # forward-only, so there are no stored activations to fit, and the
        # val loader's ragged/padded tail batches need not divide into the
        # plan's microbatches
        self.eval_model = (create_model(cfg.model, cfg.mixed_precision,
                                        mesh=self.mesh)
                           if self.pipeline_plan is not None else self.model)

        spec = model_input_spec(cfg.model, cfg.data)
        import jax.numpy as jnp

        if "tokens" in spec:
            # parameters do not depend on the length: a short init sample
            sample = jnp.zeros((1, min(spec["tokens"][1], 128)), jnp.int32)
        elif "slow" in spec:
            sample = (jnp.zeros(spec["slow"]), jnp.zeros(spec["fast"]))
        else:
            sample = jnp.zeros(spec["video"])
        # init through a mesh-free twin on multi-device meshes: the param
        # tree is identical by contract (mesh/pipeline are lowering
        # choices — init values depend only on module structure + rng),
        # and the transformer families' block-boundary sharding
        # constraints would reject the batch-1 init sample on a data>1
        # TRAIN mesh (its leading dim can't divide the data axis — the
        # pipelined-videomae configuration hit this). The CP backends
        # keep the original mesh'd init: they REQUIRE the mesh at
        # construction, and their library-mesh init predates this twin.
        init_model = (create_model(cfg.model, cfg.mixed_precision)
                      if self.mesh.size > 1 and not self._cp
                      else self.model)
        variables = init_model.init(self.rng.init_key(), sample)

        steps_per_epoch = self.train_loader.steps_per_epoch()
        # T_max semantics: optimizer steps over the whole run (run.py:193-195,
        # with the scheduler-x-world quirk consciously fixed — optim.py)
        self.total_steps = max(steps_per_epoch * cfg.optim.num_epochs, 1)
        backbone_filter = getattr(type(self.model), "backbone_param_filter", None)
        self.tx = build_optimizer(
            cfg.optim, self.total_steps,
            backbone_filter=backbone_filter,
            freeze_backbone=cfg.model.freeze_backbone,
        )
        self.lr_schedule = build_lr_schedule(cfg.optim, self.total_steps)

        params = shard_params(self.mesh, variables["params"], tp=self._tp)
        batch_stats = shard_params(self.mesh, variables.get("batch_stats", {}),
                                   tp=self._tp)
        if not 0.0 <= cfg.optim.ema_decay < 1.0:
            raise ValueError(
                f"optim.ema_decay must be in [0, 1), got "
                f"{cfg.optim.ema_decay} (1.0 would freeze the EMA at the "
                "init weights while eval keeps scoring them)")
        self.state = TrainState.create(params, batch_stats, self.tx,
                                       ema=cfg.optim.ema_decay > 0)
        # settle EVERY leaf's layout (step counter, optax counts/momentum)
        # to committed mesh shardings, not just the shard_params-placed
        # params: otherwise the first step returns a differently-placed
        # state and the SECOND step pays a full silent XLA recompile
        # (found by the pva_train_recompiles guard; parallel/sharding.py
        # shard_state)
        self.state = shard_state(self.mesh, self.state, tp=self._tp)
        # pva-tpu-hbm ledger: the settled TrainState IS the trainer's
        # standing device pin (params + optimizer moments + EMA +
        # batch_stats) — measured leaf bytes, not an estimate. Pretrained
        # loading below replaces values in the same tree, so the byte
        # count registered here stays truthful.
        obs_memory.register("train_state", obs_memory.tree_nbytes(self.state))

        if cfg.model.pretrained and not cfg.model.pretrained_path:
            # unlike the reference there is no runtime hub fetch (zero
            # network dependency in the training job) — a converted
            # artifact path is required, so say so instead of silently
            # training from scratch
            logger.warning(
                "--model.pretrained set but --model.pretrained_path empty: "
                "training from scratch. Convert a checkpoint first "
                "(pva-tpu-convert SRC.pth OUT.npz) and pass its path.")
        if cfg.model.pretrained and cfg.model.pretrained_path:
            from pytorchvideo_accelerate_tpu.models.convert import load_pretrained

            merged, report = load_pretrained(
                cfg.model.pretrained_path,
                {"params": self.state.params,
                 "batch_stats": self.state.batch_stats},
                mesh=self.mesh, model=cfg.model.name, tp=self._tp,
            )
            self.state = self.state.replace(
                params=merged["params"], batch_stats=merged["batch_stats"],
                # the EMA must start from the loaded weights, not the
                # discarded random init it was copied from at create()
                ema_params=(jax.tree.map(jnp.copy, merged["params"])
                            if self.state.ema_params is not None else None),
            )
            main_print(
                f"pretrained: loaded {len(report['loaded'])} tensors, "
                f"kept {len(report['kept'])} fresh"
            )
            if report.get("interpolated"):
                main_print("pretrained: pos-embed grid interpolated to this "
                           "run's geometry: "
                           + ", ".join(report["interpolated"]))
            mism = report.get("mismatched", [])
            # head paths by model family: .../head/... (resnet/slowfast,
            # mvit, videomae) or X3D's top-level params/proj — exact
            # anchors only, so e.g. an MViT block's attn/proj mismatch is
            # NOT mistaken for a head swap
            nonhead = [p for p in mism
                       if "/head/" not in p
                       and p not in ("params/proj/kernel", "params/proj/bias")]
            if mism:
                main_print(f"pretrained: {len(mism)} shape-mismatched leaves "
                           "kept fresh (expected for a swapped head): "
                           + ", ".join(mism[:4])
                           + ("..." if len(mism) > 4 else ""))
            if nonhead:
                logger.warning(
                    "pretrained artifact has %d NON-head shape mismatches "
                    "(stale artifact from an older layout? regenerate with "
                    "models.convert): %s",
                    len(nonhead), ", ".join(nonhead[:8]),
                )

        if self.task == "next_token":
            if self.pipeline_plan is not None:
                raise ValueError("the next-token step has no pipelined form")
            self.train_step = make_lm_step(
                self.model, self.tx, self.mesh,
                accum_steps=cfg.optim.gradient_accumulation_steps,
                lr_schedule=self.lr_schedule,
                debug_asserts=cfg.debug_asserts,
                ema_decay=cfg.optim.ema_decay,
                health_metrics=self.obs_on,
                guard_skip=cfg.guard.enabled,
            )
            self.eval_step = make_lm_eval_step(self.eval_model, self.mesh)
        elif self.task == "reconstruct":
            self.train_step = make_pretrain_step(
                self.model, self.tx, self.mesh,
                accum_steps=cfg.optim.gradient_accumulation_steps,
                lr_schedule=self.lr_schedule,
                debug_asserts=cfg.debug_asserts,
                ema_decay=cfg.optim.ema_decay,
                health_metrics=self.obs_on,
                guard_skip=cfg.guard.enabled,
                pipeline=self.pipeline_plan,
            )
            self.eval_step = make_pretrain_eval_step(self.eval_model,
                                                     self.mesh)
        else:
            self.train_step = make_train_step(
                self.model, self.tx, self.mesh,
                accum_steps=cfg.optim.gradient_accumulation_steps,
                label_smoothing=cfg.optim.label_smoothing,
                lr_schedule=self.lr_schedule,
                debug_asserts=cfg.debug_asserts,
                device_normalize=self._device_normalize,
                mixup_alpha=cfg.optim.mixup_alpha,
                cutmix_alpha=cfg.optim.cutmix_alpha,
                ema_decay=cfg.optim.ema_decay,
                health_metrics=self.obs_on,
                guard_skip=cfg.guard.enabled,
                pipeline=self.pipeline_plan,
            )
            self.eval_step = make_eval_step(
                self.eval_model, self.mesh,
                label_smoothing=cfg.optim.label_smoothing,
                device_normalize=self._device_normalize,
            )

    def register_for_checkpointing(self, name: str, obj) -> None:
        """Add a custom object to every checkpoint (reference
        `accelerator.register_for_checkpointing(lr_scheduler)`, run.py:199).

        `obj` must expose `state_dict() -> dict` (JSON-serializable) and
        `load_state_dict(dict)`; its state is saved with each checkpoint and
        restored on resume, keyed by `name`."""
        if not (callable(getattr(obj, "state_dict", None))
                and callable(getattr(obj, "load_state_dict", None))):
            raise TypeError(
                f"{type(obj).__name__} needs state_dict()/load_state_dict() "
                "methods to be registered for checkpointing"
            )
        self._registered[name] = obj

    # --- resume -----------------------------------------------------------

    def _maybe_resume(self) -> int:
        """Restore state + data position; returns starting epoch."""
        if not (self.cfg.checkpoint.resume_from_checkpoint and self.checkpointer):
            return 0
        latest = self.checkpointer.latest_step()
        if jax.process_count() > 1:
            # the directory scan can race across hosts (a checkpoint landing
            # mid-scan -> host A sees step 200, host B step 100 or none, and
            # the collective restore diverges or hangs): process 0's answer
            # is authoritative for the whole pod (-1 encodes "none")
            from pytorchvideo_accelerate_tpu.parallel.collectives import (
                host_broadcast,
            )

            latest = int(host_broadcast(  # pva: disable=host-sync -- resume-time host collective, once per run before the step loop
                np.int64(-1 if latest is None else latest)))
            latest = None if latest < 0 else latest
        if latest is None:
            if self.cfg.checkpoint.resume_from_checkpoint == "auto":
                main_print("resume=auto: no checkpoint found, starting fresh")
                return 0
            raise FileNotFoundError(
                f"no checkpoint to resume in {self.checkpointer.directory}"
            )
        self.state, extra, step = self.checkpointer.restore(
            self.state, step=latest, mesh=self.mesh, tp=self._tp
        )
        main_print(f"resumed from checkpoint step {step}")
        for name, obj in self._registered.items():
            if name in extra.get("registered", {}):
                obj.load_state_dict(extra["registered"][name])
        data_state = LoaderState.from_dict(extra.get("data_state"))
        # epoch-end checkpoints restart at the next epoch (reference
        # `epoch_{i} -> starting_epoch=i+1`, run.py:218-219); mid-epoch ones
        # fast-forward the loader position (run.py:221-224, but O(1))
        self.train_loader.state = data_state
        return data_state.epoch

    def export_inference(self, path: str) -> str:
        """Write the params-only (EMA-resolved) serving artifact for the
        CURRENT in-memory state — the checkpoint-to-endpoint handoff
        (trainer/checkpoint.export_inference; serve it with
        `pva-tpu-serve --serve.checkpoint PATH`). With
        `--serve.quantization int8` the artifact is baked int8 at export
        (per-channel absmax; docs/SERVING.md § quantization)."""
        from pytorchvideo_accelerate_tpu.trainer.checkpoint import (
            export_inference,
        )

        return export_inference(
            path, self.state, config=self.cfg,
            meta={"num_classes": self.num_classes,
                  "model": self.cfg.model.name},
            quantization=self.cfg.serve.quantization,
        )

    def close(self) -> None:
        """Release loaders/checkpointer/trackers without running fit()
        (export-only and aborted constructions)."""
        if self.trackers:
            self.trackers.finish()
            self.trackers = None
        if self.checkpointer is not None:
            self.checkpointer.close()
            self.checkpointer = None
        if self.train_guard is not None:
            self.train_guard.close()
        if self.watchdog is not None:
            uninstall_collective_watch()
            self.watchdog.stop()
            self.watchdog = None
        if self.train_feed is not None:
            self.train_feed.close()
        self.train_loader.close()
        self.val_loader.close()

    # --- fit ----------------------------------------------------------------

    def _save(self, kind: str, epoch: int) -> None:
        if self.checkpointer is None:
            return
        with obs.span("ckpt"):
            self._save_inner(kind, epoch)

    def _save_inner(self, kind: str, epoch: int) -> None:
        self.checkpointer.save(
            int(self.state.step),  # pva: disable=host-sync -- checkpoint save is a deliberate sync point (inside the "ckpt" span)
            self.state,
            {
                "kind": kind,
                "epoch": epoch,
                "data_state": self.train_loader.state.to_dict(),
                "num_classes": self.num_classes,
                "model": self.cfg.model.name,
                "registered": {n: o.state_dict()
                               for n, o in self._registered.items()},
            },
        )

    def _emergency_save(self, epoch: int, reason: str = "") -> None:
        """Preemption grace (reliability/preemption.py): persist the exact
        consumed position — an orbax checkpoint (kind "preempt", the
        loader's consumed position in `extra`) plus the atomic
        `emergency_checkpoint.json` breadcrumb — and dump the flight ring.
        Creates a checkpointer on demand when checkpointing was off: a
        preempted run must still be resumable with `resume=auto`."""
        cfg = self.cfg
        if self.checkpointer is None:
            self.checkpointer = Checkpointer(
                os.path.join(cfg.checkpoint.output_dir, "checkpoints"),
                max_to_keep=cfg.checkpoint.max_to_keep,
                use_async=False, retries=cfg.reliability.ckpt_retries,
                retry_base_delay_s=cfg.reliability.retry_base_delay_s,
                retry_max_delay_s=cfg.reliability.retry_max_delay_s,
                retry_deadline_s=cfg.reliability.retry_deadline_s,
            )
        step = int(self.state.step)  # pva: disable=host-sync -- preemption exit path, the run is over
        if self.checkpointer.latest_step() != step:
            # != instead of unconditional: a checkpointing_steps boundary
            # may have saved this very step already (orbax refuses a
            # duplicate step; the data is on disk either way)
            self._save("preempt", epoch)
        self.checkpointer.wait()  # ON DISK before the process may exit
        record_emergency(cfg.checkpoint.output_dir, step=step, epoch=epoch,
                         checkpoint_dir=self.checkpointer.directory,
                         reason=reason)
        if self.obs_on:
            recorder = obs.get_recorder()
            recorder.record("preempt", "emergency checkpoint saved",
                            step=step, epoch=epoch)
            recorder.dump()
        main_print(
            f"preempted ({reason or 'requested'}): emergency checkpoint at "
            f"step {step}; resume with --resume_from_checkpoint auto")

    def _guard_rollback(self, action) -> None:
        """Execute a TrainGuard rollback verdict: restore the last-known-
        good state through the mesh-portable restore path and fast-forward
        the loader PAST the offending span (the anomalous batch's consumed
        `LoaderState` — replaying the same span into the same divergence
        would be a rollback loop by construction)."""
        state, step = self.train_guard.restore(self.state, action)
        self.state = state
        self.train_loader.state = LoaderState.from_dict(
            action.resume_position)
        if self.obs_on:
            obs.get_recorder().record(
                "guard", "rollback", lkg_step=step,
                resume=dict(action.resume_position), reason=action.reason)
        main_print(
            f"guard: rolled back to last-known-good step {step} "
            f"({action.reason}); loader fast-forwarded to epoch "
            f"{self.train_loader.state.epoch} position "
            f"{self.train_loader.state.position}"
            + (f"; replay bundle: {action.bundle_path}"
               if action.bundle_path else ""))

    def _run_eval(self, epoch: int) -> tuple:
        """One pass over the val loader with in-graph masked metric sums
        (shared by fit()'s per-epoch eval and evaluate());
        returns (top1, top5, mean_loss)."""
        val = SumMetrics()
        if self.cfg.data.limit_val_batches == 0:
            # no eval asked for: no val batch is placed and the eval step
            # is never called, so it is never compiled either
            return val.accuracy(), val.accuracy_top5(), val.mean_loss()
        # from_start: eval is stateless — a prior early-broken pass (e.g.
        # limit_val_batches) must not make this one resume mid-epoch.
        # Batches arrive pre-placed on the mesh (device prefetch), so the
        # eval H2D transfers overlap eval compute the same way training's do.
        with obs.span("eval"):
            for step_in_epoch, batch in enumerate(
                    self.val_prefetch.epoch(epoch, from_start=True)):
                if self.watchdog is not None:
                    self.watchdog.heartbeat("train")
                val.update(self.eval_step(self.state, batch))
                if 0 <= self.cfg.data.limit_val_batches <= step_in_epoch + 1:
                    break
        return val.accuracy(), val.accuracy_top5(), val.mean_loss()

    def evaluate(self) -> dict:
        """Run the validation loop once, without training — for scoring a
        resumed or converted checkpoint (`--resume_from_checkpoint` /
        `--model.pretrained_path` decide the weights). Same in-graph masked
        metrics as fit()'s epoch eval; logs to the configured trackers and
        closes loaders/trackers before returning."""
        if not (self.cfg.checkpoint.resume_from_checkpoint
                or (self.cfg.model.pretrained
                    and self.cfg.model.pretrained_path)):
            logger.warning(
                "evaluate() without --resume_from_checkpoint or "
                "--model.pretrained_path: scoring freshly-initialized "
                "random weights — the result is meaningless.")
        try:
            if self.watchdog is not None:
                self.watchdog.start()  # re-arm after a prior fit()
                self.watchdog.heartbeat("train")
            self._maybe_resume()
            acc, acc5, loss = self._run_eval(epoch=0)
            if self.task == "reconstruct":
                result = {"val_recon_loss": loss}
                main_print(f"evaluate: val_recon_loss={loss:.4f}")
            else:
                result = {"val_accuracy": acc, "val_accuracy_top5": acc5,
                          "val_loss": loss}
                main_print(f"evaluate: val_acc={acc:.4f} val_acc5={acc5:.4f}")
            if self.trackers:
                self.trackers.log(result, step=int(self.state.step))  # pva: disable=host-sync -- evaluate() exit; metrics already drained this value's step
            return result
        finally:
            if self.trackers:
                self.trackers.finish()
            if self.checkpointer is not None:
                self.checkpointer.close()
            if self.watchdog is not None:
                self.watchdog.stop()
            if self.train_feed is not None:
                self.train_feed.close()
            self.train_loader.close()
            self.val_loader.close()

    def _profile_window(self, run_step: int) -> None:
        """--profile / --obs.profile_steps A..B: the run-relative capture
        window, published atomically as profile_steps_A_B/ by the one
        capture object (obs/profiler.py). Opens before step A is asked
        for and closes after step B-1's iteration has ended."""
        prof = obs.profiler.get_profiler()
        first, end = self.profile_steps
        if prof is None:
            return
        if run_step == first and not prof.busy:
            with obs.span("profile"):
                prof.start(tag=f"steps_{first}_{end}")
        elif run_step >= end and prof.busy:
            with obs.span("profile"):
                out = prof.stop()
            if out:
                main_print(f"profile window written to {out}")

    def _dump_step_records(self) -> Optional[str]:
        """Write fit()'s per-iteration records (one JSON object a line:
        gstep, t0_ns, and the seconds of iter / input_wait / step / log,
        ready) beside flight_record.json. Like the flight recorder's dump,
        a failed write is not worth dying over."""
        out_dir = self.cfg.checkpoint.output_dir
        if not self.obs_on or not out_dir or not self.step_records:
            return None
        path = os.path.join(out_dir, STEP_RECORDS_FILE)
        try:
            os.makedirs(out_dir, exist_ok=True)
            with open(path, "w") as f:
                f.writelines(json.dumps(r) + "\n"
                             for r in self.step_records)
        except OSError:
            return None
        return path

    def _obs_on_flush(self):
        """DeferredStepLogger hook mirroring the logged step metrics into
        the metric registry (obs/registry): grad/param-norm and
        update-ratio gauges plus a non-finite-loss counter. Sampled at
        log_every — a per-step host check would sync the async pipeline."""
        if not self.obs_on:
            return None
        reg = obs.get_registry()
        g_grad = reg.gauge("pva_train_grad_norm",
                           "global gradient norm (sampled at log_every)")
        g_param = reg.gauge("pva_train_param_norm",
                            "global parameter norm (sampled at log_every)")
        g_ratio = reg.gauge("pva_train_update_ratio",
                            "update-norm / param-norm (sampled at log_every)")
        c_nonfinite = reg.counter(
            "pva_train_nonfinite_loss_total",
            "non-finite loss values observed at log_every sampling")

        def on_flush(vals: Dict[str, float], step: int) -> None:
            if "grad_norm" in vals:
                g_grad.set(vals["grad_norm"])
            if "obs/param_norm" in vals:
                g_param.set(vals["obs/param_norm"])
            if "obs/update_ratio" in vals:
                g_ratio.set(vals["obs/update_ratio"])
            if vals.get("obs/nonfinite"):
                c_nonfinite.inc()
                obs.get_recorder().warn("non-finite loss", step=step)

        return on_flush

    def fit(self) -> dict:
        cfg = self.cfg
        starting_epoch = self._maybe_resume()
        steps_per_epoch = self.train_loader.steps_per_epoch()
        # host-side mirror of state.step: reading the device scalar
        # (int/float) every step would block on the step's result before
        # dispatching the next one, killing async-dispatch pipelining
        # (VERDICT r2 weak #4) — metrics are only fetched every `log_every`.
        # The ONE fetch here (fit() start, pre-loop) also seeds the progress
        # bar; it used to be fetched twice (pva-tpu-lint host-sync).
        gstep = int(self.state.step)  # pva: disable=host-sync -- one fetch at fit() start, before the step loop exists
        use_tqdm = is_main_process()
        if use_tqdm:
            from tqdm.auto import tqdm

            progress = tqdm(total=cfg.optim.num_epochs * steps_per_epoch,
                            initial=gstep)
        last_val_acc, last_train_loss = 0.0, float("nan")
        last_val_acc5, last_val_loss = 0.0, float("nan")
        last_perf: Dict[str, float] = {}
        # train-section wall time per epoch (excludes eval/ckpt; epoch 0
        # includes compile) — lets benchmarks measure steady-state throughput
        epoch_train_times = []

        # profile window is relative to THIS run's first step, so resumed
        # runs (gstep >> 0) still capture a trace
        run_start_step = gstep
        self.step_records.clear()
        metrics = None
        # metric logging is one step delayed: the fetch happens after the
        # NEXT step has been dispatched, so logging never syncs the step
        # just dispatched (the old float(metrics["loss"]) blocked dispatch
        # at every log_every boundary)
        deferred = (DeferredStepLogger(self.trackers,
                                       on_flush=self._obs_on_flush())
                    if self.trackers else None)
        # steady-state recompile guard (analysis/recompile_guard): armed
        # after the first step of THIS run (the legitimate compile), then
        # any jit-cache growth is a mid-training XLA compile stall. Sampled
        # at every log_every boundary + epoch end into the
        # `pva_train_recompiles` gauge; fit() reports the count as
        # `train_recompiles` (asserted 0 by the benchmark's `recompiles`
        # and tests/test_zgraphcheck.py) — the runtime teeth behind
        # pva-tpu-lint's static `recompile` rule.
        recompile_guard = RecompileGuard(self.train_step)
        # obs window accounting: the collector aggregates named spans; every
        # log_every boundary drains them into a per-window step-time
        # breakdown (obs/iter_s, obs/iter_self_s, obs/step_s,
        # obs/input_wait_s, ...) logged through the trackers
        collector = obs.get_collector() if self.obs_on else None
        loader_rows = LoaderRowCounts()
        # trace-time facts of the step, logged once, with the first window
        site_gauges = {"obs/conv_lane_fold_sites": "pva_conv_lane_fold_sites",
                       "obs/gdn_scan_kernel_sites": "pva_gdn_scan_kernel_sites",
                       "obs/attn_window_sites": "pva_attn_window_sites",
                       "obs/attn_kernel_sites": "pva_attn_kernel_sites",
                       "obs/attn_kept_sites": "pva_attn_kept_sites",
                       "obs/ssd_scan_sites": "pva_ssd_scan_sites",
                       "obs/ut_steps": "pva_ut_steps"}
        loop_thread = threading.get_ident()
        tokens_per_step = (
            self.train_loader.global_batch_size * self.train_loader.accum_steps
            * cfg.data.seq_len if self.task == "next_token" else 0)

        def drain_spans(log_step=None, window_wall=None):
            if collector is None:
                return
            window, self_by_thread = collector.drain()
            if log_step is None or not self.trackers or not window:
                return
            vals = {f"obs/{n}_s": t for n, (t, _c, _s) in window.items()}
            # a span that had children also reports its SELF time (its
            # duration less what they cover): obs/iter_self_s is the loop's
            # own Python and whatever took the GIL from it, measured
            vals.update({f"obs/{n}_self_s": s
                         for n, (t, _c, s) in window.items() if s < t})
            if window_wall is not None:
                # the self times of everything THIS thread recorded sum to
                # the wall time it spent inside any span; worker threads'
                # spans (h2d/batch/decode) overlap it and are reported,
                # not summed. What is left was outside every span: near
                # zero while the whole iteration is inside `iter`
                vals["obs/window_wall_s"] = window_wall
                vals["obs/unattributed_s"] = (
                    window_wall - self_by_thread.get(loop_thread, 0.0))
            # beside obs/batch_s: the share of the window's batch rows that
            # the clip source wrote in place (1.0 unless a source returns
            # arrays of its own for the worker to copy in)
            share = loader_rows.window_share()
            if share is not None:
                vals["obs/loader_rows_in_place_share"] = share
            while site_gauges:
                key, name = site_gauges.popitem()
                gauge = obs.get_registry().get(name)
                if gauge is not None:
                    vals[key] = gauge.value()
            if tokens_per_step and window_wall and "iter" in window:
                # a next-token model's rate over the window: the window's
                # iterations (the `iter` span's count), host arithmetic
                vals["obs/tokens_per_s"] = (
                    tokens_per_step * window["iter"][1] / window_wall)
            self.trackers.log(vals, step=log_step)

        if collector is not None:
            collector.drain()  # init/resume spans: not this window's
        if self.watchdog is not None:
            self.watchdog.start()  # re-arm after a prior fit/evaluate
            self.watchdog.heartbeat("train")
        # preemption grace (reliability/preemption.py): SIGTERM/SIGINT set
        # an Event; the step loop polls it once per step (no locks, no
        # syncs) and exits through the emergency-save path below. NOTE the
        # semantics change vs PR 3: with the guard installed, the first
        # signal no longer falls through to the flight recorder's re-raise
        # death — it drains gracefully; a second signal still kills.
        guard = get_guard() if cfg.reliability.graceful_shutdown else None
        if guard is not None:
            guard.install()
        preempted = False
        # self-healing guard (reliability/guard.py): observed one step
        # behind dispatch (the deferred-fetch discipline), escalating
        # skip -> rollback-to-LKG -> GuardHalt; None = one check per step
        tguard = self.train_guard
        hang_watch = self.watchdog  # collective-hang attribution source
        host_tag = hangcheck_host_tag() if hang_watch is not None else ""
        if hang_watch is not None and self.pipeline_plan is not None:
            # pipelined layout: the step's stage-boundary collectives
            # (ppermute rotations, the stage-output reduce) are what a
            # wedged dispatch is actually stuck in, so the section detail
            # names the stage slice this host computes — the dump then
            # reads "stage i/P" before the external kill
            host_tag = (f"{host_tag} "
                        f"stage={stage_tag(self.mesh)}").strip()
        # distributed tracing: hoisted armed check — disarmed, the step
        # loop pays one bool test per step (obs.trace.NOOP is shared)
        traced = obs.trace.get_tracer() is not None
        # per-stage span attribution (pipelined runs): sampled train_step
        # trace roots carry the stage slice this process computes, so a
        # merged multi-host timeline separates stage timing without any
        # extra span nesting (nested consumer spans would double-count in
        # the window sum-to-wall contract)
        trace_tags = ({"stage": stage_tag(self.mesh)}
                      if self.pipeline_plan is not None else {})
        window_t0 = time.perf_counter()
        try:
            # while (not for): a guard rollback restores an EARLIER
            # (state, loader) position mid-epoch and re-enters the same —
            # or a previous — epoch from the fast-forwarded position
            epoch = starting_epoch
            while epoch < cfg.optim.num_epochs:
                if use_tqdm:
                    progress.set_description_str(f"Epoch: {epoch}")
                epoch_loss = MeanLoss()
                t_epoch = time.time()
                train_steps_this_epoch = 0
                rolled_back = False
                self.train_prefetch.pop_wait()  # epoch-scoped accounting
                # discard inter-epoch spans (epoch-end ckpt save, teardown):
                # they precede this epoch's first window and would otherwise
                # surface as a negative obs/unattributed_s in it
                drain_spans()
                window_t0 = time.perf_counter()

                # batches arrive pre-placed on the mesh: the device prefetch
                # thread overlaps the H2D copy of batch N+1 with compute of
                # batch N, so steady-state steps never block on the host link
                batches = iter(self.train_prefetch.epoch(epoch))
                step_in_epoch = -1
                try:
                    while True:
                        step_in_epoch += 1
                        if self.profile_steps is not None:
                            # between two iterations, so that every
                            # iteration of the window is whole in the trace
                            self._profile_window(gstep - run_start_step)
                        # ONE span per iteration, from asking for the next
                        # batch to the bottom of the body, carrying gstep:
                        # input_wait (inside the prefetcher), step, log,
                        # ckpt are its children and inherit the id; its
                        # self time is the loop's own. A sampled iteration
                        # is also a distributed-trace root tagged (epoch,
                        # gstep) — the coordinates the profiler's
                        # StepTraceAnnotation carries, so a merged timeline
                        # and an XLA trace correlate by gstep
                        iter_span = obs.span("iter", step=gstep)
                        iter_root = (obs.trace.root("train_iter", epoch=epoch,
                                                    gstep=gstep, **trace_tags)
                                     if traced else obs.trace.NOOP)
                        step_span = log_span = None
                        try:
                            with iter_root, iter_span:
                                ready = self.train_prefetch.ready()
                                wait_before = self.train_prefetch.wait_s
                                global_batch = next(batches, None)
                                if global_batch is None:
                                    # the epoch ran out: step `gstep` is not
                                    # of this epoch, so neither is an `iter`
                                    # (the wait stays in `input_wait`)
                                    iter_span.discard()
                                    iter_root.drop()
                                    break
                                waited = (self.train_prefetch.wait_s
                                          - wait_before)
                                if self.watchdog is not None:
                                    self.watchdog.heartbeat("train")
                                # chaos hook: "delay" = a slow dispatch,
                                # "raise" = a failing one, "nan" = poison
                                # the dispatched batch (the numeric
                                # divergence the guard ladder recovers
                                # from). Disarmed: one global read.
                                if fault_point("step.dispatch") == "nan":
                                    global_batch = poison_batch(global_batch)
                                # "step" span = dispatch time; under async
                                # dispatch it absorbs compute only when the
                                # dispatch queue pushes back (or at
                                # compile), which is exactly the reading
                                # that matters for the per-window
                                # breakdown. With a watchdog live,
                                # STEADY-STATE dispatches also run inside
                                # an attributed "collective" section: queue
                                # push-back from a wedged mesh collective
                                # then dumps per-host evidence instead of
                                # anonymous silence. The first dispatch
                                # (the legitimate minutes-long XLA compile)
                                # is deliberately unwatched — attributing
                                # it would be the exact wedged-collective
                                # misverdict this detector exists to
                                # prevent; any LATER slow dispatch is
                                # either a real wedge or a recompile the
                                # recompile guard flags anyway.
                                with (hang_watch.section(
                                        "collective",
                                        f"step_dispatch {host_tag} "
                                        f"gstep={gstep}")
                                      if hang_watch is not None
                                      and recompile_guard.armed
                                      else nullcontext()):
                                    with obs.span("step") as step_span:
                                        with jax.profiler.StepTraceAnnotation(
                                                "train", step_num=gstep):
                                            self.state, metrics = \
                                                self.train_step(
                                                    self.state, global_batch,
                                                    self.rng.step_key(gstep))
                                gstep += 1
                                train_steps_this_epoch += 1
                                if not recompile_guard.armed:
                                    # the first dispatch has returned, so
                                    # its trace + compile are done:
                                    # everything past this baseline is a
                                    # steady-state recompile
                                    recompile_guard.arm()
                                if deferred is not None:
                                    # previous boundary's metrics: their
                                    # step has retired behind the one just
                                    # dispatched, so this fetch doesn't
                                    # stall the pipeline
                                    with obs.span("log") as log_span:
                                        deferred.flush()
                                if tguard is not None:
                                    # observe the PREVIOUS step's metrics
                                    # (retired behind the dispatch above —
                                    # never a pipeline stall) and stash
                                    # this one; a rollback verdict breaks
                                    # out, GuardHalt raises through
                                    action = tguard.step(
                                        gstep, metrics, global_batch,
                                        self.train_loader.state, self.state)
                                    if action is not None:
                                        self._guard_rollback(action)
                                        rolled_back = True
                                        break
                                if use_tqdm:
                                    progress.update(1)
                                # device scalar; the host->device sync
                                # happens at epoch end (MeanLoss.mean) or at
                                # the deferred log_every fetch
                                epoch_loss.update_async(metrics["loss"])
                                if (deferred is not None
                                        and gstep % cfg.tracking.log_every == 0):
                                    vals = {"train_loss_step": metrics["loss"],
                                            "lr": metrics["lr"],
                                            "grad_norm": metrics["grad_norm"]}
                                    if self.obs_on:
                                        # on-device health gauges ride the
                                        # same deferred fetch (steps.py
                                        # health_metrics)
                                        vals["obs/param_norm"] = \
                                            metrics["param_norm"]
                                        vals["obs/update_ratio"] = \
                                            metrics["update_ratio"]
                                        vals["obs/nonfinite"] = \
                                            metrics["nonfinite"]
                                    if self.task == "next_token":
                                        # the step's own counters, same
                                        # fetch (none under accumulation)
                                        vals.update(lm_log_values(metrics))
                                    deferred.defer(vals, step=gstep)
                                if (isinstance(self.checkpointing_steps, int)
                                        and gstep % self.checkpointing_steps
                                        == 0):
                                    self._save("step", epoch)
                                    main_print(
                                        f"saved checkpoint at step {gstep}")
                                if guard is not None and guard.requested:
                                    # finish-the-step-then-leave: the
                                    # dispatch above has returned, so
                                    # breaking here never abandons an
                                    # in-flight optimizer update
                                    preempted = True
                                    break
                                if (0 <= cfg.data.limit_train_batches
                                        <= step_in_epoch + 1):
                                    break
                                # every observer sees every step, also the
                                # one at which another asks for the end
                                if [o for o in self.step_observers
                                        if o(gstep, self)]:
                                    break
                        finally:
                            if step_span is not None and self.obs_on:
                                # the iteration reached its dispatch: its
                                # record (written by this thread only)
                                self.step_records.append({
                                    "gstep": iter_span.step,
                                    "t0_ns": iter_span.t0_ns,
                                    "iter": iter_span.dur_s,
                                    "input_wait": waited,
                                    "step": step_span.dur_s,
                                    "log": (log_span.dur_s if log_span
                                            is not None else 0.0),
                                    "ready": ready,
                                })
                            if self.profile_steps is not None:
                                # step B-1's iteration has ended: close the
                                # capture here, also where a `break` leaves
                                # the loop and no iteration follows (the
                                # trace would stay open through eval)
                                self._profile_window(gstep - run_start_step)
                        # a log window closes BETWEEN two iterations, so
                        # that every `iter` span lies wholly in one window
                        if self.obs_on and gstep % cfg.tracking.log_every == 0:
                            now = time.perf_counter()
                            drain_spans(log_step=gstep,
                                        window_wall=now - window_t0)
                            window_t0 = now
                            recompile_guard.sample()  # refresh the gauge
                            # append a scrape tick to the bounded history
                            # ring (obs/history.py) — the ledger's live
                            # gauges land in the same tick, so hbm series
                            # accrue for free
                            hist = obs.history.get_history()
                            if hist is not None:
                                hist.tick()
                finally:
                    # stops the prefetch worker and frees its device
                    # batches now, not when the generator is collected
                    batches.close()
                if preempted:
                    # grace path: sync the last step's result, flush the
                    # pending log, persist, and leave — no eval, no
                    # further epochs, exit 0 (resume=auto lands here)
                    if metrics is not None:
                        with obs.span("sync"):
                            fetch_loss(metrics)
                    if deferred is not None:
                        deferred.flush()
                    self._emergency_save(
                        epoch, reason=guard.reason if guard else "")
                    break
                if metrics is not None and not rolled_back:
                    # value-fetch sync, never block_until_ready (acked
                    # early by forwarding backends — would end the epoch
                    # timer with work still queued; bench_setup.fetch_loss).
                    # Watched: a straggler host wedges HERE, so the hang
                    # detector attributes the fetch per host.
                    with obs.span("sync"):
                        with collective_section("epoch_sync", step=gstep):
                            fetch_loss(metrics)
                if deferred is not None:
                    with obs.span("log"):
                        deferred.flush()
                if tguard is not None and not rolled_back:
                    # the last step of the epoch is still pending in the
                    # guard; an anomaly there must not slip into the next
                    # epoch's LKG window unobserved
                    action = tguard.flush(self.state,
                                          self.train_loader.state)
                    if action is not None:
                        self._guard_rollback(action)
                        rolled_back = True
                if rolled_back:
                    # resume from the restored LKG: the loader already
                    # points PAST the offending span; restart window/span
                    # accounting so the next epoch's breakdown stays pure
                    gstep = int(self.state.step)  # pva: disable=host-sync -- anomaly-recovery path, once per rollback
                    metrics = None
                    drain_spans()
                    window_t0 = time.perf_counter()
                    epoch = self.train_loader.state.epoch
                    continue
                epoch_train_times.append(time.time() - t_epoch)
                # time the step loop spent blocked waiting for the next
                # device batch — the number that proves (or disproves) the
                # transfer/compute overlap (input_wait_frac << 1)
                train_wait_s = self.train_prefetch.pop_wait()
                if self.obs_on:
                    # close the train section's residual window before eval
                    # so train windows stay pure (the sum-to-wall property
                    # only holds for windows without overlapping eval spans)
                    now = time.perf_counter()
                    drain_spans(log_step=gstep, window_wall=now - window_t0)
                    window_t0 = now

                # Evaluation (reference run.py:287-304, in-graph metric sums)
                last_val_acc, last_val_acc5, last_val_loss = \
                    self._run_eval(epoch)
                if self.obs_on:
                    # eval window: logged for the timeline (obs/eval_s), no
                    # sum contract (eval nests its own input waits)
                    now = time.perf_counter()
                    drain_spans(log_step=gstep)
                    window_t0 = now
                last_train_loss = epoch_loss.mean()
                val_str = (
                    f"val_recon_loss={last_val_loss:.4f}"
                    if self.task == "reconstruct"
                    else f"val_acc={last_val_acc:.4f} "
                         f"val_acc5={last_val_acc5:.4f}"
                )
                main_print(
                    f"epoch {epoch}: {val_str} "
                    f"train_loss={last_train_loss:.4f} "
                    f"({time.time() - t_epoch:.1f}s)"
                )
                # epoch throughput — computed unconditionally so fit()'s
                # return dict carries it even without --with_tracking
                steps_done = train_steps_this_epoch
                t_train = epoch_train_times[-1]
                if t_train > 0 and steps_done > 0:
                    sps = steps_done / t_train
                    last_perf = {
                        "steps_per_sec": sps,
                        "clips_per_sec": (
                            sps * self.train_loader.global_batch_size
                            * self.train_loader.accum_steps
                        ),
                        # fraction of the train section blocked on input:
                        # << 1 = the H2D overlap is real; -> 1 = the input
                        # pipeline (host decode or transfer), not the model,
                        # bounds throughput
                        "input_wait_s": train_wait_s,
                        "input_wait_frac": min(train_wait_s / t_train, 1.0),
                    }
                    # jit-cache growth since the post-first-step baseline;
                    # 0 is the only healthy steady-state reading. None =
                    # probe unavailable (future jax without _cache_size):
                    # the key stays present so consumers see "unknown"
                    # instead of a missing-key failure, and never a lying 0
                    last_perf["train_recompiles"] = recompile_guard.sample()
                    if self.pipeline_plan is not None:
                        # the analytic schedule numbers (a single run
                        # can't separate fill/drain idle from per-tick
                        # compute, so no measured bubble here)
                        plan = self.pipeline_plan
                        # host ints by construction (PipelinePlan fields)
                        last_perf["pipeline_stages"] = plan.stages
                        last_perf["pipeline_microbatches"] = (
                            plan.microbatches)
                        last_perf["pipeline_bubble_frac_analytic"] = (
                            analytic_bubble_frac(plan.stages,
                                                 plan.microbatches))
                        last_perf["pipeline_cps_per_chip"] = (
                            last_perf["clips_per_sec"] / self.mesh.size)
                        if self.obs_on:
                            obs.get_registry().gauge(
                                "pva_pipeline_bubble_frac",
                                "pipeline fill/drain idle fraction, "
                                "analytic (P-1)/(M+P-1)",
                            ).set(last_perf["pipeline_bubble_frac_analytic"])
                    if tguard is not None:
                        # guard verdicts ride the perf dict; a clean run
                        # reads 0 for both
                        last_perf.update(tguard.perf_keys())
                if self.trackers:
                    epoch_metrics = {"train_loss_epoch": last_train_loss,
                                     "epoch": epoch}
                    if self.task == "reconstruct":
                        epoch_metrics["val_recon_loss"] = last_val_loss
                    else:
                        epoch_metrics["accuracy"] = last_val_acc
                        epoch_metrics["accuracy_top5"] = last_val_acc5
                    epoch_metrics.update(last_perf)
                    self.trackers.log(epoch_metrics, step=epoch)
                if cfg.debug_desync:
                    import optax

                    from pytorchvideo_accelerate_tpu.parallel.distributed import (
                        check_desync,
                    )

                    check_desync(float(optax.global_norm(self.state.params)),  # pva: disable=host-sync -- opt-in --debug_desync path, once per epoch end
                                 name=f"params@epoch{epoch}")
                if self.checkpointing_steps == "epoch":
                    self._save("epoch", epoch)
                epoch += 1

        except BaseException as e:
            # the flight recorder's whole purpose: the recent span/metric
            # timeline survives the crash as <output_dir>/flight_record.json
            # (complementing the partial-profile flush below)
            if self.obs_on:
                recorder = obs.get_recorder()
                recorder.record("exception", type(e).__name__,
                                message=str(e)[:500], step=gstep)
                recorder.dump()  # install(output_dir) set the destination
            raise  # pva: disable=spmd-divergence -- crash path: this host is already dying; surviving hosts wedge ATTRIBUTABLY in their next hangcheck section
        finally:
            # flush a partial trace even when the run dies mid-window —
            # that trace is most valuable exactly when diagnosing a crash;
            # stop() still publishes atomically
            if self.profile_steps is not None:
                prof = obs.profiler.get_profiler()
                if prof is not None and prof.busy:
                    prof.stop()
            # the distributed-trace ring lands next to the flight record
            # (<output_dir>/trace_ring.json) on clean exit AND on a crash;
            # no-op when tracing is disarmed
            obs.trace.dump()
            self._dump_step_records()
            if self.watchdog is not None:
                self.watchdog.clear("train")
                self.watchdog.stop()
            if guard is not None:
                guard.uninstall()  # restore the pre-fit signal handlers

        if self.trackers:
            self.trackers.finish()
        # final save (reference run.py:325, minus its NameError footgun);
        # a preempted run already persisted this exact step
        if not preempted:
            self._save("final", cfg.optim.num_epochs - 1)
        if self.checkpointer:
            self.checkpointer.close()
        if tguard is not None:
            tguard.close()  # fence the LKG ring's async saves
        if use_tqdm:
            progress.close()
        if self.train_feed is not None:
            self.train_feed.close()
        self.train_loader.close()
        self.val_loader.close()
        result = {"train_loss": last_train_loss, "steps": int(self.state.step),  # pva: disable=host-sync -- fit() exit: training is over, the sync is free
                  "epoch_train_times": epoch_train_times,
                  "preempted": preempted,
                  "step_records": list(self.step_records),
                  **last_perf}
        if self.task == "reconstruct":
            result["val_recon_loss"] = last_val_loss
        else:
            result["val_accuracy"] = last_val_acc
            result["val_accuracy_top5"] = last_val_acc5
        return result

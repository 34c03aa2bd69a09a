"""Shared train-step scaffolding for the tools that build a step.

analysis/graphcheck.py (jaxpr/HLO passes), utils/memfit.py (compile-time
batch fitting) and chip_smoke.py (bring-up) all need the same setup:
model from the registry, synthetic host batch of the right family shape
(slowfast dual-pathway vs single clip; label unless pretraining; optional
micro-batch axis), init, optimizer state, compiled step. One builder keeps
the tools looking at the same thing — family/batch-layout changes
land here once.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional


def is_pretrain_model(model_name: str) -> bool:
    return model_name.endswith("_pretrain")


@dataclass
class StepSetup:
    model: Any
    mesh: Any
    state: Any
    step: Callable  # jitted (state, batch, rng) -> (state, metrics)
    n_chips: int
    global_batch: int
    host_batch: Callable[[int], dict]  # seed -> host numpy batch
    device_batch: Callable[[int], Any]  # seed -> mesh-sharded batch
    pretrain: bool
    input_u8: bool = False  # effective (clamped off for pretrain)
    tx: Any = None  # optimizer, for callers that rebuild step variants
    #               (graphcheck's guard-armed donation probe)


def build_step_setup(
    model_name: str,
    *,
    frames: int,
    crop: int,
    batch_per_chip: int,
    num_classes: int = 700,
    alpha: int = 4,
    accum: int = 1,
    pretrain: Optional[bool] = None,  # None = infer from the name
    overrides: Optional[dict] = None,
    devices=None,
    total_steps: int = 30,
    fill: str = "random",  # random | zeros (compile-only callers: zeros
    #                        pages are calloc'd, no RNG cost at big batches)
    input_u8: bool = False,  # raw-u8 batches + in-graph normalize (the
    #                          host_cast=u8 production path; supervised only)
    mesh_cfg=None,  # MeshConfig for a non-default layout (e.g. the 2-D
    #                 (data, model) shapes graphcheck's pipelined target uses)
    mixed_precision: str = "bf16",  # "fp32" for numerics probes (bf16
    #                 summation-order noise compounds across update steps)
    global_batch: Optional[int] = None,  # fixed TOTAL batch instead of
    #                 batch_per_chip * n_chips — the mesh-parity lane needs
    #                 the identical batch on every mesh shape
    pipeline_stages: int = 1,  # >1: run the transformer trunk as a P-stage
    #                 SPMD pipeline over the mesh's model axis
    #                 (parallel/pipeline.py; stages must equal that axis's
    #                 size — pass a matching mesh_cfg). Params stay
    #                 replicated over the stage axis (no TP).
    pipeline_microbatches: int = 0,  # 0 = auto (accum when >1, else 2P)
) -> StepSetup:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import (
        DataConfig, MeshConfig, ModelConfig, OptimConfig,
    )
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import (
        data_shard_count,
        make_train_mesh,
    )
    from pytorchvideo_accelerate_tpu.parallel.sharding import (
        family_uses_tp,
        shard_batch,
        shard_state,
    )
    from pytorchvideo_accelerate_tpu.trainer import (
        TrainState, build_optimizer, make_pretrain_step, make_train_step,
    )

    if pretrain is None:
        pretrain = is_pretrain_model(model_name)
    input_u8 = input_u8 and not pretrain  # MAE target needs the f32 clip
    cfg = ModelConfig(name=model_name, num_classes=num_classes,
                      slowfast_alpha=alpha, **(overrides or {}))
    if devices is None:
        devices = jax.devices()
    n_chips = len(devices)
    # the trainer's backbone layout (2-D (data, model) train mesh); a
    # legacy MeshConfig still resolves to the 4-axis library mesh
    mesh = make_train_mesh(mesh_cfg or MeshConfig(), devices=devices)
    plan = None
    if pipeline_stages > 1:
        from pytorchvideo_accelerate_tpu.parallel.pipeline import (
            make_plan as make_pipeline_plan,
        )

        plan = make_pipeline_plan(mesh, pipeline_stages,
                                  microbatches=pipeline_microbatches,
                                  accum_steps=accum)
    model = create_model(cfg, mixed_precision, mesh=None, pipeline=plan)
    B = global_batch if global_batch is not None else batch_per_chip * n_chips
    if B % data_shard_count(mesh):
        raise ValueError(
            f"global batch {B} must divide the mesh's "
            f"{data_shard_count(mesh)} data shards")

    if accum > 1 and B % accum:
        raise ValueError(
            f"global batch {B} ({batch_per_chip}/chip x {n_chips}) must be "
            f"divisible by accum={accum}")

    def host_batch(seed: int) -> dict:
        r = np.random.default_rng(seed)

        def clips(shape):
            if input_u8:
                # raw-u8 batches (the --data.host_cast u8 production path):
                # 4x fewer bytes over the host->device link, with the
                # normalize affine applied in-graph by the step
                if fill == "zeros":
                    return np.zeros(shape, np.uint8)
                return r.integers(0, 256, shape, np.uint8)
            if fill == "zeros":
                return np.zeros(shape, np.float32)
            return r.standard_normal(shape, dtype=np.float32)

        if model_name.startswith("slowfast"):
            b = {
                "slow": clips((B, frames // alpha, crop, crop, 3)),
                "fast": clips((B, frames, crop, crop, 3)),
            }
        else:
            b = {"video": clips((B, frames, crop, crop, 3))}
        if not pretrain:
            b["label"] = r.integers(0, num_classes, B).astype(np.int32)
        if accum > 1:
            b = {k: v.reshape(accum, B // accum, *v.shape[1:])
                 for k, v in b.items()}
        return b

    def device_batch(seed: int):
        return shard_batch(mesh, host_batch(seed), micro_dim=accum > 1)

    # model init sample: shapes are arithmetic — no need to materialize a
    # full batch just to read them
    if model_name.startswith("slowfast"):
        sample = (jnp.zeros((1, frames // alpha, crop, crop, 3)),
                  jnp.zeros((1, frames, crop, crop, 3)))
    else:
        sample = jnp.zeros((1, frames, crop, crop, 3))
    variables = model.init(jax.random.key(0), sample)
    tx = build_optimizer(OptimConfig(), total_steps=total_steps)
    # shard_state, not raw create: uncommitted single-device leaves would
    # make the measured step's SECOND call recompile (layout settling),
    # corrupting the warmup accounting — same fix as Trainer's. The tp
    # flag mirrors the trainer's per-family model-axis decision.
    state = shard_state(mesh, TrainState.create(
        variables["params"], variables.get("batch_stats", {}), tx),
        tp=family_uses_tp(model_name) and plan is None)
    if pretrain:
        step = make_pretrain_step(model, tx, mesh, accum_steps=accum,
                                  pipeline=plan)
    else:
        d = DataConfig()  # canonical mean/std — the stats the u8
        #                   production path normalizes with
        step = make_train_step(
            model, tx, mesh, accum_steps=accum,
            device_normalize=(d.mean, d.std) if input_u8 else None,
            pipeline=plan,
        )
    return StepSetup(model=model, mesh=mesh, state=state, step=step,
                     n_chips=n_chips, global_batch=B, host_batch=host_batch,
                     device_batch=device_batch, pretrain=pretrain,
                     input_u8=input_u8, tx=tx)


def fetch_loss(metrics) -> float:
    """Value-fetch sync for timed loops: a timed window must end with the
    device's work done, and a sync that returns early reads as an
    impossible rate (earlier rounds recorded 430%+ "MFU" that way). A
    device->host transfer of the loss scalar's bytes cannot complete
    early, and the step-state chain means the last loss implies every
    prior step executed. Fetched values are cached per-array, so callers
    must pass a FRESH array each time (each step's metrics are)."""
    import numpy as np

    return float(np.asarray(metrics["loss"]))

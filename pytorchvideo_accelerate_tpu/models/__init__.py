"""Model zoo + registry.

Replaces the reference's torch.hub model fetch + finetuner builders
(run.py:105-118): `create_model(cfg)` returns a Flax module; pretrained
weights come from the torch->Flax converter (models/convert.py) via
`ModelConfig.pretrained_path` instead of a network hub call.
"""

from __future__ import annotations

import dataclasses
import inspect

from typing import Callable, Dict

import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.config import ModelConfig
from pytorchvideo_accelerate_tpu.models.heads import ResBasicHead  # noqa: F401
from pytorchvideo_accelerate_tpu.models.resnet3d import SlowR50
from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast
from pytorchvideo_accelerate_tpu.models.x3d import X3D
from pytorchvideo_accelerate_tpu.models.r2plus1d import R2Plus1D
from pytorchvideo_accelerate_tpu.models.csn import CSN
from pytorchvideo_accelerate_tpu.models.mvit import MViT
from pytorchvideo_accelerate_tpu.models.videomae import (  # noqa: F401
    VideoMAEClassifier,
    VideoMAEForPretraining,
)

_REGISTRY: Dict[str, Callable] = {}
# what a model is trained to do, declared where it is registered and read by
# the trainer (never off the model's name): "classify" (clips and a label),
# "reconstruct" (clips, the model returns its own loss), "next_token"
# (token sequences, the model returns its summed cross-entropy)
TASKS = ("classify", "reconstruct", "next_token")
_TASKS: Dict[str, str] = {}


def register_model(name: str, task: str = "classify"):
    if task not in TASKS:
        raise ValueError(f"task must be one of {TASKS}, got {task!r}")

    def deco(fn):
        _REGISTRY[name] = fn
        _TASKS[name] = task
        return fn

    return deco


def model_task(name: str) -> str:
    """The registered model's task; an unknown name is `create_model`'s to
    refuse, so it reads as the default here."""
    return _TASKS.get(name, "classify")


@register_model("slow_r50")
def _slow_r50(cfg: ModelConfig, dtype, mesh=None):
    return SlowR50(
        num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
        fused=cfg.fused_kernels, dtype=dtype
    )


@register_model("tiny3d")
def _tiny3d(cfg: ModelConfig, dtype, mesh=None):
    """Deliberately tiny Slow-style net for integration tests / CLI smokes
    (compiles in seconds on a CPU host; not a reference architecture)."""
    return SlowR50(
        num_classes=cfg.num_classes, depths=(1, 1, 1, 1), stem_features=8,
        dropout_rate=cfg.dropout_rate, fused=cfg.fused_kernels, dtype=dtype,
    )


@register_model("slowfast_r50")
def _slowfast_r50(cfg: ModelConfig, dtype, mesh=None):
    return SlowFast(
        num_classes=cfg.num_classes,
        alpha=cfg.slowfast_alpha,
        dropout_rate=cfg.dropout_rate,
        fused=cfg.fused_kernels,
        dtype=dtype,
    )


@register_model("slowfast_t")
def _slowfast_t(cfg: ModelConfig, dtype, mesh=None):
    """Deliberately tiny SlowFast (the `tiny3d` of the dual-pathway
    family): one block per stage, 16-channel stem — the dual-rate
    streaming-ring tests and chaos legs compile it in seconds on a CPU
    host. Not a reference architecture."""
    return SlowFast(
        num_classes=cfg.num_classes, depths=(1, 1, 1, 1),
        stem_features=16,
        alpha=cfg.slowfast_alpha,
        dropout_rate=cfg.dropout_rate,
        fused=cfg.fused_kernels,
        dtype=dtype,
    )


@register_model("slowfast_r101")
def _slowfast_r101(cfg: ModelConfig, dtype, mesh=None):
    return SlowFast(
        num_classes=cfg.num_classes,
        depths=(3, 4, 23, 3),
        alpha=cfg.slowfast_alpha,
        dropout_rate=cfg.dropout_rate,
        fused=cfg.fused_kernels,
        dtype=dtype,
    )


@register_model("x3d_xs")
def _x3d_xs(cfg: ModelConfig, dtype, mesh=None):
    return X3D(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
               depthwise_impl=cfg.depthwise_impl, fused=cfg.fused_kernels,
               dtype=dtype)


@register_model("x3d_s")
def _x3d_s(cfg: ModelConfig, dtype, mesh=None):
    # XS and S share the trunk; they differ in sampling (13f@160px for S)
    return X3D(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
               depthwise_impl=cfg.depthwise_impl, fused=cfg.fused_kernels,
               dtype=dtype)


@register_model("x3d_m")
def _x3d_m(cfg: ModelConfig, dtype, mesh=None):
    return X3D(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
               depthwise_impl=cfg.depthwise_impl, fused=cfg.fused_kernels,
               dtype=dtype)


@register_model("x3d_l")
def _x3d_l(cfg: ModelConfig, dtype, mesh=None):
    # depth-factor 5.0 trunk (pytorchvideo create_x3d stage depths
    # (1,2,5,3) x 5.0 -> (5,10,25,15)); sampled 16f@312px in the paper
    return X3D(num_classes=cfg.num_classes, depths=(5, 10, 25, 15),
               dropout_rate=cfg.dropout_rate,
               depthwise_impl=cfg.depthwise_impl, fused=cfg.fused_kernels,
               dtype=dtype)


@register_model("c2d_r50")
def _c2d_r50(cfg: ModelConfig, dtype, mesh=None):
    """Hub `c2d_r50` (Kinetics-400 8x8): the create_resnet skeleton with
    NO temporal convolutions anywhere — slow_r50 with all-1 temporal
    kernels (per-frame 2D convs batched over time; parameter count 24.3M
    = the published hub figure) plus the builder's parameterless (2,1,1)
    temporal max-pool after res2. models/resnet3d.py."""
    return SlowR50(
        num_classes=cfg.num_classes, temporal_kernels=(1, 1, 1, 1),
        stage1_temporal_pool=True,
        dropout_rate=cfg.dropout_rate, fused=cfg.fused_kernels, dtype=dtype,
    )


@register_model("csn_r101")
def _csn_r101(cfg: ModelConfig, dtype, mesh=None):
    """Hub `csn_r101` (ir-CSN-101, Kinetics-400 32x2); models/csn.py."""
    return CSN(
        num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
        depthwise_impl=cfg.depthwise_impl, fused=cfg.fused_kernels,
        dtype=dtype,
    )


@register_model("r2plus1d_r50")
def _r2plus1d_r50(cfg: ModelConfig, dtype, mesh=None):
    """Hub `r2plus1d_r50` (Kinetics-400 16x4); models/r2plus1d.py."""
    return R2Plus1D(
        num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
        fused=cfg.fused_kernels, dtype=dtype,
    )


@register_model("mvit_b")
def _mvit_b(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    if cfg.attention not in ("dense", "pallas", "ring", "ulysses"):
        raise NotImplementedError(
            f"attention backend {cfg.attention!r} not available for mvit_b"
        )
    return MViT(
        num_classes=cfg.num_classes,
        dropout_rate=cfg.dropout_rate,
        attention_backend=cfg.attention,
        context_mesh=mesh if cfg.attention in ("ring", "ulysses") else None,
        shard_mesh=mesh,  # block-boundary activation anchors (GSPMD)
        pipeline=pipeline,  # SPMD stage pipeline (parallel/pipeline.py)
        depthwise_impl=cfg.depthwise_impl,
        remat=cfg.remat,
        dtype=dtype,
    )


@register_model("mvit_b_32x3")
def _mvit_b_32x3(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    """Hub `mvit_base_32x3` (32 frames x stride 3): structurally the same
    MViT-B — the pos embeds are input-sized, so only the training recipe
    (drop_path 0.3) and sampling geometry differ. Run with
    --num_frames 32 --sampling_rate 3."""
    return _mvit_b(cfg, dtype, mesh=mesh,
                   pipeline=pipeline).clone(drop_path_rate=0.3)


@register_model("videomae_b")
def _videomae_b(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    """Fine-tune path of BASELINE config 5 (SSv2/K400 classification)."""
    return VideoMAEClassifier(
        num_classes=cfg.num_classes,
        dropout_rate=cfg.dropout_rate,
        attention_backend=cfg.attention,
        context_mesh=mesh if cfg.attention in ("ring", "ulysses") else None,
        shard_mesh=mesh,  # block-boundary activation anchors (GSPMD)
        pipeline=pipeline,  # SPMD stage pipeline (parallel/pipeline.py)
        remat=cfg.remat,
        attn_mask=cfg.attn_mask,  # banded trunk (streaming KV reuse)
        attn_window=cfg.attn_window,
        dtype=dtype,
    )


@register_model("videomae_b_pretrain", task="reconstruct")
def _videomae_b_pretrain(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    """MAE pretraining path of BASELINE config 5 (self-supervised; the
    reference stack has no SSL path — run.py is supervised-only)."""
    return VideoMAEForPretraining(
        mask_ratio=cfg.mask_ratio,
        attention_backend=cfg.attention,
        context_mesh=mesh if cfg.attention in ("ring", "ulysses") else None,
        shard_mesh=mesh,  # block-boundary activation anchors (GSPMD)
        pipeline=pipeline,  # SPMD stage pipeline (parallel/pipeline.py)
        remat=cfg.remat,
        dtype=dtype,
    )


@register_model("videomae_t")
def _videomae_t(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    """Deliberately tiny VideoMAE classifier (the `tiny3d` of the
    transformer family): CI smokes, tests/test_zpipeline.py, and the
    chaos pipeline-preemption leg compile it in seconds on a CPU host.
    Not a reference architecture."""
    return VideoMAEClassifier(
        num_classes=cfg.num_classes, dim=32, depth=4, num_heads=2,
        tubelet=(2, 8, 8), dropout_rate=cfg.dropout_rate,
        attention_backend=cfg.attention,
        context_mesh=mesh if cfg.attention in ("ring", "ulysses") else None,
        shard_mesh=mesh, pipeline=pipeline, remat=cfg.remat,
        attn_mask=cfg.attn_mask, attn_window=cfg.attn_window, dtype=dtype,
    )


@register_model("mvit_t")
def _mvit_t(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    """Deliberately tiny MViT (the `videomae_t` of the multiscale family):
    depth 2, dim 16, uniform schedule — CI smokes and the streaming
    stem-seam tests compile it in seconds on a CPU host. Not a reference
    architecture."""
    return MViT(
        num_classes=cfg.num_classes, depth=2, embed_dim=16, num_heads=2,
        stage_starts=(), drop_path_rate=0.0,
        dropout_rate=cfg.dropout_rate,
        attention_backend=cfg.attention,
        context_mesh=mesh if cfg.attention in ("ring", "ulysses") else None,
        shard_mesh=mesh, pipeline=pipeline,
        depthwise_impl=cfg.depthwise_impl, remat=cfg.remat, dtype=dtype,
    )


@register_model("videomae_t_pretrain", task="reconstruct")
def _videomae_t_pretrain(cfg: ModelConfig, dtype, mesh=None, pipeline=None):
    """Tiny VideoMAE pretraining twin of `videomae_t` (depth 4 encoder /
    depth 2 decoder — both divide by 2 stages, the encoder by 4)."""
    return VideoMAEForPretraining(
        dim=32, depth=4, num_heads=2, decoder_dim=16, decoder_depth=2,
        decoder_heads=2, tubelet=(2, 8, 8), mask_ratio=cfg.mask_ratio,
        attention_backend=cfg.attention,
        context_mesh=mesh if cfg.attention in ("ring", "ulysses") else None,
        shard_mesh=mesh, pipeline=pipeline, remat=cfg.remat, dtype=dtype,
    )


def _qwen3_next(cfg: ModelConfig, dtype, published: dict):
    """The held share of a Qwen3-Next decoder: published widths, with the
    depth, the vocabulary slice and the experts held as the config says."""
    from pytorchvideo_accelerate_tpu.models.lm_common import check_share
    from pytorchvideo_accelerate_tpu.models.qwen3_next import (
        Qwen3Next,
        Qwen3NextArch,
    )

    arch = Qwen3NextArch(**published)
    arch = dataclasses.replace(
        arch,
        num_hidden_layers=cfg.num_layers or arch.num_hidden_layers,
        vocab_size=cfg.vocab_size or arch.vocab_size,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset)
    check_share(arch.num_hidden_layers, arch.full_attention_interval,
                arch.expert_offset, arch.held, arch.num_experts)
    return Qwen3Next(arch, dtype=dtype, remat=True)


@register_model("qwen3_next_80b_a3b", task="next_token")
def _qwen3_next_80b_a3b(cfg: ModelConfig, dtype, mesh=None):
    """Qwen3-Next-80B-A3B-Instruct at its published widths (the defaults of
    `Qwen3NextArch`: huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct
    config.json). One chip holds a share: `--model.num_layers`,
    `--model.vocab_size`, `--model.experts_held` (docs/TOKENS.md)."""
    return _qwen3_next(cfg, dtype, {})


@register_model("qwen3_next_t", task="next_token")
def _qwen3_next_t(cfg: ModelConfig, dtype, mesh=None):
    """Deliberately tiny Qwen3-Next (the `tiny3d` of the token family): every
    mechanism, toy widths — tests and the CPU rehearsal. Not a reference
    architecture."""
    return _qwen3_next(cfg, dtype, dict(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        vocab_size=256))


def _smallthinker(cfg: ModelConfig, dtype, published: dict):
    """The held share of a SmallThinker decoder: published widths, with the
    depth (the layouts' first layers), the vocabulary slice and the experts
    held as the config says."""
    from pytorchvideo_accelerate_tpu.models.lm_common import check_share
    from pytorchvideo_accelerate_tpu.models.smallthinker import (
        SmallThinker,
        SmallThinkerArch,
    )

    arch = SmallThinkerArch(**published)
    layers = cfg.num_layers or arch.num_hidden_layers
    arch = dataclasses.replace(
        arch, num_hidden_layers=layers,
        vocab_size=cfg.vocab_size or arch.vocab_size,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset)
    check_share(layers, arch.period, arch.expert_offset, arch.held,
                arch.moe_num_primary_experts)
    if layers > len(arch.sliding_window_layout):
        raise ValueError(f"model.num_layers={layers} is more than the "
                         f"{len(arch.sliding_window_layout)} layers published")
    return SmallThinker(arch, dtype=dtype, remat=True)


@register_model("smallthinker_21b_a3b", task="next_token")
def _smallthinker_21b_a3b(cfg: ModelConfig, dtype, mesh=None):
    """SmallThinker-21BA3B-Instruct at its published widths (the defaults of
    `SmallThinkerArch`: huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
    config.json). One chip holds a share: `--model.num_layers`,
    `--model.vocab_size`, `--model.experts_held` (docs/TOKENS.md)."""
    return _smallthinker(cfg, dtype, {})


@register_model("smallthinker_t", task="next_token")
def _smallthinker_t(cfg: ModelConfig, dtype, mesh=None):
    """Deliberately tiny SmallThinker: every mechanism (a full layer without
    positions, three windowed rotary layers, 7 query heads on one key-value
    head, the router ahead of the attention), toy widths — tests and the CPU
    rehearsal. Not a reference architecture."""
    return _smallthinker(cfg, dtype, dict(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=7,
        num_key_value_heads=1, head_dim=16, sliding_window_size=32,
        rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_ffn_hidden_size=32, vocab_size=256))


def _ouro(cfg: ModelConfig, dtype, published: dict):
    """An Ouro decoder: published widths, with the depth and the vocabulary
    slice as the config says. It has no experts to hold a share of."""
    from pytorchvideo_accelerate_tpu.models.ouro import Ouro, OuroArch

    if cfg.experts_held or cfg.expert_offset:
        raise ValueError(
            f"model {cfg.name!r} has no experts: model.experts_held="
            f"{cfg.experts_held} and model.expert_offset={cfg.expert_offset} "
            "must stay 0")
    arch = OuroArch(**published)
    arch = dataclasses.replace(
        arch, num_hidden_layers=cfg.num_layers or arch.num_hidden_layers,
        vocab_size=cfg.vocab_size or arch.vocab_size)
    return Ouro(arch, dtype=dtype, remat=True)


@register_model("ouro_2_6b", task="next_token")
def _ouro_2_6b(cfg: ModelConfig, dtype, mesh=None):
    """Ouro-2.6B at its published widths (the defaults of `OuroArch`:
    huggingface.co/ByteDance/Ouro-2.6B config.json): 48 layers run four times
    a step. One chip holds a pipeline stage's layers: `--model.num_layers`,
    `--model.vocab_size` (docs/TOKENS.md)."""
    return _ouro(cfg, dtype, {})


@register_model("ouro_t", task="next_token")
def _ouro_t(cfg: ModelConfig, dtype, mesh=None):
    """Deliberately tiny Ouro: every mechanism (two layers run three times,
    sandwich norms, the exit gate and the exit-weighted loss), toy widths —
    tests and the CPU rehearsal. Not a reference architecture."""
    return _ouro(cfg, dtype, dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=96,
        vocab_size=256, total_ut_steps=3))


def _nemotron_h(cfg: ModelConfig, dtype, published: dict):
    """The held share of a Nemotron-H decoder: published widths, with the
    depth (the pattern's first layers), the vocabulary slice and the experts
    held as the config says."""
    from pytorchvideo_accelerate_tpu.models.lm_common import check_share
    from pytorchvideo_accelerate_tpu.models.nemotron_h import (
        KINDS,
        NemotronH,
        NemotronHArch,
    )

    arch = NemotronHArch(**published)
    layers = cfg.num_layers or arch.num_hidden_layers
    pattern = arch.hybrid_override_pattern
    if layers > len(pattern):
        raise ValueError(f"model.num_layers={layers} is more than the "
                         f"{len(pattern)} layers of the pattern {pattern!r}")
    if set(pattern) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} holds a layer "
                         f"kind other than {KINDS!r}")
    arch = dataclasses.replace(
        arch, num_hidden_layers=layers,
        vocab_size=cfg.vocab_size or arch.vocab_size,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset)
    check_share(layers, 1, arch.expert_offset, arch.held,
                arch.n_routed_experts)
    return NemotronH(arch, dtype=dtype, remat=True)


@register_model("nemotron_3_nano_30b_a3b", task="next_token")
def _nemotron_3_nano_30b_a3b(cfg: ModelConfig, dtype, mesh=None):
    """NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths (the defaults
    of `NemotronHArch`: huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    config.json). One chip holds a share: `--model.num_layers` (the pattern's
    first layers), `--model.vocab_size`, `--model.experts_held`
    (docs/TOKENS.md)."""
    return _nemotron_h(cfg, dtype, {})


@register_model("nemotron_h_t", task="next_token")
def _nemotron_h_t(cfg: ModelConfig, dtype, mesh=None):
    """Deliberately tiny Nemotron-H: every mechanism in the published
    pattern's first six layers (`MEMEM*`: three Mamba-2 mixers of 4 heads in
    2 groups, two sigmoid-routed relu^2 mixtures with a shared expert,
    attention without positions at 4 query heads on 1 key-value head), toy
    widths — tests and the CPU rehearsal. Not a reference architecture."""
    return _nemotron_h(cfg, dtype, dict(
        hidden_size=64, num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        chunk_size=32, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        vocab_size=256))


def available_models():
    return sorted(_REGISTRY)


def create_model(cfg: ModelConfig, mixed_precision: str = "bf16", mesh=None,
                 pipeline=None):
    """Build the Flax module for `cfg.name`.

    `mixed_precision="bf16"` sets compute dtype bf16 with fp32 params — the
    TPU-native replacement for the reference's fp16 AMP path. `"fp16"` is
    accepted and mapped to bf16 (reference launch-script compat: fp16 has no
    advantage on TPU and needs loss scaling).

    `mesh`: required for the context-parallel attention backends
    ("ring"/"ulysses") — the attention router opens a `shard_map` region over
    the mesh's context-parallel axis (the library mesh's ``context`` axis /
    the 2-D train mesh's ``model`` axis), so the model stays usable from
    ordinary auto-sharded (jit) training code. The transformer families also
    use it for block-boundary activation sharding constraints
    (parallel/sharding.constrain_block).

    `pipeline`: an ACTIVE parallel/pipeline.PipelinePlan routes the
    transformer trunk's block stack through the SPMD stage pipeline
    (parallel.pipeline_stages > 1). Transformer families only — a family
    whose builder has no stage-cut seam (the conv nets) refuses loudly
    instead of silently training unpipelined.
    """
    if cfg.name not in _REGISTRY:
        raise ValueError(f"unknown model {cfg.name!r}; available: {available_models()}")
    from pytorchvideo_accelerate_tpu.models.common import FUSED_MODES

    if cfg.fused_kernels not in FUSED_MODES:
        raise ValueError(
            f"model.fused_kernels must be one of {FUSED_MODES}, got "
            f"{cfg.fused_kernels!r} (docs/KERNELS.md)")
    if cfg.attention in ("ring", "ulysses") and mesh is None:
        raise ValueError(
            f"attention={cfg.attention!r} needs the device mesh: "
            "create_model(cfg, mixed_precision, mesh=mesh)"
        )
    from pytorchvideo_accelerate_tpu.precision import policy_compute_dtype

    dtype = policy_compute_dtype(mixed_precision)
    builder = _REGISTRY[cfg.name]
    # user-registered builders may use the original (cfg, dtype) signature;
    # pass the mesh/pipeline only to builders that declare the parameter
    try:
        params = inspect.signature(builder).parameters
    except (TypeError, ValueError):
        params = {}
    takes_mesh = "mesh" in params
    takes_pipeline = "pipeline" in params
    active_pipeline = pipeline is not None and getattr(pipeline, "active",
                                                       False)
    if active_pipeline and not takes_pipeline:
        raise ValueError(
            f"model {cfg.name!r} has no pipeline stage-cut seam "
            "(parallel.pipeline_stages > 1 needs a transformer block "
            "stack — mvit/videomae families); conv families spend the "
            "model axis on replication, not stages")
    kwargs = {}
    if takes_mesh:
        kwargs["mesh"] = mesh
    if takes_pipeline:
        kwargs["pipeline"] = pipeline
    if kwargs:
        return builder(cfg, dtype, **kwargs)
    return builder(cfg, dtype)


def model_input_spec(cfg: ModelConfig, data_cfg) -> dict:
    """Shapes the model expects for one clip batch (B=1), NDHWC; for a
    next-token model one sequence of int32 ids."""
    if model_task(cfg.name) == "next_token":
        return {"tokens": (1, data_cfg.seq_len)}
    t, s = data_cfg.num_frames, data_cfg.crop_size
    if cfg.name.startswith("slowfast"):
        return {
            "slow": (1, max(t // cfg.slowfast_alpha, 1), s, s, 3),
            "fast": (1, t, s, s, 3),
        }
    return {"video": (1, t, s, s, 3)}

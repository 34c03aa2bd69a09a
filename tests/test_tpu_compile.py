"""Every `pallas_call` in ops/ must lower for the chip: each is compiled,
not interpreted, for a DESCRIBED TPU v5e (no chip attached — the TPU
compiler is installed here) at the real model widths. Interpret mode
cannot see what Mosaic refuses: halo DMA slices not aligned to the (8, 128)
tile and scoped-VMEM overflows passed every interpret-mode test.

A compile that passes is not a chip run: nothing executes, so results and
times come from `chip_smoke.py` on the chip. The persistent compile cache
is off for the whole session (conftest), which keeps these compiles from
writing entries no chip-less process can read back.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from pytorchvideo_accelerate_tpu.ops import attention, gated_delta
from pytorchvideo_accelerate_tpu.ops import pallas_fused as pf
from pytorchvideo_accelerate_tpu.ops import pallas_gated_delta as pgd
from pytorchvideo_accelerate_tpu.ops.pallas_attention import (
    causal_flash_attention,
    flash_attention,
)
from pytorchvideo_accelerate_tpu.ops.pallas_depthwise import (
    pallas_depthwise3d_s1,
)

F32 = jnp.float32


@pytest.fixture(scope="module")
def v5e():
    """ShapeDtypeStruct factory placed on one chip of a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)


def _attention(grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    if not grad:
        return fwd
    return jax.grad(lambda q, k, v: fwd(q, k, v).astype(F32).sum(),
                    argnums=(0, 1, 2))


def _fused(fn, act, grad=False):
    def fwd(x, w, s, b):
        return fn(x, w, s, b, act=act, mode="pallas", interpret=False)

    if not grad:
        return fwd
    return jax.grad(lambda *a: fwd(*a).astype(F32).sum(), argnums=(0, 1))


def _attn_case(grad):
    qkv = (8, 1568, 12, 64)  # videomae_b: 8x14x14 tokens, 12 heads x 64
    return _attention(grad), lambda dt: [(qkv, dt)] * 3


def _conv_case(x_shape, w_shape, grad=False):
    cout = w_shape[-1]
    return (_fused(pf.fused_conv3d_bn_act, "relu", grad),
            lambda dt: [(x_shape, dt), (w_shape, dt), ((cout,), F32),
                        ((cout,), F32)])


def _fused_dw_case(x_shape):
    c = x_shape[-1]
    return (_fused(pf.fused_depthwise_bn_act, "silu"),
            lambda dt: [(x_shape, dt), ((3, 3, 3, 1, c), dt), ((c,), F32),
                        ((c,), F32)])


def _dw_case(x_shape):
    c = x_shape[-1]
    return (lambda x, k: pallas_depthwise3d_s1(x, k, False),
            lambda dt: [(x_shape, dt), ((3, 3, 3, 1, c), dt)])


def _gdn_case(grad):
    """qwen3_next_80b_a3b.train_8k's DeltaNet layer: 2 sequences of 8192
    tokens, 16 key heads under 32 value heads, 128 wide."""
    b, t, hk, hv, d = 2, 8192, 16, 32, 128
    rows = (b, hv, t // pgd.CHUNK, pgd.CHUNK)

    def fwd(q, k, v, gam, beta):
        return pgd.gdn_chunks(q, k, v, gam, beta, hk, False)

    fn = fwd if not grad else jax.grad(
        lambda *a: fwd(*a)[0].astype(F32).sum(), argnums=(0, 1, 2, 3, 4))
    return fn, lambda dt: [((b, t, hk * d), dt), ((b, t, hk * d), dt),
                           ((b, t, hv * d), dt), (rows, F32), (rows, F32)]


def _causal_case(b, t, hq, hkv, d, window, grad):
    """`causal_gqa_attention`'s kernels at a token cell's shapes."""
    def fwd(q, k, v):
        return causal_flash_attention(q, k, v, d ** -0.5, window, False)

    fn = fwd if not grad else jax.grad(
        lambda *a: fwd(*a).astype(F32).sum(), argnums=(0, 1, 2))
    return fn, lambda dt: [((b, t, hq, d), dt), ((b, t, hkv, d), dt),
                           ((b, t, hkv, d), dt)]


# qwen3_next_80b_a3b.train_8k: 2 x 8192, 16 query heads on 2 key-value heads
# of 256; smallthinker_21b_a3b.train_16k: 1 x 16,384, 28 on 4 of 128, its
# full layer and its three under the 4096-token band; ouro_2_6b.train_4k:
# 1 x 4096, 16 on 16 of 128 (groups of one)
_CAUSAL = {"causal_8k_heads_of_256": (2, 8192, 16, 2, 256, None),
           "causal_16k_groups_of_7": (1, 16384, 28, 4, 128, None),
           "window_16k_groups_of_7": (1, 16384, 28, 4, 128, 4096),
           "causal_4k_groups_of_one": (1, 4096, 16, 16, 128, None)}

# name -> (function, dtype -> [(shape, dtype), ...]); shapes are the
# real model shapes, plus one stage of x3d_s
# (13 frames; W 40/20/10/5 at inner C 54/108/216/432) and of each
# slowfast_r50 pathway (32f/256^2: slow T=8, fast T=32) for the halo kernels
CASES = {
    "attention_fwd": _attn_case(grad=False),
    "attention_bwd_dq_dkv": _attn_case(grad=True),
    "pointwise_x3d_res3": _conv_case((2, 13, 20, 20, 48),
                                     (1, 1, 1, 48, 108)),
    "pointwise_sf_res2": _conv_case((8, 8, 56, 56, 256), (1, 1, 1, 256, 64)),
    "conv311_sf_fast_res4": _conv_case((2, 32, 16, 16, 128),
                                       (3, 1, 1, 128, 32)),
    "conv133_sf_slow_res4": _conv_case((2, 8, 16, 16, 256),
                                       (1, 3, 3, 256, 256)),
    "conv133_sf_slow_res2": _conv_case((8, 8, 64, 64, 64),
                                       (1, 3, 3, 64, 64)),
    "conv133_sf_fast_res2": _conv_case((8, 32, 64, 64, 8), (1, 3, 3, 8, 8)),
    # the dx pass of this site overflowed the default 16 MiB scoped VMEM
    "conv311_sf_slow_res4_grad": _conv_case(
        (8, 8, 16, 16, 1024), (3, 1, 1, 1024, 256), grad=True),
    "fused_dw_x3d_res3": _fused_dw_case((2, 13, 20, 20, 108)),
    "fused_dw_x3d_res2": _fused_dw_case((8, 13, 40, 40, 54)),
    "fused_dw_x3d_res5": _fused_dw_case((8, 13, 5, 5, 432)),
    "dw_x3d_res3": _dw_case((2, 13, 20, 20, 108)),
    "dw_x3d_res2": _dw_case((8, 13, 40, 40, 54)),
    "dw_x3d_res5": _dw_case((8, 13, 5, 5, 432)),
    "gated_delta_fwd": _gdn_case(grad=False),
    "gated_delta_fwd_saving_bwd": _gdn_case(grad=True),
    **{f"{name}_{'fwd_dq_dkv' if grad else 'fwd'}": _causal_case(*shape, grad)
       for name, shape in _CAUSAL.items() for grad in (False, True)},
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, name, dtype):
    fn, shapes = CASES[name]
    compiled = jax.jit(fn).lower(
        *(v5e(shape, dt) for shape, dt in shapes(dtype))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fast_stem_lane_fold_compiles_lane_dense(v5e, monkeypatch):
    """SlowFast's fast stem ((5,7,7), stride (1,2,2), 3 -> 8) at
    `slowfast_r50.train`'s shape, forward and weight gradient, as the
    lane-filling contraction (ops/lane_fold.py): it compiles, the conv
    writes its output with the 128 folded channels minor (nn.Conv's is
    8 channels to a 128-lane tile, 16 times the bytes), and the program's
    temp stays under 1 GB (3.2 GB with nn.Conv). The described chip is not
    this process's default backend, so the rule's backend half is forced."""
    import re

    from pytorchvideo_accelerate_tpu.models.common import ConvBNAct
    from pytorchvideo_accelerate_tpu.ops import lane_fold

    monkeypatch.setattr(lane_fold, "takes_fold", lambda: True)
    stem = ConvBNAct(8, kernel=(5, 7, 7), stride=(1, 2, 2),
                     dtype=jnp.bfloat16)
    x_shape = (8, 32, 256, 256, 3)
    variables = jax.eval_shape(
        lambda: stem.init(jax.random.key(0), jnp.zeros(x_shape, jnp.bfloat16)))

    def loss(params, stats, x):
        with lane_fold.count_sites() as sites:
            y, _ = stem.apply({"params": params, "batch_stats": stats}, x,
                              train=True, mutable=["batch_stats"])
        assert len(sites) == 1
        return jnp.sum(y.astype(F32) ** 2)

    on_chip = jax.tree.map(lambda a: v5e(a.shape, a.dtype), variables)
    compiled = jax.jit(jax.grad(loss)).lower(
        on_chip["params"], on_chip["batch_stats"],
        v5e(x_shape, jnp.bfloat16)).compile()
    # the forward conv (fused with the batch sums) and the weight-gradient
    # conv: the last array of each result is the conv's own output
    convs = [line.split(" fusion(")[0]
             for line in compiled.as_text().splitlines()
             if " fusion(" in line and re.search(
                 r'op_name="[^"]*conv/conv_general_dilated"', line)]
    outputs = [re.findall(r"bf16\[([\d,]+)\]\{(\d+),", c)[-1] for c in convs]
    assert ("8,32,128,8,128", "4") in outputs  # B,T,H/2,W/32, 16 cols x 8 ch
    assert ("5,7,3,96,128", "4") in outputs    # kt,kh,blocks, 32 cols x 3, 128
    for dims, minor in outputs:
        assert dims.split(",")[int(minor)] == "128", convs
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_gated_delta_kernels_sit_under_the_scan_scope(v5e, monkeypatch):
    """A `GatedDeltaNet` layer's gradient at `qwen3_next_80b_a3b.train_8k`'s
    shapes, inside the model's rematerialised mixer unit, compiled for the chip
    with the rule's backend half forced: the forward, the rematerialised
    forward and the backward `pallas_call` are all there, and each sits under
    an `op_name` that contains `gdn/scan/`, the rule by which
    `gdn_scan_roofline` and `gdn_ms_per_step` select device time
    (benchmarks/lib/hlo.py): a `custom_vjp`'s backward keeps the call's name
    stack."""
    import re

    import flax.linen as nn

    from pytorchvideo_accelerate_tpu.models.qwen3_next import (
        Qwen3NextArch,
        _Mixer,
    )

    monkeypatch.setattr(gated_delta, "takes_kernel", lambda: True)
    monkeypatch.setattr(gated_delta, "_interpret", lambda: False)
    layer = nn.remat(_Mixer)(Qwen3NextArch(), jnp.bfloat16,
                             "linear_attention", name="mixer_0")
    x_shape = (2, 8192, 2048)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0),
                           jnp.zeros(x_shape, jnp.bfloat16)))["params"]

    def loss(params, x):
        with gated_delta.count_sites() as sites:
            y = layer.apply({"params": params}, x)
        assert len(sites) == 1
        return jnp.sum(y.astype(F32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(lambda a: v5e(a.shape, a.dtype), params),
        v5e(x_shape, jnp.bfloat16)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call" in line and "pva_gdn" in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert sorted(re.search(r"pva_gdn_\w+", n).group(0) for n in names) == [
        "pva_gdn_bwd", "pva_gdn_fwd_saving", "pva_gdn_fwd_saving"], names
    assert all("gdn/scan/" in n for n in names), names


@pytest.mark.parametrize("family,scope", [
    ("qwen3_next", "attn/core/"),
    ("smallthinker_full", "attn/core/"),
    ("smallthinker_window", "swa/core/"),
    ("ouro", "attn/core/"),
])
def test_attention_kernels_sit_under_the_core_scope(v5e, monkeypatch, family,
                                                    scope):
    """An attention layer's gradient at each token cell's shapes, inside the
    family's rematerialised mixer unit (which keeps the forward's `o` and
    `lse`), compiled for the chip with the rule's backend half forced: the
    forward (once: the unit's recomputation does not run it again), dq and
    dk/dv `pallas_call`s are all there and each sits under an `op_name` that
    contains `attn/core/` (`swa/core/` for a windowed layer), the rule by which
    `causal_attention_roofline` and `window_attention_roofline` select device
    time and raise over 100% (benchmarks/lib/scoped.py): a backward kernel
    outside the scope would turn a gain into `output_malformed`."""
    import re

    from pytorchvideo_accelerate_tpu.models import ouro, qwen3_next, smallthinker
    from pytorchvideo_accelerate_tpu.models.lm_common import (
        remat_keeping_attention,
    )

    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    if family == "qwen3_next":
        layer = remat_keeping_attention(qwen3_next._Mixer)(
            qwen3_next.Qwen3NextArch(), jnp.bfloat16, "full_attention",
            name="mixer_3")
        x_shape, window = (2, 8192, 2048), None
    elif family == "ouro":   # one layer execution: the loop's remat unit
        layer = remat_keeping_attention(ouro._Layer)(
            ouro.OuroArch(), jnp.bfloat16, name="layer_0")
        x_shape, window = (1, 4096, 2048), None
    else:
        arch = smallthinker.SmallThinkerArch()
        window = arch.sliding_window_size if family.endswith("window") else None
        layer = remat_keeping_attention(smallthinker._Mixer)(
            arch, jnp.bfloat16, rotary=window is not None, window=window,
            name="mixer_0")
        x_shape = (1, 16384, arch.hidden_size)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0),
                           jnp.zeros(x_shape, jnp.bfloat16)))["params"]

    def loss(params, x):
        with attention.count_kernel_sites() as sites:
            y = layer.apply({"params": params}, x)
        assert [(w, kept) for _shape, w, kept in sites] == [(window, True)]
        return sum(jnp.sum(leaf.astype(F32) ** 2)
                   for leaf in jax.tree.leaves(y)
                   if jnp.issubdtype(leaf.dtype, jnp.floating))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(lambda a: v5e(a.shape, a.dtype), params),
        v5e(x_shape, jnp.bfloat16)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call" in line and "pva_attn" in line]
    op_names = [re.search(r'op_name="([^"]*)"', line).group(1)
                for line in calls]
    assert sorted(re.search(r"pva_attn_\w+", n).group(0)
                  for n in op_names) == ["pva_attn_dkv", "pva_attn_dq",
                                         "pva_attn_fwd"], op_names
    assert all(scope in n for n in op_names), op_names


def test_nemotron_cell_step_fits_the_chip(v5e, monkeypatch):
    """`nemotron_3_nano_30b_a3b.train_8k`'s whole train step (9 layers
    MEMEM*EME, 8 of 128 experts, 16,384 vocabulary rows, 2 x 8,192 tokens,
    AdamW behind the clip, the health gauges), compiled for one chip of a
    described v5e with the attention kernels taken as on a TPU: the Mamba-2
    mixers' four scopes are in the compiled step, the flash kernels serve the
    groups of 16, and the step's scratch beside the state (float32 masters
    and Adam's two moments, 8.0 GB) stays under the chip's 15.75 GiB (14.6 GB
    read)."""
    import re

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from pytorchvideo_accelerate_tpu.config import ModelConfig, OptimConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import TRAIN_MESH_AXIS_NAMES
    from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), TRAIN_MESH_AXIS_NAMES)
    model = create_model(ModelConfig(name="nemotron_3_nano_30b_a3b",
                                     num_layers=9, vocab_size=16384,
                                     experts_held=8), "bf16")
    tx = build_optimizer(OptimConfig(optimizer="adamw", lr=3e-4,
                                     weight_decay=0.1, grad_clip_norm=1.0),
                         1000)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    replicated = NamedSharding(mesh, PartitionSpec())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(lambda p: TrainState.create(p, {}, tx), params))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, 8192), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec("data")))}
    compiled = make_lm_step(model, tx, mesh, health_metrics=True).lower(
        state, batch, jax.random.key(0)).compile()
    text = compiled.as_text()
    assert set(re.findall(r"/mamba/(\w+)/", text)) == {"in_proj", "conv",
                                                       "scan", "out"}
    assert re.search(r"custom-call.*pva_attn_fwd", text)
    memory = compiled.memory_analysis()
    state_bytes = memory.argument_size_in_bytes
    assert 7.9e9 < state_bytes < 8.1e9
    assert state_bytes + memory.temp_size_in_bytes < 15.75 * 2 ** 30

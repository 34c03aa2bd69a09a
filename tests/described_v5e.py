"""What the `test_tpu_compile*.py` files share: the described chip and the
kernel cases.

Every `pallas_call` in ops/ must lower for the chip: each is compiled, not
interpreted, for a DESCRIBED TPU v5e (no chip attached — the TPU compiler is
installed here) at the real model widths. Interpret mode cannot see what
Mosaic refuses: halo DMA slices not aligned to the (8, 128) tile and
scoped-VMEM overflows passed every interpret-mode test.

A compile that passes is not a chip run: nothing executes, so results and
times come from `chip_smoke.py` on the chip. The persistent compile cache is
off for the whole session (conftest), which keeps these compiles from writing
entries no chip-less process can read back.

The compiles are split over three files, so that no one xdist worker holds
them all: `test_tpu_compile.py` (the kernel cases below but the long
attention ones, and the nemotron cell's whole step),
`test_tpu_compile_long_attention.py` (the 8k and 16k attention kernels) and
`test_tpu_compile_layers.py` (a layer's gradient at a cell's shapes).
The topology is described in a fixture, so only the worker given a file loads
the TPU library; two such workers at once need `ALLOW_MULTIPLE_LIBTPU_LOAD=1`,
which the tier-1 command sets (without it the second one's tests skip).
"""

import os

import jax
import jax.numpy as jnp
import pytest

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
DTYPES = pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                                 ids=["bf16", "f32"])


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
_PASSES = {False: "fwd", True: "fwd_dq_dkv"}

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
    **{f"{name}_{_PASSES[grad]}": _causal_case(*shape, grad)
       for name, shape in _CAUSAL.items() for grad in (False, True)},
}
# the 8k and 16k attention kernels, compiled in a file of their own
LONG_ATTENTION = sorted(f"{name}_{p}" for name in _CAUSAL if "_4k_" not in name
                        for p in _PASSES.values())


def compiled_case(v5e, name, dtype):
    """`CASES[name]` compiled for the described chip."""
    fn, shapes = CASES[name]
    return jax.jit(fn).lower(
        *(v5e(shape, dt) for shape, dt in shapes(dtype))).compile()

"""Fused conv -> norm-affine -> activation kernels for the 3D-CNN hot paths.

ROADMAP item 1 ("raw speed"): the slowfast/x3d residual blocks — the
dominant FLOPs of the headline `slowfast_r50` recipe — run today as
unfused XLA ops: conv, then a BatchNorm normalize pass, then an
activation pass, each a round trip over the activation tensor in HBM.
This module collapses the chain into single kernels behind the
`model.fused_kernels` knob (models/common.py wires them; off = today's
graph, byte for byte):

- `fused_pointwise_bn_act` — (1,1,1) conv + per-channel affine + act.
  A pointwise NDHWC conv IS a matmul over (B*T*H*W, Cin); the Pallas
  kernel tiles the row dim, accumulates on the MXU in f32, and applies
  bias + activation in the epilogue before the single cast-and-store.
- `fused_conv3d_bn_act` — dense small-kernel stride-1 SAME conv
  ((kt,1,1) temporal, (1,3,3) spatial, any odd kt/kh/kw) + affine +
  act. The halo-tile lowering of ops/pallas_depthwise.py generalized to
  channel-mixing convs: the grid tiles the OUTPUT over (batch, t, h),
  each program DMAs ONE overlapping input window (tile + (k-1)-halo,
  W and Cin padded to whole (sublane, lane) tiles by `_pad_for_tiles`,
  which is what the v5e compiler asks of the DMA's slice) HBM->VMEM,
  then runs the kt*kh*kw taps as MXU
  matmuls against a single f32 VMEM accumulator — input crosses
  HBM->VMEM once per tile, the output is written once, already
  normalized and activated.
- `fused_depthwise_bn_act` — the x3d conv_b / csn / stem_t depthwise
  chain: the halo kernel with the BN affine folded into the per-channel
  taps and bias + activation in the epilogue (VPU path, no MXU).

Norm-affine contract: callers pass the RESOLVED per-channel (scale,
bias) — for BatchNorm that is `scale = gamma * rsqrt(var + eps)`,
`bias = beta - mean * scale` (running stats at eval/serve time, batch
stats in training — models/common.BNAffine computes both). The scale
half folds into the conv WEIGHTS (`w * scale` commutes with the
channel-linear conv), so the kernels only carry a bias + act epilogue;
GroupNorm/LayerNorm affines fold the same way.

Backend dispatch (`mode`): "auto" lowers to the Pallas kernels on TPU
and to `_xla_*` — the scale-folded conv + bias + act formulation XLA
fuses well — everywhere else; interpret-mode Pallas is a PARITY tool,
never a production CPU path. "pallas"/"xla" force a lowering (the
parity tests; graphcheck traces the forced-pallas graph so the
registered-FLOPs hooks in analysis/gc_flops.py are exercised off-TPU).

Training: every Pallas path carries a `jax.custom_vjp` — dx reuses the
SAME kernel (stride-1 transpose conv = correlation with the
tap-flipped, channel-transposed weights), dw is per-tap strided
contractions XLA fuses, dbias a sum; act' is recomputed from the
pre-activation (one extra kernel pass instead of a saved residual —
the remat trade the rest of the stack already makes). The XLA mode is
plain autodiff. Parity against `jax.grad` of the unfused reference is
asserted in tests/test_zkernels.py.

Precision: accumulation and the bias/act epilogue run in deliberate
f32 islands (`precision.f32_island`; allowlisted by qualname in
analysis/gc_dtype.py), with ONE `precision.end_island` downcast to the
compute dtype at the store.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorchvideo_accelerate_tpu.ops.depthwise import depthwise_conv3d_shift
from pytorchvideo_accelerate_tpu.ops.pallas_depthwise import (
    _pad_for_tiles,
    _tile_sizes,
)
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

# the epilogues the model graph actually uses (nn.relu, nn.swish/silu,
# and the act=None projection convs); static strings so the jit cache
# keys stay hashable and each kernel specializes once
FUSED_ACTS = ("identity", "relu", "silu")


def apply_act(x, act: str):
    """Epilogue activation on the f32 accumulator (shared by the Pallas
    kernels, the XLA lowering, and the ops/kbench_refs.py references)."""
    if act == "relu":
        return jnp.maximum(x, 0.0)
    if act == "silu":
        return jax.nn.silu(x)
    if act == "identity":
        return x
    raise ValueError(f"fused act must be one of {FUSED_ACTS}, got {act!r}")


def _act_grad(z32, act: str):
    """d act/dz at the (f32) pre-activation z."""
    if act == "relu":
        return (z32 > 0).astype(z32.dtype)
    if act == "silu":
        s = jax.nn.sigmoid(z32)
        return s * (1.0 + z32 * (1.0 - s))
    return jnp.ones_like(z32)


def _use_pallas(mode: str) -> bool:
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    if mode != "auto":
        raise ValueError(f"fused mode must be auto|pallas|xla, got {mode!r}")
    return jax.default_backend() == "tpu"


def _interp(interpret: Optional[bool]) -> bool:
    # non-TPU backends run the identical kernel code interpreted so the
    # CPU harness unit-tests the real path (pallas_depthwise convention)
    return jax.default_backend() != "tpu" if interpret is None else interpret


# --- pointwise (1,1,1): tiled matmul + epilogue -----------------------------


def _pw_bn_act_kernel(x_ref, w_ref, b_ref, o_ref, *, act: str):
    # one MXU matmul per row tile, f32 accumulation, epilogue in f32
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    acc = apply_act(acc + f32_island(b_ref[0]), act)
    o_ref[:] = acc.astype(o_ref.dtype)


def _pw_call(x2d, w, b2d, act: str, interpret: bool):
    m, cin = x2d.shape
    cout = w.shape[-1]
    bm = min(256, -(-m // 8) * 8)
    pad = (-m) % bm
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_pw_bn_act_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((m + pad, cout), x2d.dtype),
        grid=((m + pad) // bm,),
        in_specs=[
            pl.BlockSpec((bm, cin), lambda i: (i, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cout), lambda i: (i, 0)),
        interpret=interpret,
        name="pva_fused_pointwise_bn_act",
    )(x2d, w, b2d)
    return out[:m] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pw_pallas(x2d, wf, b2d, act: str, interpret: bool):
    """act((x2d @ wf) + bias) over (M, Cin) rows; wf is scale-folded."""
    return _pw_call(x2d, wf, b2d, act, interpret)


def _pw_fwd(x2d, wf, b2d, act, interpret):
    return _pw_call(x2d, wf, b2d, act, interpret), (x2d, wf, b2d)


def _pw_bwd(act, interpret, res, g):
    x2d, wf, b2d = res
    # recompute the pre-activation (remat instead of a saved residual)
    z32 = f32_island(_pw_call(x2d, wf, b2d, "identity", interpret))
    dz32 = f32_island(g) * _act_grad(z32, act)
    dz = end_island(dz32, x2d.dtype)
    # dx: the same tiled-matmul kernel against the transposed weights
    zeros = jnp.zeros((1, wf.shape[0]), jnp.float32)
    dx = _pw_call(dz, wf.T, zeros, "identity", interpret)
    dwf = end_island(
        jnp.einsum("mc,md->cd", f32_island(x2d), dz32), wf.dtype)
    db = jnp.sum(dz32, axis=0, keepdims=True)
    return dx, dwf, db


_pw_pallas.defvjp(_pw_fwd, _pw_bwd)


# --- dense small-kernel stride-1 SAME conv + epilogue -----------------------


# v5e's default scoped-VMEM limit is 16 MiB of the core's 128 MiB; the
# f32 dx pass of slowfast_r50's slow res4 conv_a (3,1,1) 256->1024 needs
# 18.8 MiB (weights + output tile double-buffered, plus the accumulator)
_CONV_VMEM_LIMIT = 64 * 2 ** 20


def _conv_bn_act_kernel(x_hbm, w_ref, b_ref, o_ref, win_ref, sem, *,
                        tb: int, hb: int, ow: int,
                        kt: int, kh: int, kw: int, act: str):
    b = pl.program_id(0)
    ti = pl.program_id(1)
    hi = pl.program_id(2)
    # one DMA: the output tile's input window incl. halo (full W, full Cin)
    dma = pltpu.make_async_copy(
        x_hbm.at[b, pl.ds(ti * tb, tb + kt - 1),
                 pl.ds(hi * hb, hb + kh - 1)],
        win_ref, sem)
    dma.start()
    dma.wait()

    cin = w_ref.shape[1]  # the window's C is padded to the lane tile
    cout = o_ref.shape[-1]
    rows = tb * hb * ow
    acc = jnp.zeros((rows, cout), jnp.float32)
    for dt in range(kt):
        for dh in range(kh):
            for dw in range(kw):
                tap = win_ref[dt:dt + tb, dh:dh + hb, dw:dw + ow, :cin]
                acc += jnp.dot(tap.reshape(rows, cin),
                               w_ref[(dt * kh + dh) * kw + dw],
                               preferred_element_type=jnp.float32)
    acc = apply_act(acc + f32_island(b_ref[0]), act)
    o_ref[0] = acc.reshape(tb, hb, ow, cout).astype(o_ref.dtype)


def _conv_call(x, wf, b2d, act: str, interpret: bool):
    kt, kh, kw, cin, cout = wf.shape
    b, t, h, w, _ = x.shape
    tb, hb = _tile_sizes(t, h)
    xp = _pad_for_tiles(x, kt, kh, kw, tb, hb)
    n_t = -(-t // tb)
    n_h = -(-h // hb)
    wflat = wf.reshape(kt * kh * kw, cin, cout)
    return pl.pallas_call(
        functools.partial(_conv_bn_act_kernel, tb=tb, hb=hb, ow=w,
                          kt=kt, kh=kh, kw=kw, act=act),
        out_shape=jax.ShapeDtypeStruct((b, t, h, w, cout), x.dtype),
        grid=(b, n_t, n_h),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((kt * kh * kw, cin, cout),
                         lambda bi, ti, hi: (0, 0, 0)),
            pl.BlockSpec((1, cout), lambda bi, ti, hi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tb, hb, w, cout),
                               lambda bi, ti, hi: (bi, ti, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tb + kt - 1, hb + kh - 1, *xp.shape[3:]),
                       xp.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_CONV_VMEM_LIMIT),
        interpret=interpret,
        name="pva_fused_conv3d_bn_act",
    )(xp, wflat, b2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_pallas(x, wf, b2d, act: str, interpret: bool):
    """act(conv3d_s1(x, wf) + bias), SAME k//2 padding; wf scale-folded."""
    return _conv_call(x, wf, b2d, act, interpret)


def _conv_fwd(x, wf, b2d, act, interpret):
    return _conv_call(x, wf, b2d, act, interpret), (x, wf, b2d)


def _conv_bwd(act, interpret, res, g):
    x, wf, b2d = res
    kt, kh, kw, cin, cout = wf.shape
    z32 = f32_island(_conv_call(x, wf, b2d, "identity", interpret))
    dz32 = f32_island(g) * _act_grad(z32, act)
    dz = end_island(dz32, x.dtype)
    # dx: correlation with the tap-flipped, channel-transposed weights —
    # the stride-1 transpose conv is the same stencil, so the same kernel
    wt = wf[::-1, ::-1, ::-1].transpose(0, 1, 2, 4, 3)
    zeros = jnp.zeros((1, cin), jnp.float32)
    dx = _conv_call(dz, wt, zeros, "identity", interpret)
    # dw: per-tap contractions over the padded input — plain jnp, XLA fuses
    xp = jnp.pad(x, ((0, 0), (kt // 2, kt // 2), (kh // 2, kh // 2),
                     (kw // 2, kw // 2), (0, 0)))
    t, h, w = dz.shape[1:4]
    taps = []
    for dt in range(kt):
        for dh in range(kh):
            for dw in range(kw):
                win = xp[:, dt:dt + t, dh:dh + h, dw:dw + w, :]
                taps.append(jnp.einsum("bthwc,bthwd->cd",
                                       f32_island(win), dz32))
    dwf = end_island(jnp.stack(taps).reshape(kt, kh, kw, cin, cout),
                     wf.dtype)
    db = jnp.sum(dz32, axis=(0, 1, 2, 3))[None, :]
    return dx, dwf, db


_conv_pallas.defvjp(_conv_fwd, _conv_bwd)


# --- depthwise + epilogue ---------------------------------------------------


def _dw_bn_act_kernel(x_hbm, k_ref, b_ref, o_ref, win_ref, sem, *,
                      tb: int, hb: int, ow: int,
                      kt: int, kh: int, kw: int, act: str):
    b = pl.program_id(0)
    ti = pl.program_id(1)
    hi = pl.program_id(2)
    dma = pltpu.make_async_copy(
        x_hbm.at[b, pl.ds(ti * tb, tb + kt - 1),
                 pl.ds(hi * hb, hb + kh - 1)],
        win_ref, sem)
    dma.start()
    dma.wait()

    c = o_ref.shape[-1]  # the window's C is padded to the lane tile
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)  # (tb, hb, ow, C)
    for dt in range(kt):
        for dh in range(kh):
            for dw in range(kw):
                tap = win_ref[dt:dt + tb, dh:dh + hb, dw:dw + ow, :c]
                acc += f32_island(tap) * f32_island(
                    k_ref[(dt * kh + dh) * kw + dw])
    acc = apply_act(acc + f32_island(b_ref[0]), act)
    o_ref[0] = acc.astype(o_ref.dtype)


def _dw_call(x, kf, b2d, act: str, interpret: bool):
    kt, kh, kw, one, c = kf.shape
    b, t, h, w, _ = x.shape
    tb, hb = _tile_sizes(t, h)
    xp = _pad_for_tiles(x, kt, kh, kw, tb, hb)
    n_t = -(-t // tb)
    n_h = -(-h // hb)
    kflat = kf.reshape(kt * kh * kw, c)
    return pl.pallas_call(
        functools.partial(_dw_bn_act_kernel, tb=tb, hb=hb, ow=w,
                          kt=kt, kh=kh, kw=kw, act=act),
        out_shape=jax.ShapeDtypeStruct((b, t, h, w, c), x.dtype),
        grid=(b, n_t, n_h),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((kt * kh * kw, c), lambda bi, ti, hi: (0, 0)),
            pl.BlockSpec((1, c), lambda bi, ti, hi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tb, hb, w, c),
                               lambda bi, ti, hi: (bi, ti, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tb + kt - 1, hb + kh - 1, *xp.shape[3:]),
                       xp.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
        name="pva_fused_depthwise_bn_act",
    )(xp, kflat, b2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dw_pallas(x, kf, b2d, act: str, interpret: bool):
    """act(depthwise_conv3d_s1(x, kf) + bias); kf (kt,kh,kw,1,C)
    scale-folded."""
    return _dw_call(x, kf, b2d, act, interpret)


def _dw_fwd(x, kf, b2d, act, interpret):
    return _dw_call(x, kf, b2d, act, interpret), (x, kf, b2d)


def _dw_bwd(act, interpret, res, g):
    x, kf, b2d = res
    kt, kh, kw = kf.shape[:3]
    z32 = f32_island(_dw_call(x, kf, b2d, "identity", interpret))
    dz32 = f32_island(g) * _act_grad(z32, act)
    dz = end_island(dz32, x.dtype)
    zeros = jnp.zeros((1, kf.shape[-1]), jnp.float32)
    dx = _dw_call(dz, kf[::-1, ::-1, ::-1], zeros, "identity", interpret)
    xp = jnp.pad(x, ((0, 0), (kt // 2, kt // 2), (kh // 2, kh // 2),
                     (kw // 2, kw // 2), (0, 0)))
    t, h, w = dz.shape[1:4]
    rows = []
    for dt in range(kt):
        for dh in range(kh):
            for dw in range(kw):
                tap = xp[:, dt:dt + t, dh:dh + h, dw:dw + w, :]
                rows.append(jnp.sum(f32_island(tap) * dz32,
                                    axis=(0, 1, 2, 3)))
    dkf = end_island(jnp.stack(rows).reshape(kt, kh, kw, 1, -1), kf.dtype)
    db = jnp.sum(dz32, axis=(0, 1, 2, 3))[None, :]
    return dx, dkf, db


_dw_pallas.defvjp(_dw_fwd, _dw_bwd)


# --- XLA lowerings (the production non-TPU path; also autodiff-plain) -------


def _xla_conv_bias_act(x, wf, bias32, act: str):
    """Scale-folded conv + bias + act as ONE fusable XLA chain — the
    `mode="xla"` lowering `mode="auto"` picks off-TPU."""
    y = lax.conv_general_dilated(
        x, wf, (1, 1, 1), [(k // 2, k // 2) for k in wf.shape[:3]],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    return end_island(apply_act(f32_island(y) + bias32, act), x.dtype)


def _xla_dw_bias_act(x, kf, bias32, act: str):
    """Depthwise fold: the tap-decomposition lowering (ops/depthwise.py)
    with the affine folded in — the formulation that beats XLA's grouped
    conv by two orders of magnitude on CPU hosts."""
    y = depthwise_conv3d_shift(x, kf)
    return end_island(apply_act(f32_island(y) + bias32, act), x.dtype)


# --- public dispatchers -----------------------------------------------------


def fused_pointwise_bn_act(x, w, scale, bias, *, act: str = "identity",
                           mode: str = "auto",
                           interpret: Optional[bool] = None):
    """(1,1,1) conv + resolved norm affine + act. x: (B,T,H,W,Cin);
    w: (1,1,1,Cin,Cout) or (Cin,Cout); scale/bias: (Cout,) f32."""
    if w.ndim == 5:
        w = w.reshape(w.shape[-2], w.shape[-1])
    cin, cout = w.shape
    scale32, bias32 = f32_island(scale), f32_island(bias)
    wf = end_island(f32_island(w) * scale32, x.dtype)
    if not _use_pallas(mode):
        y = f32_island(x.reshape(-1, cin) @ wf) + bias32
        y = end_island(apply_act(y, act), x.dtype)
        return y.reshape(*x.shape[:-1], cout)
    y = _pw_pallas(x.reshape(-1, cin), wf, bias32[None, :], act,
                   _interp(interpret))
    return y.reshape(*x.shape[:-1], cout)


def fused_conv3d_bn_act(x, w, scale, bias, *, act: str = "identity",
                        mode: str = "auto",
                        interpret: Optional[bool] = None):
    """Dense stride-1 SAME conv + resolved norm affine + act.
    x: (B,T,H,W,Cin); w: (kt,kh,kw,Cin,Cout) odd taps; scale/bias:
    (Cout,) f32. (1,1,1) weights route to the pointwise matmul kernel;
    even-tap kernels fall back to the XLA lowering (the halo kernel
    hard-codes odd SAME geometry)."""
    kt, kh, kw = w.shape[:3]
    if (kt, kh, kw) == (1, 1, 1):
        return fused_pointwise_bn_act(x, w, scale, bias, act=act,
                                      mode=mode, interpret=interpret)
    scale32, bias32 = f32_island(scale), f32_island(bias)
    wf = end_island(f32_island(w) * scale32, x.dtype)
    if not _use_pallas(mode) or not all(k % 2 for k in (kt, kh, kw)):
        return _xla_conv_bias_act(x, wf, bias32, act)
    return _conv_pallas(x, wf, bias32[None, :], act, _interp(interpret))


def fused_depthwise_bn_act(x, k, scale, bias, *, act: str = "identity",
                           mode: str = "auto",
                           interpret: Optional[bool] = None):
    """Depthwise stride-1 SAME conv + resolved norm affine + act.
    x: (B,T,H,W,C); k: (kt,kh,kw,1,C) odd taps; scale/bias: (C,) f32.
    The per-channel affine scale folds into the per-channel taps."""
    scale32, bias32 = f32_island(scale), f32_island(bias)
    kf = end_island(f32_island(k) * scale32, x.dtype)
    if (not _use_pallas(mode)
            or not all(d % 2 for d in k.shape[:3])):
        return _xla_dw_bias_act(x, kf, bias32, act)
    return _dw_pallas(x, kf, bias32[None, :], act, _interp(interpret))

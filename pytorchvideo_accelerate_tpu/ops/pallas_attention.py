"""Hand-tiled flash attention Pallas kernels for TPU — forward AND backward.

Escape hatch for sizes where XLA's default attention schedule underperforms
(SURVEY §7 hard-part 2: "Pallas kernels as escape hatch"). Forward is the
classic flash-attention recurrence laid out for the TPU memory hierarchy:

- grid (B·H, Nq/block_q, Nk/block_k); the last grid axis is sequential on a
  TensorCore, so VMEM scratch (acc/m/l) persists across K/V blocks of one
  query tile — HBM traffic is one pass over K/V per query tile and a single
  write of the output tile.
- QK^T and PV hit the MXU via `jnp.dot(..., preferred_element_type=f32)`;
  the online-softmax update (exp/max/sum) runs on the VPU in f32.
- m/l running stats live in (block_q, 128) VMEM tiles (lane-dim 128 is the
  minimum f32 tile; every lane carries the same value — broadcast storage
  sidesteps 1-D layout constraints).

Training works: a `jax.custom_vjp` pairs the forward with two backward
kernels (FlashAttention-2 style recomputation, Dao 2023 §3.2):
- forward additionally emits L = m + log(l) (the per-row logsumexp);
- dq kernel, grid (BH, nQ, nK): p = exp(s - L) recomputed blockwise,
  ds = p∘(dO·Vᵀ - Δ), dq += ds·K accumulated in VMEM scratch over K blocks;
- dk/dv kernel, grid (BH, nK, nQ): same recompute with the loop order
  flipped, dv += pᵀ·dO and dk += dsᵀ·Q accumulated over Q blocks;
- Δ = rowsum(dO ∘ O) is a cheap elementwise jnp precompute.

Numerics match `ops.attention.dense_attention` to f32 rounding: accumulation
is f32 regardless of input dtype (bf16 in, bf16 out, f32 inside).

On non-TPU backends the kernels run in interpreter mode so the same code
path is unit-testable on the 8-fake-CPU-device harness (SURVEY §4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.precision import f32_island
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # broadcast width for per-row stats (min f32 lane tile)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, nk_valid: int, block_k: int):
    ki = pl.program_id(2)
    nk_blocks = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                     # (bq, D)
    k = k_ref[0]                                     # (bk, D)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    # mask K padding (Nk rounded up to a block multiple)
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < nk_valid, s, NEG_INF)

    m_prev = m_ref[:, 0:1]                           # (bq, 1)
    l_prev = l_ref[:, 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                           # (bq, bk) f32
    alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk_blocks - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale: float, nk_valid: int, block_k: int):
    ki = pl.program_id(2)
    nk_blocks = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < nk_valid, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, 0:1])              # (bq, bk); 0 for padding
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, 0:1]) * scale     # (bq, bk) f32
    acc_ref[:] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk_blocks - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, nk_valid: int, block_k: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq_blocks = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < nk_valid, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, 0:1])              # (bq, bk)
    dv_acc[:] += jnp.dot(p.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, 0:1]) * scale
    dk_acc[:] += jnp.dot(ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == nq_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_seq(x, block):
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _fwd_call(q, k, v, scale, block_q, block_k, interpret):
    BH, nq, D = q.shape
    nk = k.shape[1]
    q = _pad_seq(q, block_q)
    k = _pad_seq(k, block_k)
    v = _pad_seq(v, block_k)
    nq_p, nk_p = q.shape[1], k.shape[1]
    grid = (BH, nq_p // block_q, nk_p // block_k)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, nk_valid=nk, block_k=block_k),
        out_shape=[
            jax.ShapeDtypeStruct((BH, nq_p, D), q.dtype),
            jax.ShapeDtypeStruct((BH, nq_p, LANES), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :nq], lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhnd(q, k, v, scale, block_q, block_k, interpret):
    """q/k/v: (BH, N, D) -> (BH, Nq, D)."""
    out, _ = _fwd_call(q, k, v, scale, block_q, block_k, interpret)
    return out


def _flash_bhnd_fwd(q, k, v, scale, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bhnd_bwd(scale, block_q, block_k, interpret, res, dout):
    q, k, v, out, lse = res
    BH, nq, D = q.shape
    nk = k.shape[1]

    # Δ_i = Σ_d dO_id · O_id, broadcast over lanes for tiled VMEM access
    delta = jnp.broadcast_to(
        jnp.sum(f32_island(dout) * f32_island(out),
                axis=-1, keepdims=True),
        (BH, nq, LANES),
    )

    qp = _pad_seq(q, block_q)
    dop = _pad_seq(dout, block_q)
    lsep = _pad_seq(lse, block_q)
    deltap = _pad_seq(delta, block_q)
    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    nq_p, nk_p = qp.shape[1], kp.shape[1]
    # padded-q rows: lse is finite (they attended real keys in fwd) and
    # dout rows are zero, so their ds/dv contributions vanish

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    stat_spec = pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, nk_valid=nk,
                          block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((BH, nq_p, D), q.dtype),
        grid=(BH, nq_p // block_q, nk_p // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    # loop order flipped: K/V block fixed per grid row, Q blocks stream
    q_spec2 = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    stat_spec2 = pl.BlockSpec((1, block_q, LANES), lambda b, j, i: (b, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, nk_valid=nk,
                          block_k=block_k),
        out_shape=[
            jax.ShapeDtypeStruct((BH, nk_p, D), k.dtype),
            jax.ShapeDtypeStruct((BH, nk_p, D), v.dtype),
        ],
        grid=(BH, nk_p // block_k, nq_p // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, stat_spec2, stat_spec2],
        out_specs=[k_spec2, k_spec2],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    return dq[:, :nq], dk[:, :nk], dv[:, :nk]


_flash_bhnd.defvjp(_flash_bhnd_fwd, _flash_bhnd_bwd)


def flash_attention(q, k, v, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Flash attention, API-compatible with `dense_attention`; differentiable
    (custom VJP backed by Pallas backward kernels).

    q: (B, Nq, H, D); k/v: (B, Nkv, H, D) -> (B, Nq, H, D). Sequence lengths
    need not be block multiples (padded + masked internally). `interpret`
    defaults to True off-TPU so tests run on CPU.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, nq, H, D = q.shape
    nkv = k.shape[1]

    def fold(x):   # (B, N, H, D) -> (B*H, N, D)
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    out = _flash_bhnd(fold(q), fold(k), fold(v), float(scale),
                      min(block_q, _round_up(nq)), min(block_k, _round_up(nkv)),
                      bool(interpret))
    return out.reshape(B, H, nq, D).transpose(0, 2, 1, 3)


def _round_up(n: int, mult: int = 8) -> int:
    return ((n + mult - 1) // mult) * mult

"""Hand-tiled Pallas depthwise conv3d — the halo-tile lowering.

Third lowering for the depthwise spatiotemporal convs (X3D conv_b/stem_t,
ir-CSN conv_b, MViT pooling; SURVEY §2.3-N3 "Pallas kernels where XLA conv
layouts underperform"). The existing options trade differently:

- XLA grouped conv: MXU path, but 1-channel groups tile the systolic
  array badly;
- shift decomposition (ops/depthwise.py): kt*kh*kw fused VPU FMAs, but
  XLA materializes strided windows per tap — up to 27x read amplification
  against HBM if the fusion re-reads.

This kernel makes the bandwidth bound explicit: the grid tiles the OUTPUT
over (batch, t-tiles, h-tiles); each program DMAs ONE overlapping input
window (the tile plus its (k-1)-halo, full W and C) from HBM into VMEM,
then accumulates all taps on the VPU in f32 from that single resident
copy — each input element crosses HBM->VMEM once per tile (plus halo
overlap ~ (tb+2)(hb+2)/(tb*hb) ≈ 1.56x at 8x8 tiles), and the output
tile is written once.

Honest bandwidth accounting: the wrapper pre-pads the input with
`jnp.pad` (pallas_call is opaque to XLA, so the padded tensor
materializes in HBM — one extra read+write of x per call, ~2x on top of
the kernel's own traffic). The same pad also rounds W (halo included) up
to the dtype's sublane tile and C up to the 128 lanes: the v5e compiler
takes the window DMA's HBM slice only when its two minor dims are whole
tiles, and no X3D width (54/108/216/432) is one — at C=54 the padded
tensor is 2.4x the bytes (docs/KERNELS.md § what the v5e compiler
accepts). Net at C a multiple of 128: ~3.5x input reads vs the shift path's
up-to-27x if XLA's tap fusion re-reads per tap — still the bandwidth
favorite on paper, but the pad copy is why this is an A/B candidate and
not a default. In-kernel clamped DMA windows would remove the copy at
the cost of per-tile boundary masking; do that if the sweep shows this
lowering winning but by less than the pad traffic. Whether any of it
beats XLA's schedule is a device question: `depthwise_roofline` in
`x3d_s.train` (BENCHMARK.json) reads the lowering the config selects.

Scope: stride 1 (the 22/26 X3D and 29/33 ir-CSN blocks; strided stage
entries fall back to the XLA grouped path in ops/depthwise.py). Training
works: a `jax.custom_vjp` reuses the SAME kernel for dx (correlation with
the tap-flipped kernel — the stride-1 transpose conv) and computes dk
with plain jnp strided reductions (27 elementwise dot products, cheap and
fusible; no kernel needed).

On non-TPU backends the kernel runs in interpreter mode so the identical
code path is unit-testable on the CPU harness (SURVEY §4), matching
ops/pallas_attention.py's convention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.precision import f32_island
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # minor-dim tile of every TPU memory layout

def _dw_kernel(x_hbm, k_ref, o_ref, win_ref, sem, *,
               tb: int, hb: int, ow: int, kt: int, kh: int, kw: int):
    b = pl.program_id(0)
    ti = pl.program_id(1)
    hi = pl.program_id(2)
    # one DMA: the output tile's input window incl. halo (padded W and C)
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
                acc += f32_island(tap) * f32_island(k_ref[
                    (dt * kh + dh) * kw + dw])
    o_ref[0] = acc.astype(o_ref.dtype)


def _dw_call(xp, kernel, dims, out_t: int, out_h: int, out_w: int,
             tb: int, hb: int, interpret: bool):
    """xp: `_pad_for_tiles` output (B, Tp, Hp, Wp, Cp) with Tp >= n_t*tb +
    kt - 1 and Hp >= n_h*hb + kh - 1; kernel (kt*kh*kw, C) at the true C,
    which is also the output's."""
    B = xp.shape[0]
    taps, c = kernel.shape
    kt, kh, kw = dims
    n_t = -(-out_t // tb)
    n_h = -(-out_h // hb)
    return pl.pallas_call(
        functools.partial(_dw_kernel, tb=tb, hb=hb, ow=out_w,
                          kt=kt, kh=kh, kw=kw),
        out_shape=jax.ShapeDtypeStruct((B, out_t, out_h, out_w, c),
                                       xp.dtype),
        grid=(B, n_t, n_h),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((taps, c), lambda b, ti, hi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tb, hb, out_w, c),
                               lambda b, ti, hi: (b, ti, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tb + kt - 1, hb + kh - 1, *xp.shape[3:]), xp.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
        name="pva_depthwise3d_s1",
    )(xp, kernel)


def _pad_for_tiles(x, kt, kh, kw, tb, hb):
    """SAME-pad plus tail padding so every (tb, hb) output tile's input
    window exists in the array, with the two minor dims rounded up to whole
    (sublane, lane) tiles — W+halo to 8 rows of 32 bits (16 for bf16), C to
    128 — which is what Mosaic asks of the slice the window DMA reads."""
    b, t, h, w, c = x.shape
    n_t = -(-t // tb)
    n_h = -(-h // hb)
    pt, ph, pw = kt // 2, kh // 2, kw // 2
    sublanes = 8 * (4 // jnp.dtype(x.dtype).itemsize)
    return jnp.pad(x, (
        (0, 0),
        (pt, pt + (n_t * tb - t)),
        (ph, ph + (n_h * hb - h)),
        (pw, pw + (-(w + 2 * pw)) % sublanes),
        (0, (-c) % LANES),
    ))


def _tile_sizes(t: int, h: int) -> tuple:
    return min(8, t), min(8, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def pallas_depthwise3d_s1(x, kernel, interpret: Optional[bool] = None):
    """Depthwise conv3d, stride 1, SAME (k//2) padding, no bias.

    x: (B, T, H, W, C) NDHWC; kernel: (kt, kh, kw, 1, C) — the exact
    `nn.Conv(feature_group_count=C)` parameter layout (ops/depthwise.py).
    f32 accumulation, result cast to x.dtype (same contract as the other
    two lowerings)."""
    return _forward(x, kernel, interpret)


def _forward(x, kernel, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kt, kh, kw, one, c = kernel.shape
    assert one == 1, f"expected (kt,kh,kw,1,C), got {kernel.shape}"
    b, t, h, w, _ = x.shape
    tb, hb = _tile_sizes(t, h)
    xp = _pad_for_tiles(x, kt, kh, kw, tb, hb)
    flat = f32_island(kernel.reshape(kt * kh * kw, c))
    return _dw_call(xp, flat, (kt, kh, kw), t, h, w, tb, hb, interpret)


def _fwd(x, kernel, interpret):
    return _forward(x, kernel, interpret), (x, kernel)


def _bwd(interpret, res, dy):
    x, kernel = res
    kt, kh, kw = kernel.shape[:3]
    # dx: correlation of dy with the tap-flipped kernel — the stride-1
    # depthwise transpose is the same stencil, so the same Pallas kernel
    # serves the backward data path
    flipped = kernel[::-1, ::-1, ::-1]
    dx = _forward(dy, flipped, interpret).astype(x.dtype)
    # dk: 27 strided elementwise dots — plain jnp, XLA fuses
    xp = jnp.pad(x, ((0, 0), (kt // 2, kt // 2), (kh // 2, kh // 2),
                     (kw // 2, kw // 2), (0, 0)))
    t, h, w = dy.shape[1:4]
    dy32 = f32_island(dy)
    rows = []
    for dt in range(kt):
        for dh in range(kh):
            for dw in range(kw):
                tap = xp[:, dt:dt + t, dh:dh + h, dw:dw + w, :]
                rows.append(jnp.sum(f32_island(tap) * dy32,
                                    axis=(0, 1, 2, 3)))
    dk = jnp.stack(rows).reshape(kt, kh, kw, 1, -1).astype(kernel.dtype)
    return dx, dk


pallas_depthwise3d_s1.defvjp(_fwd, _bwd)

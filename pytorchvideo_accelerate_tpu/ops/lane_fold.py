"""Lane-filling lowering of an RGB stem conv (docs/KERNELS.md, "lane fold").

A conv from 3 input channels to 8 (SlowFast's fast stem) or 64 (the slow
stem, resnet3d, r2plus1d, csn) leaves the TPU's 128 lanes mostly empty:
XLA lays the 8-channel output out 8 to a 128-lane tile and moves 16 times
its bytes. The fold fills them with the same products:

- `G = 128 // Cout` adjacent output columns go into the channel axis,
  `(B,T,H',W',Cout) -> (B,T,H',W'/G, G*Cout)`, a free row-major reshape;
- the `G*sw` input columns they start from go into the input's channel
  axis, `(B,T,H,W,Cin) -> (B,T,H, W/(G*sw), G*sw*Cin)`, as free;
- the (kt,kh,kw,Cin,Cout) kernel is expanded to the block-Toeplitz weight
  (kt,kh,nb, G*sw*Cin, G*Cout) by a constant 0/1 mask: entry
  (block b, input column p, output column g) holds tap
  `dw = (b+lo)*G*sw + p - g*sw + pw` where that is a tap, else zero;
- one conv over (kt, kh, nb column blocks), W stride 1, gives the folded
  output. Same products, same accumulation, zeros added.

The parameter stays the (kt,kh,kw,Cin,Cout) kernel; autodiff carries the
weight gradient back through the mask. `fold_group` is the shape rule, and
with `takes_fold` the whole of the choice: no knob selects this path.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LANES = 128
# "few input channels": an image or flow stem. Beyond it the input's own
# lanes fill as the network widens, and the fold's zero products (nb*G*sw
# columns for kw taps) buy nothing
MAX_FOLD_CIN = 4


_sites: contextvars.ContextVar = contextvars.ContextVar(
    "pva_lane_fold_sites", default=None)


@contextlib.contextmanager
def count_sites() -> Iterator[Set[tuple]]:
    """Collects the module paths of the sites that take the fold while a
    model is traced inside the block: the lowering is static, so its
    engagement is a fact of the trace (the `pva_conv_lane_fold_sites`
    gauge, trainer/steps.py)."""
    sites: Set[tuple] = set()
    token = _sites.set(sites)
    try:
        yield sites
    finally:
        _sites.reset(token)


def note_site(path: tuple) -> None:
    sites = _sites.get()
    if sites is not None:
        sites.add(tuple(path))


def takes_fold() -> bool:
    """The backend half of the rule (`pallas_fused._use_pallas`'s): on the
    CPU the fold is 2 to 14 times the FLOPs for nothing."""
    return jax.default_backend() == "tpu"


def fold_group(cin: int, cout: int, kernel: Sequence[int],
               stride: Sequence[int], width: int) -> int:
    """Output columns folded into the channel axis at this site, 0 where
    the fold does not apply: needs few input channels, an output narrower
    than a lane tile, an odd W tap count (padding kw//2 then gives W/sw
    columns), and W a multiple of the folded input block."""
    group = LANES // cout
    if cin > MAX_FOLD_CIN or group < 2 or kernel[2] % 2 == 0:
        return 0
    return group if width % (group * stride[2]) == 0 else 0


def _toeplitz_mask(kw: int, sw: int, group: int) -> Tuple[np.ndarray, int]:
    """(mask[nb, G*sw, G, kw] of 0/1, lo): mask[b, p, g, dw] = 1 where
    input column p of block j+b+lo is tap dw of output column g of block j."""
    pw, block = kw // 2, group * sw
    lo = (-pw) // block
    hi = ((group - 1) * sw - pw + kw - 1) // block
    b = np.arange(lo, hi + 1)[:, None, None]
    p = np.arange(block)[None, :, None]
    g = np.arange(group)[None, None, :]
    dw = b * block + p - g * sw + pw  # (nb, block, G)
    mask = dw[..., None] == np.arange(kw)
    return mask.astype(np.float32), lo


def fold_kernel(w, sw: int, group: int):
    """(kt,kh,kw,Cin,Cout) -> (kt,kh,nb, G*sw*Cin, G*Cout), and the left
    padding in blocks. Exact in any dtype: every sum has one term."""
    kt, kh, kw, cin, cout = w.shape
    mask, lo = _toeplitz_mask(kw, sw, group)
    wt = jnp.einsum("bpgd,thdio->thbpigo", jnp.asarray(mask, w.dtype), w,
                    precision=lax.Precision.HIGHEST)
    return wt.reshape(kt, kh, mask.shape[0], group * sw * cin,
                      group * cout), -lo


def lane_fold_conv3d(x, w, stride: Sequence[int], group: int):
    """`lax.conv_general_dilated(x, w, stride, padding k//2)` of an NDHWC
    `x`, as the folded contraction. Returns the output FOLDED,
    (B, T', H', W'/G, G*Cout) with channel index g*Cout + c: `unfold` is
    the free reshape back."""
    b, t, h, width, cin = x.shape
    kt, kh = w.shape[:2]
    st, sh, sw = stride
    wt, left = fold_kernel(w, sw, group)
    xf = x.reshape(b, t, h, width // (group * sw), group * sw * cin)
    return lax.conv_general_dilated(
        xf, wt, window_strides=(st, sh, 1),
        padding=[(kt // 2, kt // 2), (kh // 2, kh // 2),
                 (left, wt.shape[2] - 1 - left)],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))


def unfold(y, group: int):
    """(B,T,H,W/G, G*C) -> (B,T,H,W,C)."""
    b, t, h, wb, gc = y.shape
    return y.reshape(b, t, h, wb * group, gc // group)

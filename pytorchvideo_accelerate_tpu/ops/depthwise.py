"""Depthwise 3D convolution implementations.

X3D is depthwise-conv-bound (every block's spatiotemporal conv_b is
depthwise, SURVEY §7 hard-part 2; BASELINE config 2), and MViT's pooling
convs are depthwise too. XLA:TPU lowers `feature_group_count=C` convs
through the grouped-convolution path, which tiles onto the MXU badly at
small per-group sizes (1 input channel per group = 1-deep matmuls). The
alternative here decomposes the depthwise conv into its taps: for a
k_t x k_h x k_w kernel, the output is a sum of k_t*k_h*k_w shifted,
per-channel-scaled copies of the input — pure VPU multiply-adds that XLA
fuses into one bandwidth-bound loop, no MXU involvement at all. For 3x3x3
that is 27 fused FMAs over the tensor: arithmetic intensity is low but so
is the op's share of FLOPs; what matters is not starving on a bad grouped
matmul schedule.

A third lowering, `pallas`, is the hand-tiled halo kernel
(ops/pallas_depthwise.py): one HBM->VMEM DMA per output tile (tile +
halo), all taps accumulated from the single VMEM-resident window — the
explicit-bandwidth answer where the shift decomposition's fused reads
may re-amplify. Stride-1 only (the non-entry blocks, which dominate);
strided calls under `pallas` fall back to the XLA grouped path.

Which implementation wins is an empirical, device-level question (the
benchmark's `depthwise_roofline` reads the one selected). All impls create the
SAME parameter ("kernel", shape (kt, kh, kw, 1, C)) at the module's own
scope — exactly the tree `nn.Conv(feature_group_count=C, name=<same>)`
would create — so converted/pretrained checkpoints load identically and
the choice is a deployment knob (`--model.depthwise_impl
conv|shift|pallas`), not a model change.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from pytorchvideo_accelerate_tpu.precision import f32_island


def depthwise_conv3d_shift(x, kernel, stride: Tuple[int, int, int] = (1, 1, 1),
                           padding: Tuple[int, int, int] = None):
    """Shift-and-accumulate depthwise conv.

    x: (B, T, H, W, C) NDHWC; kernel: (kt, kh, kw, 1, C) — the exact
    `nn.Conv(feature_group_count=C)` parameter layout. padding defaults to
    k//2 per dim (the package-wide conv padding convention, common.py).

    Accumulates in float32 regardless of input dtype (the grouped-conv MXU
    path accumulates in f32 too — chaining 26 bf16 adds would make the two
    lowerings diverge); the result is cast back to x.dtype.
    """
    kt, kh, kw, one, C = kernel.shape
    assert one == 1, f"expected depthwise kernel (kt,kh,kw,1,C), got {kernel.shape}"
    assert x.shape[-1] == C, (x.shape, kernel.shape)
    if padding is None:
        padding = (kt // 2, kh // 2, kw // 2)
    st, sh, sw = stride
    pt, ph, pw = padding
    xp = jnp.pad(x, ((0, 0), (pt, pt), (ph, ph), (pw, pw), (0, 0)))
    B = x.shape[0]
    T, H, W = x.shape[1:4]
    ot = (T + 2 * pt - kt) // st + 1
    oh = (H + 2 * ph - kh) // sh + 1
    ow = (W + 2 * pw - kw) // sw + 1

    kernel32 = f32_island(kernel)
    out = None
    for it in range(kt):
        for ih in range(kh):
            for iw in range(kw):
                tap = lax.slice(
                    xp,
                    (0, it, ih, iw, 0),
                    (B, it + (ot - 1) * st + 1, ih + (oh - 1) * sh + 1,
                     iw + (ow - 1) * sw + 1, C),
                    (1, st, sh, sw, 1),
                )
                term = f32_island(tap) * kernel32[it, ih, iw, 0]
                out = term if out is None else out + term
    return out.astype(x.dtype)


class DepthwiseConv3D(nn.Module):
    """Depthwise conv3d with a selectable lowering, k//2 padding, no bias.

    Drop-in for `nn.Conv(C, kernel_size, strides, padding=[(k//2, k//2)...],
    feature_group_count=C, use_bias=False, name=<n>)`: the parameter is
    created at this module's own scope as "kernel" with the identical
    (kt, kh, kw, 1, C) shape, so the param path `<n>/kernel` — what the
    converter and existing checkpoints use — is unchanged by the swap.
    """

    features: int
    kernel_size: Tuple[int, int, int]
    stride: Tuple[int, int, int] = (1, 1, 1)
    impl: str = "conv"  # conv (XLA grouped) | shift (taps) | pallas (halo)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        if self.impl not in ("conv", "shift", "pallas"):
            raise ValueError(
                f"depthwise impl must be conv|shift|pallas, got {self.impl!r}")
        kt, kh, kw = self.kernel_size
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (kt, kh, kw, 1, self.features),
            jnp.float32,
        )
        x = x.astype(self.dtype)
        kernel = kernel.astype(self.dtype)
        if self.impl == "shift":
            return depthwise_conv3d_shift(x, kernel, self.stride)
        if (self.impl == "pallas" and self.stride == (1, 1, 1)
                and all(k % 2 for k in self.kernel_size)):
            from pytorchvideo_accelerate_tpu.ops.pallas_depthwise import (
                pallas_depthwise3d_s1,
            )

            return pallas_depthwise3d_s1(x, kernel)
        # strided or even-kernel pallas calls fall through to the XLA
        # grouped path (the halo kernel hard-codes odd-kernel SAME
        # semantics; every in-tree consumer is odd, but an even kernel
        # must not silently change function)
        return lax.conv_general_dilated(
            x, kernel,
            window_strides=self.stride,
            padding=[(k // 2, k // 2) for k in self.kernel_size],
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
            feature_group_count=self.features,
        )

"""X3D networks (XS/S/M), TPU-native.

BASELINE config 2 ("X3D-S on Kinetics-700, single v5e chip, bf16") names this
family; the reference stack ships it via the same pytorchvideo hub the
SlowFast models come from (run.py:107 [external]). Architecture per
Feichtenhofer 2020 ("X3D: Expanding Architectures for Efficient Video
Recognition", arXiv:2004.04730) with pytorchvideo's instantiation constants:

- stem: 3x3 spatial conv (stride 2) then 5x1x1 depthwise temporal conv, 24ch
- 4 stages of inverted-bottleneck blocks (depths 3/5/11/7 at depth-factor
  2.2): 1x1x1 expand (x2.25) -> 3x3x3 depthwise (SE every other block,
  swish) -> 1x1x1 project; spatial stride 2 at each stage entry
- conv5: 1x1x1 to 432 = round(192 * 2.25); head: 1x1x1 to 2048 -> global
  avg pool -> dropout -> linear

Depthwise 3D convs map to XLA:TPU grouped convolution; channels are kept at
multiples of 8/24 per the paper, padded to lane width by XLA.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorchvideo_accelerate_tpu.precision import f32_island

from pytorchvideo_accelerate_tpu.models.common import (
    BNAffine,
    ConvBNAct,
    ConvKernelParam,
    Dtype,
    fused_train_norm_act,
)
from pytorchvideo_accelerate_tpu.ops.depthwise import DepthwiseConv3D


def _round_width(width: int, multiplier: float, min_depth: int = 8, divisor: int = 8) -> int:
    """Channel rounding (paper appendix; pytorchvideo round_width)."""
    if not multiplier:
        return width
    width *= multiplier
    new_width = max(min_depth, int(width + divisor / 2) // divisor * divisor)
    if new_width < 0.9 * width:
        new_width += divisor
    return int(new_width)


class SqueezeExcite(nn.Module):
    """SE over (T,H,W)-pooled features, ratio 1/16 (paper §3)."""

    channels: int
    ratio: float = 0.0625
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        se_ch = _round_width(self.channels, self.ratio, min_depth=8, divisor=8)
        s = jnp.mean(x, axis=(1, 2, 3), keepdims=True)
        s = nn.Conv(se_ch, (1, 1, 1), dtype=self.dtype, name="fc1")(s)
        s = nn.relu(s)
        s = nn.Conv(self.channels, (1, 1, 1), dtype=self.dtype, name="fc2")(s)
        return x * nn.sigmoid(s)


class X3DBlock(nn.Module):
    """Inverted bottleneck: expand -> depthwise 3x3x3 (+SE, swish) -> project."""

    features_out: int
    features_inner: int
    spatial_stride: int = 1
    use_se: bool = False
    depthwise_impl: str = "conv"
    fused: str = "off"  # common.FUSED_MODES; strided blocks auto-fallback
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = ConvBNAct(self.features_inner, kernel=(1, 1, 1),
                      fused=self.fused,
                      dtype=self.dtype, name="conv_a")(x, train)
        if self.fused != "off" and self.spatial_stride == 1:
            # fused depthwise conv_b + BN (+ swish when no SE sits between)
            # through ops/pallas_fused — same conv_b/norm_b param tree
            y = self._fused_conv_b(y, train)
        else:
            # depthwise spatiotemporal conv (selectable lowering,
            # ops/depthwise); strided stage entries always land here
            y = DepthwiseConv3D(self.features_inner, kernel_size=(3, 3, 3),
                                stride=(1, self.spatial_stride,
                                        self.spatial_stride),
                                impl=self.depthwise_impl, dtype=self.dtype,
                                name="conv_b")(y)
            y = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.dtype, name="norm_b")(y)
            if self.use_se:
                y = SqueezeExcite(self.features_inner, dtype=self.dtype,
                                  name="se")(y)
            y = nn.swish(y)
        y = ConvBNAct(self.features_out, kernel=(1, 1, 1), act=None,
                      fused=self.fused,
                      dtype=self.dtype, name="conv_c")(y, train)
        if residual.shape[-1] != self.features_out or self.spatial_stride != 1:
            # pytorchvideo x3d.py quirk (create_x3d_res_block): the shortcut
            # conv appears for stride OR channel change, but its BN only for
            # channel change — stage-1 block 0 (24->24, stride 2) in the hub
            # X3D checkpoints has branch1_conv with NO branch1_norm
            residual = ConvBNAct(self.features_out, kernel=(1, 1, 1),
                                 stride=(1, self.spatial_stride, self.spatial_stride),
                                 act=None, dtype=self.dtype,
                                 use_bn=residual.shape[-1] != self.features_out,
                                 name="branch1")(residual, train)
        return nn.relu(residual + y)

    def _fused_conv_b(self, y, train: bool):
        from pytorchvideo_accelerate_tpu.ops.pallas_fused import (
            fused_depthwise_bn_act,
        )

        c = self.features_inner
        k = ConvKernelParam(c, (3, 3, 3), c, groups=c, name="conv_b")()
        bn = BNAffine(momentum=0.9, eps=1e-5, name="norm_b")
        # SE reads the NORMALIZED pre-activation, so with SE the fused
        # epilogue stops at the affine; without it swish fuses in too
        epilogue = "identity" if self.use_se else "silu"
        y = y.astype(self.dtype)
        k = k.astype(self.dtype)
        # under the scope DepthwiseConv3D(name="conv_b") would open, so the
        # layer's device time is found by its path whatever the lowering
        if train:
            with jax.named_scope("conv_b"):
                raw = fused_depthwise_bn_act(
                    y, k, jnp.ones((c,), jnp.float32),
                    jnp.zeros((c,), jnp.float32), act="identity",
                    mode=self.fused)
            y = fused_train_norm_act(raw, bn, c, epilogue, self.dtype)
        else:
            mul, add = bn(c, train=False)
            with jax.named_scope("conv_b"):
                y = fused_depthwise_bn_act(y, k, mul, add, act=epilogue,
                                           mode=self.fused)
        if self.use_se:
            y = SqueezeExcite(c, dtype=self.dtype, name="se")(y)
            y = nn.swish(y)
        return y


class X3D(nn.Module):
    num_classes: int
    depths: Tuple[int, ...] = (3, 5, 11, 7)
    stem_features: int = 24
    stage_features: Tuple[int, ...] = (24, 48, 96, 192)
    expansion: float = 2.25
    head_features: int = 2048
    dropout_rate: float = 0.5
    depthwise_impl: str = "conv"  # conv | shift (ops/depthwise.py)
    fused: str = "off"  # common.FUSED_MODES (ModelConfig.fused_kernels)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        from pytorchvideo_accelerate_tpu.ops.pallas_fused import (
            fused_depthwise_bn_act,
        )

        x = x.astype(self.dtype)
        # stem: spatial then depthwise-temporal conv
        x = nn.Conv(self.stem_features, (1, 3, 3), strides=(1, 2, 2),
                    padding=[(0, 0), (1, 1), (1, 1)], use_bias=False,
                    dtype=self.dtype, name="stem_xy")(x)
        if self.fused != "off":
            # fused stem_t depthwise + stem_norm + relu (same param tree)
            sf = self.stem_features
            k = ConvKernelParam(sf, (5, 1, 1), sf, groups=sf,
                                name="stem_t")().astype(self.dtype)
            bn = BNAffine(momentum=0.9, eps=1e-5, name="stem_norm")
            if train:
                with jax.named_scope("stem_t"):
                    raw = fused_depthwise_bn_act(
                        x, k, jnp.ones((sf,), jnp.float32),
                        jnp.zeros((sf,), jnp.float32), act="identity",
                        mode=self.fused)
                x = fused_train_norm_act(raw, bn, sf, "relu", self.dtype)
            else:
                mul, add = bn(sf, train=False)
                with jax.named_scope("stem_t"):
                    x = fused_depthwise_bn_act(x, k, mul, add, act="relu",
                                               mode=self.fused)
        else:
            x = DepthwiseConv3D(self.stem_features, (5, 1, 1),
                                impl=self.depthwise_impl, dtype=self.dtype,
                                name="stem_t")(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.dtype,
                             name="stem_norm")(x)
            x = nn.relu(x)

        for stage_idx, depth in enumerate(self.depths):
            f_out = self.stage_features[stage_idx]
            f_inner = int(round(f_out * self.expansion))
            for i in range(depth):
                x = X3DBlock(
                    features_out=f_out,
                    features_inner=f_inner,
                    spatial_stride=2 if i == 0 else 1,
                    use_se=(i % 2 == 0),  # SE every other block (paper §3)
                    depthwise_impl=self.depthwise_impl,
                    fused=self.fused,
                    dtype=self.dtype,
                    name=f"res{stage_idx + 2}_block{i}",
                )(x, train)

        # conv5 + head (pytorchvideo create_x3d_head / ProjectedPool order:
        # pre_conv -> BN -> relu -> GLOBAL POOL -> post_conv -> relu — the
        # 2048-d projection runs on pooled features, per the X3D paper; the
        # ReLU between makes the order numerically load-bearing for
        # converted weights, and pooling first is also cheaper)
        f5 = int(round(self.stage_features[-1] * self.expansion))
        x = ConvBNAct(f5, kernel=(1, 1, 1), fused=self.fused,
                      dtype=self.dtype, name="conv5")(x, train)
        x = jnp.mean(x, axis=(1, 2, 3), keepdims=True)  # (B,1,1,1,C)
        x = nn.Conv(self.head_features, (1, 1, 1), use_bias=False,
                    dtype=self.dtype, name="head_conv")(x)
        x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = nn.Dropout(rate=self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="proj")(
            f32_island(x)
        )
        return x

    @staticmethod
    def backbone_param_filter(path: Tuple[str, ...]) -> bool:
        return path[0] not in ("proj", "head_conv")

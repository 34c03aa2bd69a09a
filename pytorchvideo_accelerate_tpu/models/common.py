"""Shared building blocks for 3D-CNN video backbones.

Layout: all video tensors are **NDHWC** = (batch, time, height, width,
channels) — channels-last so XLA:TPU tiles convs onto the MXU without
transposes (the reference's torch models are NCTHW; the converter in
models/convert.py handles the permutation). Compute dtype is bf16 by policy,
params fp32 (SURVEY §2.3-N7: no GradScaler needed on TPU).

BatchNorm semantics: under pjit data-parallelism the batch axis is one global
sharded tensor, so batch statistics are computed over the *global* batch —
i.e. sync-BN by construction. The reference's DDP computes per-replica stats
(torch BN default); global stats are strictly more stable, and at the
reference's per-replica batch of 8 the difference is one of its known DP
quirks (SURVEY §2 "hard parts" #4) resolved in the TPU-native direction.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from pytorchvideo_accelerate_tpu.ops import lane_fold
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

Dtype = Any

# the fused-kernel lowering knob threaded from ModelConfig.fused_kernels
# (docs/KERNELS.md): "off" = today's unfused graph byte-for-byte; "auto" =
# Pallas kernels on TPU / folded-XLA elsewhere; "pallas"/"xla" force one
# lowering (parity tests, graphcheck)
FUSED_MODES = ("off", "auto", "pallas", "xla")


def fusable_act_name(act: Optional[Callable]) -> Optional[str]:
    """Map a ConvBNAct activation callable onto the fused-epilogue act
    vocabulary (ops/pallas_fused.FUSED_ACTS); None = not fusable (an
    unrecognized callable keeps the unfused path rather than silently
    changing function)."""
    if act is None:
        return "identity"
    if act in (nn.relu,):
        return "relu"
    if act in (nn.swish, nn.silu):
        return "silu"
    return None


class ConvKernelParam(nn.Module):
    """Creates exactly the parameter `nn.Conv(..., use_bias=False)` would —
    one "kernel" of shape (*kernel_size, Cin/groups, Cout), lecun-normal —
    at this module's own scope, WITHOUT running the conv. The fused
    lowerings consume the raw weight (they fold the norm scale into it),
    and naming the module like the nn.Conv it replaces keeps the param
    tree byte-identical across the `fused_kernels` knob, so checkpoints
    and converted weights load unchanged (the DepthwiseConv3D contract,
    applied to dense convs)."""

    features: int
    kernel: Tuple[int, int, int]
    in_features: int
    groups: int = 1

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (*self.kernel, self.in_features // self.groups, self.features),
            jnp.float32,
        )


class BNAffine(nn.Module):
    """Owns exactly the `nn.BatchNorm` param/variable tree ("scale"/"bias"
    params, "mean"/"var" batch_stats) but returns the RESOLVED per-channel
    (mul, add) affine instead of applying it — the form the fused kernels
    fold into their weights/epilogue (ops/pallas_fused.py).

    Eval: mul/add from the running stats — the whole norm is two (C,)
    vectors, so conv+norm+act collapses into one kernel. Train: the caller
    computes the batch stats of the raw conv output (they need the conv
    result, so they cannot live in here) and passes them in; running
    averages update exactly like nn.BatchNorm's (momentum form, f32)."""

    momentum: float = 0.9
    eps: float = 1e-5

    @nn.compact
    def __call__(self, features: int, batch_mean=None, batch_var=None,
                 train: bool = False):
        scale = self.param("scale", nn.initializers.ones,
                           (features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (features,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((features,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((features,), jnp.float32))
        if train:
            mean, var = batch_mean, batch_var
            if not self.is_initializing():
                ra_mean.value = (self.momentum * ra_mean.value
                                 + (1.0 - self.momentum) * mean)
                ra_var.value = (self.momentum * ra_var.value
                                + (1.0 - self.momentum) * var)
        else:
            mean, var = ra_mean.value, ra_var.value
        mul = scale * lax.rsqrt(var + self.eps)
        return mul, bias - mean * mul


def batch_norm_stats(raw32, group: int = 1):
    """Per-channel batch (mean, var) of a raw conv output, f32, the
    fast-variance form nn.BatchNorm uses (E[x^2] - E[x]^2, clamped).
    Under pjit the batch axis is one global sharded tensor, so these are
    sync-BN global stats by construction — same semantics as the unfused
    nn.BatchNorm path (module docstring above). `group` > 1: the last
    axis holds `group` output columns of each channel (ops/lane_fold.py),
    whose moments are averaged into the channel's."""
    axes = tuple(range(raw32.ndim - 1))
    mean = jnp.mean(raw32, axis=axes)
    mean2 = jnp.mean(raw32 * raw32, axis=axes)
    if group > 1:
        mean = mean.reshape(group, -1).mean(axis=0)
        mean2 = mean2.reshape(group, -1).mean(axis=0)
    return mean, jnp.maximum(mean2 - mean * mean, 0.0)


def fused_train_norm_act(raw, bn: BNAffine, features: int, act: str,
                         dtype):
    """Training-mode tail of a fused conv site: batch stats from the raw
    conv output (the one pass the fused lowering already wrote), running-
    average update via `bn`, then affine + activation as one f32 island.
    The conv itself used the fused lowering; the stats/affine/act here are
    plain elementwise XLA fuses into a single pass — training keeps
    correct autodiff through the batch statistics."""
    from pytorchvideo_accelerate_tpu.ops.pallas_fused import apply_act

    raw32 = f32_island(raw)
    mean, var = batch_norm_stats(raw32)
    mul, add = bn(features, mean, var, train=True)
    return end_island(apply_act(raw32 * mul + add, act), dtype)


class ConvBNAct(nn.Module):
    """conv3d -> BN -> activation, the unit both ResNet and X3D stems/stages
    are made of (pytorchvideo's create_conv_patch_embed / Net blocks, cited
    from the reference call sites at run.py:107,115 [external model zoo]).

    Three lowerings of one function and one param tree: nn.Conv +
    nn.BatchNorm; the `fused` kernels where that knob arms them; and, on
    the TPU, the lane fold for a site with few input channels and fewer
    than 128 output channels (an RGB stem), chosen from the site's static
    shapes alone (`_lane_fold_group`; docs/KERNELS.md)."""

    features: int
    kernel: Tuple[int, int, int]
    stride: Tuple[int, int, int] = (1, 1, 1)
    groups: int = 1
    use_bias: bool = False
    use_bn: bool = True
    act: Optional[Callable] = nn.relu
    dtype: Dtype = jnp.float32
    bn_momentum: float = 0.9  # = 1 - torch_momentum(0.1)
    bn_eps: float = 1e-5
    # fused conv+norm+act lowering (FUSED_MODES; docs/KERNELS.md): "off"
    # keeps the graph below byte-for-byte; any other value routes
    # stride-1 BN sites through ops/pallas_fused.py — same param tree
    # (ConvKernelParam/BNAffine mirror nn.Conv/nn.BatchNorm), so the
    # knob is a deployment choice, not a model change. Strided sites,
    # bias convs, and unrecognized activations keep the unfused path.
    fused: str = "off"

    @nn.compact
    def __call__(self, x, train: bool = False):
        group = self._lane_fold_group(x)
        if group:
            return self._lane_folded(x, train, group)
        act_name = fusable_act_name(self.act)
        if (self.fused != "off" and self.use_bn and not self.use_bias
                and self.groups == 1 and tuple(self.stride) == (1, 1, 1)
                and act_name is not None):
            return self._fused(x, train, act_name)
        x = nn.Conv(
            self.features,
            kernel_size=self.kernel,
            strides=self.stride,
            padding=[(k // 2, k // 2) for k in self.kernel],
            feature_group_count=self.groups,
            use_bias=self.use_bias,
            dtype=self.dtype,
            name="conv",
        )(x)
        if self.use_bn:
            x = nn.BatchNorm(
                use_running_average=not train,
                momentum=self.bn_momentum,
                epsilon=self.bn_eps,
                dtype=self.dtype,
                name="norm",
            )(x)
        if self.act is not None:
            x = self.act(x)
        return x

    @nn.nowrap
    def _lane_fold_group(self, x) -> int:
        """Output columns this site folds into its channel axis
        (ops/lane_fold.py): nonzero for an RGB stem on the TPU. `init` runs
        un-jitted and needs the parameters' shapes only: it keeps nn.Conv's
        eager programs."""
        if (self.groups != 1 or self.use_bias or not self.use_bn
                or fusable_act_name(self.act) is None
                or self.is_initializing() or not lane_fold.takes_fold()):
            return 0
        return lane_fold.fold_group(x.shape[-1], self.features, self.kernel,
                                    self.stride, x.shape[-2])

    @nn.nowrap
    def _lane_folded(self, x, train: bool, group: int):
        """The site as a lane-filling contraction: conv, batch statistics,
        affine and activation on the folded (..., W/G, G*C) tensor, whose
        last axis fills the 128 lanes, then one reshape back. Same param
        tree as the nn.Conv / nn.BatchNorm it replaces."""
        lane_fold.note_site(self.path)
        w = ConvKernelParam(self.features, tuple(self.kernel),
                            x.shape[-1], name="conv")()
        bn = BNAffine(momentum=self.bn_momentum, eps=self.bn_eps,
                      name="norm")
        # fold, expansion, conv and un-fold (and their backward) under the
        # scope the nn.Conv would open, as `_fused` does
        with jax.named_scope("conv"):
            raw = lane_fold.lane_fold_conv3d(
                x.astype(self.dtype), w.astype(self.dtype), self.stride,
                group)
        raw32 = f32_island(raw)
        if train:
            mul, add = bn(self.features, *batch_norm_stats(raw32, group),
                          train=True)
        else:
            mul, add = bn(self.features, train=False)
        # a known activation is elementwise: it commutes with the fold
        y = raw32 * jnp.tile(mul, group) + jnp.tile(add, group)
        y = end_island(y if self.act is None else self.act(y), self.dtype)
        with jax.named_scope("conv"):
            return lane_fold.unfold(y, group)

    def _fused(self, x, train: bool, act_name: str):
        from pytorchvideo_accelerate_tpu.ops.pallas_fused import (
            fused_conv3d_bn_act,
        )

        w = ConvKernelParam(self.features, tuple(self.kernel),
                            x.shape[-1], name="conv")()
        bn = BNAffine(momentum=self.bn_momentum, eps=self.bn_eps,
                      name="norm")
        x = x.astype(self.dtype)
        w = w.astype(self.dtype)
        # the kernel runs under the scope the unfused nn.Conv would open
        # (".../conv/"), forward and backward, so a reader that selects
        # device time by the layer's path finds it whatever the lowering
        if train:
            # fused conv pass; stats/affine/act ride it as one elementwise
            # tail (autodiff through the batch statistics stays plain)
            with jax.named_scope("conv"):
                raw = fused_conv3d_bn_act(
                    x, w, jnp.ones((self.features,), jnp.float32),
                    jnp.zeros((self.features,), jnp.float32),
                    act="identity", mode=self.fused)
            return fused_train_norm_act(raw, bn, self.features, act_name,
                                        self.dtype)
        mul, add = bn(self.features, train=False)
        with jax.named_scope("conv"):
            return fused_conv3d_bn_act(x, w, mul, add, act=act_name,
                                       mode=self.fused)


class Bottleneck3D(nn.Module):
    """ResNet bottleneck with a (kt,1,1) temporal conv_a, (1,3,3) spatial
    conv_b, (1,1,1) conv_c — the pytorchvideo `create_bottleneck_block`
    shape used by slow_r50/slowfast (reference consumes it via torch.hub at
    run.py:107,115)."""

    features_inner: int
    features_out: int
    temporal_kernel: int = 1
    spatial_stride: int = 1
    fused: str = "off"  # FUSED_MODES; strided sites auto-fallback
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = ConvBNAct(
            self.features_inner,
            kernel=(self.temporal_kernel, 1, 1),
            fused=self.fused,
            dtype=self.dtype,
            name="conv_a",
        )(x, train)
        y = ConvBNAct(
            self.features_inner,
            kernel=(1, 3, 3),
            stride=(1, self.spatial_stride, self.spatial_stride),
            fused=self.fused,
            dtype=self.dtype,
            name="conv_b",
        )(y, train)
        y = ConvBNAct(
            self.features_out,
            kernel=(1, 1, 1),
            act=None,
            fused=self.fused,
            dtype=self.dtype,
            name="conv_c",
        )(y, train)
        if residual.shape[-1] != self.features_out or self.spatial_stride != 1:
            residual = ConvBNAct(
                self.features_out,
                kernel=(1, 1, 1),
                stride=(1, self.spatial_stride, self.spatial_stride),
                act=None,
                fused=self.fused,
                dtype=self.dtype,
                name="branch1",
            )(residual, train)
        return nn.relu(residual + y)


class ResStage(nn.Module):
    """A stack of bottleneck blocks; the first carries the spatial stride."""

    depth: int
    features_inner: int
    features_out: int
    temporal_kernel: int = 1
    spatial_stride: int = 2
    fused: str = "off"  # FUSED_MODES; threaded into every block
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        for i in range(self.depth):
            x = Bottleneck3D(
                features_inner=self.features_inner,
                features_out=self.features_out,
                temporal_kernel=self.temporal_kernel,
                spatial_stride=self.spatial_stride if i == 0 else 1,
                fused=self.fused,
                dtype=self.dtype,
                name=f"block{i}",
            )(x, train)
        return x


def max_pool_3d(x, window: Sequence[int], strides: Sequence[int]):
    """3D max pool with SAME-style per-dim padding k//2 (torch MaxPool3d
    padding=[k//2] equivalent)."""
    pads = [(k // 2, k // 2) for k in window]
    return nn.max_pool(
        x, window_shape=tuple(window), strides=tuple(strides), padding=pads
    )


def global_avg_pool(x):
    """Mean over (T, H, W) — AdaptiveAvgPool3d(1) equivalent."""
    return jnp.mean(x, axis=(1, 2, 3))

"""Plain float32 X3D (Feichtenhofer 2020, arXiv:2004.04730, the X3D-S row of
Table 3: 13 frames, 160x160; pytorchvideo `x3d_s`).

Stem: (1,3,3) conv stride 2 to 24 channels, then a (5,1,1) depthwise
temporal conv, BN, ReLU. Four stages (depths 3,5,11,7; 24,48,96,192
channels) of inverted bottlenecks: 1x1x1 expand by 2.25, 3x3x3 depthwise
(BN, squeeze-excite in every other block starting with the first, swish),
1x1x1 project; spatial stride 2 at each stage entry. conv5 1x1x1 to 432 with
BN+ReLU, global average pool, 1x1x1 to 2048 with ReLU, linear.

Departures from the published model, each also the program's:
  * no dropout before the linear layer (rate 0 in the cell's configuration);
  * pytorchvideo's shortcut quirk is kept: the shortcut conv exists for a
    stride or a channel change, its BN only for a channel change (res2's
    first block has a conv and no BN);
  * batch statistics over the whole batch; random weights from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import plain
from .plain import batch_norm, conv3d, conv_bn_act


def inputs(batch):
    return batch["video"]


def derived_inputs(batch, arch):
    """Nothing: the one clip tensor is what the pipeline placed."""
    return {}


def expected_inputs(arch, batch, frames, crop):
    """{key: shape} of the clip tensors a placed batch has to hold."""
    return {"video": (batch, frames, crop, crop, 3)}


def round_width(width, multiplier, min_depth=8, divisor=8):
    """Channel rounding of the paper's appendix (pytorchvideo round_width)."""
    width *= multiplier
    new = max(min_depth, int(width + divisor / 2) // divisor * divisor)
    if new < 0.9 * width:
        new += divisor
    return int(new)


def _squeeze_excite(net, path, x, arch):
    c = x.shape[-1]
    se = round_width(c, arch["se_ratio"])
    s = plain.keep(net, jnp.mean(x, axis=(1, 2, 3), keepdims=True))
    s = jax.nn.relu(conv3d(net, path + ("fc1",), s, se, (1, 1, 1), bias=True))
    s = conv3d(net, path + ("fc2",), s, c, (1, 1, 1), bias=True)
    return plain.keep(net, x * jax.nn.sigmoid(s))


def _block(net, path, x, out, inner, spatial_stride, use_se, arch):
    y = conv_bn_act(net, path + ("conv_a",), x, inner, (1, 1, 1))
    y = conv3d(net, path + ("conv_b",), y, inner, (3, 3, 3),
               (1, spatial_stride, spatial_stride), groups=inner)
    y = batch_norm(net, path + ("norm_b",), y)
    if use_se:
        y = _squeeze_excite(net, path + ("se",), y, arch)
    y = plain.keep(net, jax.nn.silu(y))
    y = conv_bn_act(net, path + ("conv_c",), y, out, (1, 1, 1), act=False)
    if x.shape[-1] != out or spatial_stride != 1:
        x = conv_bn_act(net, path + ("branch1",), x, out, (1, 1, 1),
                        (1, spatial_stride, spatial_stride), act=False,
                        use_bn=x.shape[-1] != out)
    return plain.keep(net, jax.nn.relu(x + y))


def forward(net, batch, arch, remat=True):
    """Training-mode logits for a batch dict with "video"."""
    remat = remat and not net.creating
    x = batch["video"].astype(jnp.float32)
    stem = arch["stem_features"]
    x = conv3d(net, ("stem_xy",), x, stem, (1, 3, 3), (1, 2, 2))
    x = conv3d(net, ("stem_t",), x, stem, (5, 1, 1), groups=stem)
    x = plain.keep(net, jax.nn.relu(batch_norm(net, ("stem_norm",), x)))
    for s, depth in enumerate(arch["depths"]):
        out = arch["stage_features"][s]
        inner = int(round(out * arch["expansion"]))
        for i in range(depth):
            def block(x, i=i, out=out, inner=inner, s=s):
                return _block(net, (f"res{s + 2}_block{i}",), x, out, inner,
                              2 if i == 0 else 1, i % 2 == 0, arch)
            x = jax.checkpoint(block)(x) if remat else block(x)
    f5 = int(round(arch["stage_features"][-1] * arch["expansion"]))
    x = conv_bn_act(net, ("conv5",), x, f5, (1, 1, 1))
    x = plain.keep(net, jnp.mean(x, axis=(1, 2, 3), keepdims=True))
    x = jax.nn.relu(conv3d(net, ("head_conv",), x, arch["head_features"],
                           (1, 1, 1)))
    x = x.reshape(x.shape[0], -1)
    return plain.dense(net, ("proj",), x, arch["num_classes"])


def init_batch(arch):
    return {"video": jnp.zeros((1, 5, 32, 32, 3))}

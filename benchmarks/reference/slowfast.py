"""Plain float32 SlowFast (Feichtenhofer et al. 2019, arXiv:1812.03982,
Table 1; pytorchvideo model zoo `slowfast_r50`, 8x8).

Two pathways. Slow: T/alpha frames, 64-channel stem, bottleneck stages with
temporal kernels (1,1,3,3). Fast: T frames, 1/beta of the channels, temporal
kernel 3 in every stage, a (5,7,7) stem. After the stem (and its 1x3x3 max
pool), res2, res3 and res4 a time-strided (7,1,1) convolution with stride
(alpha,1,1) carries 2x the fast channels onto the slow feature (concat).
Head: global average pool of each pathway, concat (2048+256), linear.

Departures from the published model, each also the program's:
  * no dropout before the linear layer (the cell's configuration sets the
    rate to 0 so that the step is a function of the batch alone);
  * batch-norm statistics are those of the whole batch on the mesh;
  * weights are random from the seed (fan-in normal, head normal(0.01)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import plain
from .plain import conv_bn_act


def inputs(batch):
    return batch["slow"], batch["fast"]


def slow_frames(fast, alpha):
    """The slow pathway's clip, cut from the fast one by the reference itself:
    T // alpha frames at the truncated linspace of pytorchvideo's PackPathway
    (`torch.linspace(0, T - 1, T // alpha).long()`)."""
    t = fast.shape[1]
    idx = np.linspace(0, t - 1, t // alpha).astype(np.int64)
    return fast[:, idx]


def derived_inputs(batch, arch):
    """What the reference makes of the placed batch itself and the pipeline
    also placed: compared exactly (benchmarks/lib/compare.py)."""
    return {"slow": slow_frames(np.asarray(batch["fast"]), arch["alpha"])}


def expected_inputs(arch, batch, frames, crop):
    """{key: shape} of the clip tensors a placed batch has to hold."""
    return {"fast": (batch, frames, crop, crop, 3),
            "slow": (batch, frames // arch["alpha"], crop, crop, 3)}


def _bottleneck(net, path, x, inner, out, temporal_kernel, spatial_stride):
    y = conv_bn_act(net, path + ("conv_a",), x, inner, (temporal_kernel, 1, 1))
    y = conv_bn_act(net, path + ("conv_b",), y, inner, (1, 3, 3),
                    (1, spatial_stride, spatial_stride))
    y = conv_bn_act(net, path + ("conv_c",), y, out, (1, 1, 1), act=False)
    if x.shape[-1] != out or spatial_stride != 1:
        x = conv_bn_act(net, path + ("branch1",), x, out, (1, 1, 1),
                        (1, spatial_stride, spatial_stride), act=False)
    return plain.keep(net, jax.nn.relu(x + y))


def _stage(net, name, x, depth, inner, temporal_kernel, spatial_stride, remat):
    for i in range(depth):
        def block(x, i=i):
            return _bottleneck(net, (name, f"block{i}"), x, inner, inner * 4,
                               temporal_kernel,
                               spatial_stride if i == 0 else 1)
        x = jax.checkpoint(block)(x) if remat else block(x)
    return x


def _fuse(net, name, slow, fast, fast_features, arch):
    lateral = conv_bn_act(net, (name, "conv_f2s"), fast,
                          fast_features * arch["fusion_ratio"], (7, 1, 1),
                          (arch["alpha"], 1, 1))
    return jnp.concatenate([slow, lateral], axis=-1)


def forward(net, batch, arch, remat=True):
    """Training-mode logits for a batch dict with "fast" (and "label"). The
    slow pathway's frames are cut from `fast` here: the `slow` tensor the
    program's pipeline placed is not read (it is compared with this cut)."""
    remat = remat and not net.creating
    fast = batch["fast"].astype(jnp.float32)
    slow = slow_frames(fast, arch["alpha"])
    stem = arch["stem_features"]
    fast_stem = stem // arch["beta_inv"]
    slow = conv_bn_act(net, ("slow_stem",), slow, stem, (1, 7, 7), (1, 2, 2))
    fast = conv_bn_act(net, ("fast_stem",), fast, fast_stem, (5, 7, 7), (1, 2, 2))
    slow = plain.max_pool(slow, (1, 3, 3), (1, 2, 2))
    fast = plain.max_pool(fast, (1, 3, 3), (1, 2, 2))
    slow = _fuse(net, "fuse_stem", slow, fast, fast_stem, arch)
    slow_inner, fast_inner = stem, fast_stem
    depths = arch["depths"]
    for s, depth in enumerate(depths):
        stride = 1 if s == 0 else 2
        slow = _stage(net, f"slow_res{s + 2}", slow, depth, slow_inner,
                      arch["slow_temporal_kernels"][s], stride, remat)
        fast = _stage(net, f"fast_res{s + 2}", fast, depth, fast_inner, 3,
                      stride, remat)
        if s < len(depths) - 1:
            slow = _fuse(net, f"fuse_res{s + 2}", slow, fast, fast_inner * 4,
                         arch)
        slow_inner *= 2
        fast_inner *= 2
    pooled = plain.keep(net, jnp.concatenate(
        [jnp.mean(slow, axis=(1, 2, 3)), jnp.mean(fast, axis=(1, 2, 3))],
        axis=-1))
    return plain.dense(net, ("head", "proj"), pooled, arch["num_classes"],
                       init=plain.head_normal)


def init_batch(arch):
    """The smallest batch the forward pass accepts (shapes only matter for
    the channel counts of the leaves it creates)."""
    return {"fast": jnp.zeros((1, 2 * arch["alpha"], 32, 32, 3))}

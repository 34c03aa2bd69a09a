"""Kernel scopes that survive a change of lowering.

`conv_roofline` and `depthwise_roofline` select device time by the layer's
scope in the compiled step's `op_name` (benchmarks/lib/hlo.py). This file
lowers the train step of toy-width `slowfast_r50` and `x3d_s` models under
every lowering of their convs (`model.fused_kernels` off / pallas, interpret
mode here; `model.depthwise_impl` conv / shift / pallas) and shows that the
instructions of every conv layer match the readers' patterns in all of them,
forward and backward: a PR that switches a lowering keeps both readers.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import hlo
from benchmarks.metrics import conv_roofline, depthwise_roofline

CONV = re.compile(conv_roofline.SCOPE)
DEPTHWISE = re.compile(depthwise_roofline.SCOPE)
# what does a conv layer's arithmetic, whatever lowers it: XLA's conv and
# dot (the weight gradients of the fused paths are einsums), and everything
# inside one of this repo's named Pallas kernels
WORK = re.compile(r"/(conv_general_dilated|dot_general)$|/pva_\w+(/|$)")
# flax opens `<module>.<method>` for a method that is not __call__
METHOD = r"(?:[^/]+\._\w+/)?"


def x3d(fused, depthwise_impl):
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    model = X3D(num_classes=5, depths=(1, 2), stem_features=8,
                stage_features=(8, 16), head_features=32, dropout_rate=0.0,
                fused=fused, depthwise_impl=depthwise_impl)
    batch = {"video": np.zeros((2, 4, 16, 16, 3), np.float32),
             "label": np.zeros(2, np.int32)}
    return model, batch, jnp.zeros((1, 4, 16, 16, 3))


def slowfast(fused, _depthwise_impl):
    from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast

    model = SlowFast(num_classes=5, depths=(1, 1, 1, 1), stem_features=16,
                     dropout_rate=0.0, fused=fused)
    shape = {"slow": (2, 32, 32, 3), "fast": (8, 32, 32, 3)}
    batch = {k: np.zeros((2, *s), np.float32) for k, s in shape.items()}
    batch["label"] = np.zeros(2, np.int32)
    return model, batch, tuple(jnp.zeros((1, *shape[k]))
                               for k in ("slow", "fast"))


def compiled_step(model, batch, sample):
    """(the compiled train step's text, the model's parameters)."""
    from pytorchvideo_accelerate_tpu.config import MeshConfig, OptimConfig
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.parallel.sharding import (
        shard_batch,
        shard_state,
    )
    from pytorchvideo_accelerate_tpu.trainer import (
        TrainState,
        build_optimizer,
        make_train_step,
    )

    mesh = make_train_mesh(MeshConfig(), devices=jax.devices()[:1])
    variables = model.init(jax.random.key(0), sample)
    tx = build_optimizer(OptimConfig(), total_steps=10)
    state = shard_state(mesh, TrainState.create(
        variables["params"], variables.get("batch_stats", {}), tx), tp=False)
    step = make_train_step(model, tx, mesh)
    lowered = step.lower(state, shard_batch(mesh, batch), jax.random.key(0))
    return lowered.compile().as_text(), variables["params"]


def conv_layers(params):
    """[(module path, depthwise?)] of every conv layer: the 5-D kernels."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [p.key for p in path]
        if names[-1] == "kernel" and leaf.ndim == 5:
            depthwise = leaf.shape[3] == 1 and leaf.shape[4] > 1
            out.append((names[:-1], depthwise))
    return out


@pytest.mark.parametrize("family,fused,depthwise_impl", [
    (x3d, "off", "conv"), (x3d, "off", "shift"), (x3d, "off", "pallas"),
    (x3d, "pallas", "conv"), (x3d, "pallas", "pallas"),
    (slowfast, "off", "conv"), (slowfast, "pallas", "conv"),
])
def test_conv_layers_keep_their_scope(family, fused, depthwise_impl):
    text, params = compiled_step(*family(fused, depthwise_impl))
    op_names = {name for joined in hlo.scopes(text).values()
                for name in joined.split(" | ") if name}
    layers = conv_layers(params)
    assert len(layers) >= 10 and any(dw for _p, dw in layers) == (family is x3d)
    if fused == "pallas" or depthwise_impl == "pallas":
        assert any("/pva_" in n for n in op_names), "no Pallas kernel lowered"
    claimed = set()
    for path, depthwise in layers:
        layer = re.compile("/" + "".join(METHOD + re.escape(p) + "/"
                                         for p in path))
        pattern = DEPTHWISE if depthwise else CONV
        under = {n for n in op_names if layer.search(n)}
        found = {n for n in under if pattern.search(n)}
        forward = {n for n in found if "transpose(" not in n}
        assert forward and found - forward, (path, sorted(under))
        # all of the layer's arithmetic is where the reader looks (the
        # shift lowering is multiplies and adds: everything under the layer)
        work = {n for n in under if WORK.search(n)} or under
        assert work <= found, (path, sorted(work - found))
        claimed |= work
    # and no conv arithmetic of the model lies outside every conv layer
    # (the classifier's dense layer is the one contraction that is no conv)
    stray = {n for n in op_names if WORK.search(n)
             and ("jvp(" in n) and n not in claimed
             and not re.search(r"/(proj|head)/", n)}
    assert not stray, sorted(stray)

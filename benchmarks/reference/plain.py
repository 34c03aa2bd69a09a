"""Plain float32 building blocks shared by the family references.

Everything here is straightforward `jax.numpy` / `lax.conv_general_dilated`
in float32 at `Precision.HIGHEST`: no kernels, no fusion tricks, no mixed
precision. Nothing is imported from the program (`pytorchvideo_accelerate_tpu`).
Layout is NDHWC = (batch, time, height, width, channels), kernels DHWIO.

Parameters live in a nested dict whose paths are the checkpoint layout the
program loads (`<module>/conv/kernel`, `<module>/norm/scale`, ...): that
naming is the interface through which the benchmark hands its seeded weights
to the program, and the only thing the two sides share.

`q` (None | a dtype name) is the control's switch. The configuration's policy
keeps parameters in float32 and computes in a narrower type: every tensor the
forward pass makes (conv, norm, activation, pooling, residual sums) and every
cotangent of the backward pass is held in that type. With `q` set the
reference does the same in `q`: `keep(net, x)` rounds each such tensor, and
its cotangent on the way back, to `q` (an 8-bit float with a per-tensor
scale), and the contractions round their operands. `None` is the reference
proper: every `keep` is the identity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
BN_EPS = 1e-5
DENSE_SCOPE = "ref_conv_dense"  # dense and pointwise convs (groups == 1)
DEPTHWISE_SCOPE = "ref_conv_depthwise"  # one group per channel


def quantize(x, q):
    """`x` as a contraction operand in precision `q` (identity for q=None,
    the reference proper). Forward, the value is rounded to `q`; backward, so
    is the cotangent that flows to the producer, as the operands of the
    data- and weight-gradient contractions would be. An 8-bit float gets a
    per-tensor scale (max |x| onto the format's largest number), without which
    gradients underflow to zero: the careful low-precision step that would
    tempt a later PR, not a careless one."""
    if q is None:
        return x
    return _fake_quant(x, q)


def _round(x, q):
    dt = jnp.dtype(q)
    if dt.itemsize == 1:
        # the floor keeps the scale of an all-but-zero tensor from underflowing
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = amax / float(jnp.finfo(dt).max)
        return (x / scale).astype(dt).astype(jnp.float32) * scale
    return x.astype(dt).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fake_quant(x, q):
    return _round(x, q)


def _fake_quant_fwd(x, q):
    return _round(x, q), None


def _fake_quant_bwd(q, _, g):
    return (_round(g, q),)


_fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


class Net:
    """Parameter access by path. Built without variables it *creates* each
    leaf from the key the first time the forward pass asks for it (so the
    architecture is written once, in the forward function); built with
    variables it reads them."""

    def __init__(self, variables=None, key=None, q=None):
        self.creating = variables is None
        self.params = {} if self.creating else variables["params"]
        self.stats = {} if self.creating else variables.get("batch_stats", {})
        self.key = key
        self.count = 0
        self.q = q

    def param(self, path, shape, init):
        if self.creating:
            leaf = init(jax.random.fold_in(self.key, self.count), shape)
            self.count += 1
            _put(self.params, path, leaf)
        return _get(self.params, path)

    def stat(self, path, shape, value):
        """Running statistics: created for the program's benefit (its state
        tree carries them); the training-mode forward never reads them."""
        if self.creating:
            _put(self.stats, path, jnp.full(shape, value, jnp.float32))

    def variables(self):
        return {"params": self.params, "batch_stats": self.stats}


def _put(tree, path, leaf):
    for name in path[:-1]:
        tree = tree.setdefault(name, {})
    tree[path[-1]] = leaf


def _get(tree, path):
    for name in path:
        tree = tree[name]
    return tree


def keep(net, x):
    """A tensor of the forward pass as the policy's compute type holds it."""
    return quantize(x, net.q)


def fan_in_normal(key, shape):
    """Normal with variance 1/fan_in (kernel dims x input channels)."""
    fan_in = math.prod(shape[:-1])
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def head_normal(key, shape):
    return 0.01 * jax.random.normal(key, shape, jnp.float32)


def zeros(key, shape):
    return jnp.zeros(shape, jnp.float32)


def ones(key, shape):
    return jnp.ones(shape, jnp.float32)


def conv3d(net, path, x, features, kernel, stride=(1, 1, 1), groups=1,
           bias=False):
    """3-D convolution, padding k//2 on each side of each dim."""
    cin = x.shape[-1]
    w = net.param(path + ("kernel",), (*kernel, cin // groups, features),
                  fan_in_normal)
    # the scope is how benchmarks/lib/flops.py tells the two classes of conv
    # apart in this function's jaxpr, backward convs included
    with jax.named_scope(DEPTHWISE_SCOPE if groups > 1 else DENSE_SCOPE):
        y = _conv(net, x, w, stride, kernel, groups)
    if bias:
        y = y + net.param(path + ("bias",), (features,), zeros)
    return keep(net, y)


def _conv(net, x, w, stride, kernel, groups):
    return lax.conv_general_dilated(
        quantize(x, net.q), quantize(w, net.q),
        window_strides=tuple(stride),
        padding=[(k // 2, k // 2) for k in kernel],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        feature_group_count=groups,
        precision=HI,
    )


def batch_norm(net, path, x):
    """Training-mode batch norm: statistics of this batch over (N,T,H,W),
    biased variance, eps inside the square root."""
    c = x.shape[-1]
    scale = net.param(path + ("scale",), (c,), ones)
    shift = net.param(path + ("bias",), (c,), zeros)
    net.stat(path + ("mean",), (c,), 0.0)
    net.stat(path + ("var",), (c,), 1.0)
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    return keep(net, (x - mean) * (scale / jnp.sqrt(var + BN_EPS)) + shift)


def conv_bn_act(net, path, x, features, kernel, stride=(1, 1, 1), act=True,
                use_bn=True):
    y = conv3d(net, path + ("conv",), x, features, kernel, stride)
    if use_bn:
        y = batch_norm(net, path + ("norm",), y)
    return keep(net, jax.nn.relu(y)) if act else y


def max_pool(x, window, stride):
    """Max pool over (T,H,W), padding k//2 with -inf."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        (1, *window, 1), (1, *stride, 1),
        [(0, 0)] + [(k // 2, k // 2) for k in window] + [(0, 0)],
    )


def dense(net, path, x, features, init=fan_in_normal):
    w = net.param(path + ("kernel",), (x.shape[-1], features), init)
    b = net.param(path + ("bias",), (features,), zeros)
    return keep(net, jnp.dot(quantize(x, net.q), quantize(w, net.q),
                             precision=HI) + b)


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy over the batch (no smoothing)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)
    return -jnp.mean(picked)


def cosine_lr(step, lr, total_steps):
    """CosineAnnealingLR to zero over `total_steps` optimizer steps."""
    frac = jnp.minimum(step, total_steps) / total_steps
    return lr * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))


def sgd_update(params, momentum_buf, grads, step, optim):
    """torch-style SGD: g + wd*p, then momentum, then the cosine rate."""
    wd, mu = optim["weight_decay"], optim["momentum"]
    lr = cosine_lr(step, optim["lr"], optim["total_steps"])
    g = jax.tree.map(lambda g, p: g + wd * p, grads, params)
    buf = jax.tree.map(lambda b, g: mu * b + g, momentum_buf, g)
    new = jax.tree.map(lambda p, b: p - lr * b, params, buf)
    return new, buf


def leaf_norms(tree):
    """L2 norm of every leaf, as a flat {path: norm} dict."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}

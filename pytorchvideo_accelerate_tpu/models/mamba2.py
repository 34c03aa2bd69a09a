"""The Mamba-2 mixer, as the hybrid token families use it (models/nemotron_h.py,
models/granite_h.py): told its sizes, it knows no family's config names.

    [z | xBC | dt] = u W_in;  xBC = silu(causal_depthwise_conv(xBC) + b)
    = [x | B | C];  dt = softplus(dt + dt_bias);  a = -exp(A_log)
    y = ssd(x, dt, a, B, C, D)  (ops/ssd.py: head h reads group h // (H/G))
    out = (grouped_rms(y * silu(z)) * w) W_out   (`groups` groups)

H heads of P, G groups of state N, a conv of K taps, inner width H P. No
biases but the conv's. The conv starts PyTorch's default U(-1/2, 1/2) (fan-in
4), `A_log` = log(1..H), `D` = 1, `dt_bias` by the Mamba-2 rule. Scopes of the
compiled step: `in_proj`, `conv`, `scan` (dt's softplus, `-exp(A_log)` and
the state space), `out` (the gated norm and W_out), under the module's name.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.models.lm_common import _dense, causal_conv, rms
from pytorchvideo_accelerate_tpu.ops.ssd import ssd_scan
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island


def _dt_bias(time_step):
    """The Mamba-2 rule: dt log-uniform in [min, max], at least floor
    (`time_step` = (min, max, floor)), stored as softplus's inverse."""
    lo_dt, hi_dt, floor = time_step

    def init(key, shape):
        lo, hi = math.log(lo_dt), math.log(hi_dt)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo),
                         floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _conv_init(taps):
    bound = taps ** -0.5  # PyTorch's Conv1d default at fan-in `taps`
    return lambda key, shape: jax.random.uniform(key, shape, minval=-bound,
                                                 maxval=bound)


class Mamba(nn.Module):
    hidden: int
    heads: int
    head_dim: int
    groups: int
    state: int
    taps: int
    chunk: int
    eps: float
    time_step: Tuple[float, float, float]  # dt_bias's rule: (min, max, floor)
    dtype: Any

    @nn.compact
    def __call__(self, x):
        dt = self.dtype
        b, t, _ = x.shape
        h, p = self.heads, self.head_dim
        g, n = self.groups, self.state
        inner = h * p
        conv_dim = inner + 2 * g * n
        with jax.named_scope("in_proj"):
            zxbcdt = _dense(self, "in_proj", x, inner + conv_dim + h, dt)
            z, xbc, step = (zxbcdt[..., :inner],
                            zxbcdt[..., inner:inner + conv_dim],
                            zxbcdt[..., inner + conv_dim:])
        with jax.named_scope("conv"):
            taps = self.taps
            w = self.param("conv", _conv_init(taps), (taps, conv_dim)).astype(dt)
            bias = self.param("conv_bias", _conv_init(taps), (conv_dim,))
            xbc = jax.nn.silu(causal_conv(xbc, w) + bias.astype(dt))
        with jax.named_scope("scan"):
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=jnp.float32)), (h,))
            dt_bias = self.param("dt_bias", _dt_bias(self.time_step), (h,))
            skip = self.param("D", nn.initializers.ones, (h,))
            delta = jax.nn.softplus(f32_island(step) + dt_bias)
            y, _ = ssd_scan(xbc[..., :inner].reshape(b, t, h, p), delta,
                            -jnp.exp(a_log),
                            xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                            xbc[..., inner + g * n:].reshape(b, t, g, n),
                            skip, self.chunk)
        with jax.named_scope("out"):
            # the gated norm: y * silu(z), normed over each group's columns
            scale = self.param("norm", nn.initializers.ones, (inner,))
            y = f32_island(y).reshape(b, t, inner) * jax.nn.silu(f32_island(z))
            y = rms(y.reshape(b, t, g, inner // g), self.eps)
            y = end_island(y.reshape(b, t, inner) * scale, dt)
            return _dense(self, "out_proj", y, self.hidden, dt)

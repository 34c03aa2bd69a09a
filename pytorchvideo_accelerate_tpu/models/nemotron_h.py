"""Nemotron-H: a decoder whose every layer is ONE of three kinds, by a
pattern string.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(config.json, `model_type` `nemotron_h`). Layer i is the i-th character of
`hybrid_override_pattern`: `M` a Mamba-2 mixer, `E` a routed mixture of
experts with one shared expert, `*` causal grouped-query attention. Every
layer is `x + mixer(norm(x))`, `norm(x) = w * x * rsqrt(mean(x^2) + eps)`;
after the last, `norm_f` and the untied head. No biases but the conv's.

    M:  [z | xBC | dt] = u W_in;  xBC = silu(causal_depthwise_conv(xBC) + b)
        = [x | B | C];  dt = softplus(dt + dt_bias);  a = -exp(A_log)
        y = ssd(x, dt, a, B, C, D)  (ops/ssd.py: head h reads group h // (H/G))
        out = (grouped_rms(y * silu(z)) * w) W_out   (n_groups groups)
    E:  s = sigmoid_f32(u W_r); the top-k of s + b choose (b the
        `e_score_correction_bias`, which gets no gradient), weights
        routed_scaling_factor * s / sum s (ops/moe.py `route`);
        out = sum over the held chosen experts of w_k relu(u U_e)^2 V_e
              + relu(u U_s)^2 V_s   (two products an expert, not gated)
    *:  causal attention, no positional encoding at all (the published
        modelling code applies none: `rope_theta` goes unused)

Each layer is one rematerialised unit; an attention unit keeps the flash
forward's `o` and `lse` (`remat_keeping_attention`). The module is told
which share of the model it holds (`NemotronHArch`), as `models/qwen3_next.py`
is: `experts_held` of the `n_routed_experts` from `expert_offset` on and
`vocab_size` rows of the vocabulary; it routes over all experts and returns
`lm_outputs`. Departures from the published model: no multi-token
prediction, no update rule for the correction bias (it stays as given; the
optimizer's decoupled weight decay, which reaches every leaf, leaves a bias
of 0 at 0), no dropout, one document a sequence. Every matrix starts N(0,
0.02), the embedding too; the conv PyTorch's default U(-1/2, 1/2) (fan-in
4); `A_log` = log(1..H), `D` = 1, `dt_bias` by the Mamba-2 rule.

Scopes of the compiled step (benchmarks/metrics read device time by them):
`mamba/{in_proj,conv,scan,out}` (`out`: the gated norm and W_out),
`moe/{router,dispatch,experts,combine,shared}`, `attn/{qkv,core,out}`,
`lm_head`, `loss`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.models.lm_common import (
    Attention,
    Norm,
    _dense,
    _normal,
    causal_conv,
    held_experts,
    lm_outputs,
    remat_keeping_attention,
    rms,
)
from pytorchvideo_accelerate_tpu.ops.moe import expert_share, route
from pytorchvideo_accelerate_tpu.ops.ssd import ssd_scan
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

KINDS = "ME*"  # a Mamba-2 mixer, a mixture of experts, attention


@dataclasses.dataclass(frozen=True)
class NemotronHArch:
    """The sizes, under the names of the published config.json."""

    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    vocab_size: int = 131072
    layer_norm_epsilon: float = 1e-5
    # the share held here (docs/TOKENS.md); 0 experts held = all of them
    experts_held: int = 0
    expert_offset: int = 0

    @property
    def held(self) -> int:
        return held_experts(self.experts_held, self.n_routed_experts)


def relu_squared(x):
    return jnp.square(jax.nn.relu(x))


def _dt_bias(arch):
    """The Mamba-2 rule: dt log-uniform in [time_step_min, time_step_max],
    at least time_step_floor, stored as softplus's inverse."""
    def init(key, shape):
        lo, hi = math.log(arch.time_step_min), math.log(arch.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo),
                         arch.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _conv_init(taps):
    bound = taps ** -0.5  # PyTorch's Conv1d default at fan-in `taps`
    return lambda key, shape: jax.random.uniform(key, shape, minval=-bound,
                                                 maxval=bound)


class Mamba(nn.Module):
    arch: NemotronHArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, _ = x.shape
        h, p = a.mamba_num_heads, a.mamba_head_dim
        g, n = a.n_groups, a.ssm_state_size
        inner = h * p
        conv_dim = inner + 2 * g * n
        with jax.named_scope("in_proj"):
            zxbcdt = _dense(self, "in_proj", x, inner + conv_dim + h, dt)
            z, xbc, step = (zxbcdt[..., :inner],
                            zxbcdt[..., inner:inner + conv_dim],
                            zxbcdt[..., inner + conv_dim:])
        with jax.named_scope("conv"):
            taps = a.conv_kernel
            w = self.param("conv", _conv_init(taps), (taps, conv_dim)).astype(dt)
            bias = self.param("conv_bias", _conv_init(taps), (conv_dim,))
            xbc = jax.nn.silu(causal_conv(xbc, w) + bias.astype(dt))
        with jax.named_scope("scan"):
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=jnp.float32)), (h,))
            dt_bias = self.param("dt_bias", _dt_bias(a), (h,))
            skip = self.param("D", nn.initializers.ones, (h,))
            delta = jax.nn.softplus(f32_island(step) + dt_bias)
            y, _ = ssd_scan(xbc[..., :inner].reshape(b, t, h, p), delta,
                            -jnp.exp(a_log),
                            xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                            xbc[..., inner + g * n:].reshape(b, t, g, n),
                            skip, a.chunk_size)
        with jax.named_scope("out"):
            # the gated norm: y * silu(z), normed over each group's columns
            scale = self.param("norm", nn.initializers.ones, (inner,))
            y = f32_island(y).reshape(b, t, inner) * jax.nn.silu(f32_island(z))
            y = rms(y.reshape(b, t, g, inner // g), a.layer_norm_epsilon)
            y = end_island(y.reshape(b, t, inner) * scale, dt)
            return _dense(self, "out_proj", y, a.hidden_size, dt)


class Mixture(nn.Module):
    """The routed experts held here plus the shared expert; returns
    (y, (rows (held,), tight)): the tokens each held expert computed, and
    whether they fit the tight row buffers (`expert_share`)."""

    arch: NemotronHArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, d = x.shape
        f = a.moe_intermediate_size
        flat = x.reshape(b * t, d)
        with jax.named_scope("router"):
            router = self.param("router", _normal(), (d, a.n_routed_experts))
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (a.n_routed_experts,))
            weights, experts = route(
                flat, router, a.num_experts_per_tok, a.norm_topk_prob,
                score="sigmoid", selection_bias=jax.lax.stop_gradient(bias),
                scale=a.routed_scaling_factor)
        w_up = self.param("w_up", _normal(), (a.held, d, f))
        w_down = self.param("w_down", _normal(), (a.held, f, d))
        y, rows, tight = expert_share(flat, weights, experts, None, w_up,
                                      w_down, a.expert_offset,
                                      a.n_routed_experts,
                                      activation=relu_squared)
        with jax.named_scope("shared"):
            fs = a.moe_shared_expert_intermediate_size
            hidden = relu_squared(_dense(self, "shared_up_proj", flat, fs, dt))
            y = y + _dense(self, "shared_down_proj", hidden, d, dt)
        return y.reshape(b, t, d), (rows, tight)


class _Layer(nn.Module):
    """x + mixer(norm(x)), the mixer by `kind`: one rematerialised unit."""

    arch: NemotronHArch
    dtype: Any
    kind: str

    @nn.compact
    def __call__(self, x):
        a = self.arch
        normed = Norm(a.layer_norm_epsilon, name="norm")(x)
        if self.kind == "M":
            return x + Mamba(a, self.dtype, name="mamba")(normed)
        if self.kind == "*":
            return x + Attention(a, self.dtype, rotary=False, window=None,
                                 name="attn")(normed)
        y, share = Mixture(a, self.dtype, name="moe")(normed)
        return x + y, share


class NemotronH(nn.Module):
    arch: NemotronHArch
    dtype: Any = jnp.bfloat16
    remat: bool = True       # per layer: boundaries only
    loss_block: int = 2048   # positions whose logits exist at once

    @nn.compact
    def __call__(self, tokens, targets: Optional[jnp.ndarray] = None,
                 weights: Optional[jnp.ndarray] = None, train: bool = False):
        del train  # no dropout, no batch statistics
        a = self.arch
        embed = self.param("embed", _normal(), (a.vocab_size, a.hidden_size))
        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        attn_cls = remat_keeping_attention(_Layer) if self.remat else _Layer
        layer_cls = nn.remat(_Layer) if self.remat else _Layer
        shares = []
        for i, kind in enumerate(a.hybrid_override_pattern[:a.num_hidden_layers]):
            cls = attn_cls if kind == "*" else layer_cls
            out = cls(a, self.dtype, kind, name=f"layer_{i}")(x)
            if kind == "E":
                x, share = out
                shares.append(share)
            else:
                x = out
        x = Norm(a.layer_norm_epsilon, name="final_norm")(x)
        head = self.param("lm_head", _normal(), (a.hidden_size, a.vocab_size))
        return lm_outputs(x, head, targets, weights, self.loss_block, shares)

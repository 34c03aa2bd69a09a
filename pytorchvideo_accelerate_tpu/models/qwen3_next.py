"""Qwen3-Next: a decoder whose layers are of two kinds, by position.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct (config.json,
`model_type` `qwen3_next`). Layer i mixes tokens with gated softmax attention
where (i + 1) % `full_attention_interval` == 0 and with a Gated DeltaNet
(ops/gated_delta.py) otherwise; every layer is followed by a routed mixture
of experts with one gated shared expert (ops/moe.py). All norms are
`x * rsqrt(mean(x^2) + eps)`; "zero-centred" scales multiply by 1 + w.

    h = x + mixer_i(zc_norm(x));   y = h + moe(zc_norm(h))
    after the last layer: zc_norm, then the untied head

The module is told which share of the model it holds (`Qwen3NextArch`):
`experts_held` of the `num_experts` routed experts from `expert_offset` on,
and `vocab_size` rows of the vocabulary; it routes over all `num_experts`.
Called with `targets` (and a weight a position) it returns the summed
cross-entropy over the held vocabulary in float32, taken `loss_block`
positions at a time so that the logits of the whole batch never exist, and the
step's counters; without, the logits. Departures from the published model:
no router auxiliary loss, no multi-token-prediction module, no dropout, one
document a sequence.

Scopes of the compiled step (benchmarks/metrics read device time by them):
`gdn/{in_proj,conv,scan,out}`, `attn/{qkv,core,out}`,
`moe/{router,dispatch,experts,combine,shared}`, `lm_head`, `loss`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.models.lm_common import (
    _dense,
    _normal,
    held_experts,
    lm_outputs,
    remat_keeping_attention,
    rms,
)
from pytorchvideo_accelerate_tpu.ops.attention import (
    causal_gqa_attention,
    rotate_half,
)
from pytorchvideo_accelerate_tpu.ops.gated_delta import gated_delta_rule
from pytorchvideo_accelerate_tpu.ops.moe import expert_share, route
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island


@dataclasses.dataclass(frozen=True)
class Qwen3NextArch:
    """The sizes, under the names of the published config.json."""

    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    vocab_size: int = 151936
    rms_norm_eps: float = 1e-6
    # the share held here (docs/TOKENS.md); 0 experts held = all of them
    experts_held: int = 0
    expert_offset: int = 0

    @property
    def held(self) -> int:
        return held_experts(self.experts_held, self.num_experts)

    def layer_type(self, i: int) -> str:
        return ("full_attention" if (i + 1) % self.full_attention_interval == 0
                else "linear_attention")


class ZeroCentredNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        return end_island(rms(x, self.eps) * (1.0 + w), x.dtype)


class GatedDeltaNet(nn.Module):
    arch: Qwen3NextArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, _ = x.shape
        hk, hv = a.linear_num_key_heads, a.linear_num_value_heads
        dk, dv = a.linear_key_head_dim, a.linear_value_head_dim
        kdim, vdim = hk * dk, hv * dv
        with jax.named_scope("in_proj"):
            qkvz = _dense(self, "in_proj_qkvz", x, 2 * kdim + 2 * vdim, dt)
            ba = _dense(self, "in_proj_ba", x, 2 * hv, dt)
            qkv, z = qkvz[..., :2 * kdim + vdim], qkvz[..., 2 * kdim + vdim:]
        with jax.named_scope("conv"):
            # causal depthwise conv over time, kernel taps oldest first
            taps = a.linear_conv_kernel_dim
            w = self.param("conv", _normal(), (taps, qkv.shape[-1])).astype(dt)
            padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
            qkv = jax.nn.silu(sum(padded[:, i:i + t] * w[i] for i in range(taps)))
        with jax.named_scope("scan"):
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(
                    jax.random.uniform(key, shape, minval=1e-3, maxval=16.0)),
                (hv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
            beta = jax.nn.sigmoid(f32_island(ba[..., :hv]))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                f32_island(ba[..., hv:]) + dt_bias)
            q = qkv[..., :kdim].reshape(b, t, hk, dk)
            k = qkv[..., kdim:2 * kdim].reshape(b, t, hk, dk)
            v = qkv[..., 2 * kdim:].reshape(b, t, hv, dv)

            def l2norm(y):
                y = f32_island(y)
                return y * jax.lax.rsqrt(
                    jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

            q = end_island(l2norm(q) * dk ** -0.5, dt)
            k = end_island(l2norm(k), dt)
            # hk key heads: value heads h*hv/hk .. share key head h
            o, _ = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("out"):
            scale = self.param("norm", nn.initializers.ones, (dv,))
            o = rms(o, a.rms_norm_eps) * scale * jax.nn.silu(
                f32_island(z.reshape(b, t, hv, dv)))
            return _dense(self, "out_proj", end_island(o, dt).reshape(b, t, vdim),
                          a.hidden_size, dt)


class GatedAttention(nn.Module):
    arch: Qwen3NextArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, _ = x.shape
        hq, hkv, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim
        with jax.named_scope("qkv"):
            qg = _dense(self, "q_proj", x, hq * 2 * d, dt).reshape(b, t, hq, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            k = _dense(self, "k_proj", x, hkv * d, dt).reshape(b, t, hkv, d)
            v = _dense(self, "v_proj", x, hkv * d, dt).reshape(b, t, hkv, d)
            q = ZeroCentredNorm(a.rms_norm_eps, name="q_norm")(q)
            k = ZeroCentredNorm(a.rms_norm_eps, name="k_norm")(k)
            rotary = int(d * a.partial_rotary_factor)
            positions = jnp.arange(t)
            q = rotate_half(q, positions, a.rope_theta, rotary)
            k = rotate_half(k, positions, a.rope_theta, rotary)
        with jax.named_scope("core"):
            o = causal_gqa_attention(q, k, v, scale=d ** -0.5)
        with jax.named_scope("out"):
            o = o * jax.nn.sigmoid(f32_island(gate)).astype(dt)
            return _dense(self, "o_proj", o.reshape(b, t, hq * d),
                          a.hidden_size, dt)


class SparseMoe(nn.Module):
    """The routed experts held here plus the shared expert; returns
    (y, (rows (held,), tight)): the tokens each held expert computed, and
    whether they fit the tight row buffers (`expert_share`)."""

    arch: Qwen3NextArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, d = x.shape
        f = a.moe_intermediate_size
        flat = x.reshape(b * t, d)
        with jax.named_scope("router"):
            router = self.param("router", _normal(), (d, a.num_experts))
            weights, experts = route(flat, router, a.num_experts_per_tok,
                                     a.norm_topk_prob)
        w_gate = self.param("w_gate", _normal(), (a.held, d, f))
        w_up = self.param("w_up", _normal(), (a.held, d, f))
        w_down = self.param("w_down", _normal(), (a.held, f, d))
        y, rows, tight = expert_share(flat, weights, experts, w_gate, w_up,
                                      w_down, a.expert_offset, a.num_experts)
        with jax.named_scope("shared"):
            fs = a.shared_expert_intermediate_size
            hidden = jax.nn.silu(_dense(self, "shared_gate_proj", flat, fs, dt)) \
                * _dense(self, "shared_up_proj", flat, fs, dt)
            shared = _dense(self, "shared_down_proj", hidden, d, dt)
            gate = jax.nn.sigmoid(f32_island(
                _dense(self, "shared_expert_gate", flat, 1, dt)))
            y = y + shared * gate.astype(dt)
        return y.reshape(b, t, d), (rows, tight)


class _Mixer(nn.Module):
    """x + mixer(zc_norm(x)): one rematerialised unit of a layer."""

    arch: Qwen3NextArch
    dtype: Any
    kind: str

    @nn.compact
    def __call__(self, x):
        normed = ZeroCentredNorm(self.arch.rms_norm_eps, name="input_norm")(x)
        if self.kind == "full_attention":
            return x + GatedAttention(self.arch, self.dtype, name="attn")(normed)
        return x + GatedDeltaNet(self.arch, self.dtype, name="gdn")(normed)


class _Mixture(nn.Module):
    """h + moe(zc_norm(h)): the other rematerialised unit."""

    arch: Qwen3NextArch
    dtype: Any

    @nn.compact
    def __call__(self, h):
        normed = ZeroCentredNorm(self.arch.rms_norm_eps, name="post_norm")(h)
        y, share = SparseMoe(self.arch, self.dtype, name="moe")(normed)
        return h + y, share


class Qwen3Next(nn.Module):
    arch: Qwen3NextArch
    dtype: Any = jnp.bfloat16
    remat: bool = True       # per mixer and per mixture: boundaries only
    loss_block: int = 2048   # positions whose logits exist at once

    @nn.compact
    def __call__(self, tokens, targets: Optional[jnp.ndarray] = None,
                 weights: Optional[jnp.ndarray] = None, train: bool = False):
        del train  # no dropout, no batch statistics
        a = self.arch
        embed = self.param("embed", _normal(), (a.vocab_size, a.hidden_size))
        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        mixer_cls = remat_keeping_attention(_Mixer) if self.remat else _Mixer
        mixture_cls = nn.remat(_Mixture) if self.remat else _Mixture
        shares = []
        for i in range(a.num_hidden_layers):
            x = mixer_cls(a, self.dtype, a.layer_type(i), name=f"mixer_{i}")(x)
            x, share = mixture_cls(a, self.dtype, name=f"mixture_{i}")(x)
            shares.append(share)
        x = ZeroCentredNorm(a.rms_norm_eps, name="final_norm")(x)
        head = self.param("lm_head", _normal(), (a.hidden_size, a.vocab_size))
        return lm_outputs(x, head, targets, weights, self.loss_block, shares)

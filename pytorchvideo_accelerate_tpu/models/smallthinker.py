"""SmallThinker: a decoder whose layers differ by window and by position
encoding, and whose router reads the attention's input.

Source: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
(config.json, `model_name` `smallthinker_21b_instruct`; arXiv:2507.20984).
Layer i attends causally over every earlier token with NO positional encoding
where `sliding_window_layout[i]` / `rope_layout[i]` are 0, and over the
trailing `sliding_window_size` tokens with full-width rotary where they are 1
(the published layouts are `[0, 1, 1, 1]` x 13). Every layer ends in a routed
mixture of ReGLU experts with no shared expert (ops/moe.py), whose routing is
made from the ATTENTION's normed input. `norm(x) = w * x * rsqrt(mean(x^2) +
eps)`, w initialised 1; no biases anywhere.

    n = norm_in(x);  (w_k, e_k) = route(n);  h = x + attn_i(n)
    y = h + moe(norm_post(h); w_k, e_k)
    after the last layer: norm_f, then the untied head

The mixer (norm, routing, attention) and the mixture are two rematerialised
units; what crosses between them besides `h` is the routing, (N, k) float32
weights and (N, k) int32 experts. The module is told which share of the model
it holds (`SmallThinkerArch`), as `models/qwen3_next.py` is, and returns the
same things. Departures from the published model: the router's input is
assumed from the paper (config.json has no key for it), no secondary experts,
no router auxiliary loss, no dropout, one document a sequence. Every matrix
starts N(0, 0.02), the embedding too, as `models/qwen3_next.py`'s do (the
published model's own `_init_weights` is not in this repository: the std is
unverified). With RANDOM weights at that std the routers past the first layer
see nearly one vector (docs/TOKENS.md); the benchmark gives its cell weights
of its own (`benchmarks/reference/smallthinker.py` `init_params`).

Scopes of the compiled step (benchmarks/metrics read device time by them):
`attn/{qkv,core,out}` (the full layers), `swa/{qkv,core,out}` (the windowed
layers), `moe/{router,dispatch,experts,combine}`, `lm_head`, `loss`;
`moe/router` is opened in the mixer unit, where the routing is made.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.models.lm_common import (
    Attention,
    Norm,
    _normal,
    held_experts,
    lm_outputs,
    remat_keeping_attention,
)
from pytorchvideo_accelerate_tpu.ops.moe import expert_share, route


@dataclasses.dataclass(frozen=True)
class SmallThinkerArch:
    """The sizes, under the names of the published config.json."""

    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1500000.0
    sliding_window_size: int = 4096
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    norm_topk_prob: bool = True
    vocab_size: int = 151936
    rms_norm_eps: float = 1e-6
    # the share held here (docs/TOKENS.md); 0 experts held = all of them
    experts_held: int = 0
    expert_offset: int = 0

    @property
    def held(self) -> int:
        return held_experts(self.experts_held, self.moe_num_primary_experts)

    @property
    def period(self) -> int:
        """Layers after which both layouts repeat."""
        both = list(zip(self.rope_layout, self.sliding_window_layout))
        return next(p for p in range(1, len(both) + 1)
                    if len(both) % p == 0 and both == both[:p] * (len(both) // p))


class Router(nn.Module):
    """(weights (N, k) float32, experts (N, k) int32) over ALL experts."""

    arch: SmallThinkerArch

    @nn.compact
    def __call__(self, x):
        a = self.arch
        with jax.named_scope("router"):
            kernel = self.param("router", _normal(),
                                (x.shape[-1], a.moe_num_primary_experts))
            return route(x.reshape(-1, x.shape[-1]), kernel,
                         a.moe_num_active_primary_experts, a.norm_topk_prob)


class Experts(nn.Module):
    """The routed ReGLU experts held here, given the routing; returns
    (y, (rows (held,), tight)): the tokens each held expert computed, and
    whether they fit the tight row buffers (`expert_share`)."""

    arch: SmallThinkerArch
    dtype: Any

    @nn.compact
    def __call__(self, x, weights, experts):
        a = self.arch
        b, t, d = x.shape
        f = a.moe_ffn_hidden_size
        w_gate = self.param("w_gate", _normal(), (a.held, d, f))
        w_up = self.param("w_up", _normal(), (a.held, d, f))
        w_down = self.param("w_down", _normal(), (a.held, f, d))
        y, rows, tight = expert_share(x.reshape(b * t, d), weights, experts,
                                      w_gate, w_up, w_down, a.expert_offset,
                                      a.moe_num_primary_experts,
                                      activation=jax.nn.relu)
        return y.reshape(b, t, d), (rows, tight)


class _Mixer(nn.Module):
    """n = norm(x): the routing from n, and x + attn(n): one rematerialised
    unit of a layer."""

    arch: SmallThinkerArch
    dtype: Any
    rotary: bool
    window: Optional[int]

    @nn.compact
    def __call__(self, x):
        normed = Norm(self.arch.rms_norm_eps, name="input_norm")(x)
        weights, experts = Router(self.arch, name="moe")(normed)
        attn = Attention(self.arch, self.dtype, self.rotary, self.window,
                         name="swa" if self.window else "attn")
        return x + attn(normed), weights, experts


class _Mixture(nn.Module):
    """h + moe(norm(h); routing): the other rematerialised unit."""

    arch: SmallThinkerArch
    dtype: Any

    @nn.compact
    def __call__(self, h, weights, experts):
        normed = Norm(self.arch.rms_norm_eps, name="post_norm")(h)
        y, share = Experts(self.arch, self.dtype, name="moe")(normed, weights,
                                                              experts)
        return h + y, share


class SmallThinker(nn.Module):
    arch: SmallThinkerArch
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
            window = a.sliding_window_size if a.sliding_window_layout[i] else None
            x, w_k, e_k = mixer_cls(a, self.dtype, bool(a.rope_layout[i]),
                                    window, name=f"mixer_{i}")(x)
            x, share = mixture_cls(a, self.dtype,
                                   name=f"mixture_{i}")(x, w_k, e_k)
            shares.append(share)
        x = Norm(a.rms_norm_eps, name="final_norm")(x)
        head = self.param("lm_head", _normal(), (a.hidden_size, a.vocab_size))
        return lm_outputs(x, head, targets, weights, self.loss_block, shares)

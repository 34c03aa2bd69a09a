"""Ouro: a decoder whose layer stack runs several times a step, with a head,
a loss and an exit gate after every pass.

Source: https://huggingface.co/ByteDance/Ouro-2.6B (config.json, `model_type`
`ouro`; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741). ONE set of `num_hidden_layers` layers is applied
`total_ut_steps` times; every layer is full causal attention (16 query heads
on 16 key-value heads of 128: groups of one; rotate-half rotary over the whole
head) and a gated SiLU MLP, each between two RMSNorms (`norm(x) = w * x *
rsqrt(mean(x^2) + eps)`, w initialised 1; no biases in a layer):

    layer_i(x):  h = x + norm_a2( attn( norm_a1(x) ) )
                 y = h + norm_m2( mlp ( norm_m1(h) ) ),  mlp(u) = (silu(u W_gate) * (u W_up)) W_down
    x_0 = embed[tokens];  x_t = norm_f( layer_{L-1}( ... layer_0( x_{t-1} ) ) ),  t = 1..total_ut_steps
    logits_t = x_t W_head (untied);  lambda_t = sigmoid( x_t . w_exit + b_exit )

The same layers, the same `norm_f`, the same head and the same gate serve
every pass; `norm_f`'s output feeds the next pass. Training scores a position
with the paper's stage-I objective: sum_t p_t CE_t - beta H(p), p the exit
distribution the gates make (`lm_common.exit_weighted_loss`). Without
`targets` the model returns the LAST pass's logits.

Assumed (config.json has no key; the paper and the published
`modeling_ouro.py` are the ground): the four norms a layer, `norm_f` between
passes, one biased gate for all passes, the objective and its beta 0.1. Not
here: the stage-II gate training, the inference-time early exit
(`early_exit_threshold`) and the cache shared between passes, dropout,
packing. Every matrix starts N(0, 0.02), the embedding too, as the other
token families' do; the benchmark gives its cell weights of its own
(`benchmarks/reference/ouro.py` `init_params`).

The passes are ONE `lax.scan` whose body closes over the stack's parameters:
the traced step holds `num_hidden_layers` layer bodies, each a
rematerialised unit, whatever `total_ut_steps` is, and the scan's transpose
adds a weight's gradients over the passes up. Against the passes unrolled as a
Python loop (32 bodies at the cell's size) the scan compiled the step in 40 s
where the loop took 159, and ran it in 578.9 ms where the loop took 591.4, at
the same peak memory (PERF.md section 6, PR 36).

Scopes of the compiled step (benchmarks/metrics read device time by them):
`attn/{qkv,core,out}`, `mlp/{gate_up,down}`, `lm_head`, `loss`,
`exit/{gate,pdf}`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.models.lm_common import (
    Attention,
    Norm,
    _dense,
    _normal,
    looped_lm_outputs,
    remat_keeping_attention,
)


@dataclasses.dataclass(frozen=True)
class OuroArch:
    """The sizes, under the names of the published config.json."""

    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    vocab_size: int = 49152
    total_ut_steps: int = 4
    exit_entropy_beta: float = 0.1   # assumed: the paper's, no key


class GatedMlp(nn.Module):
    arch: OuroArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        with jax.named_scope("gate_up"):
            hidden = jax.nn.silu(
                _dense(self, "gate_proj", x, a.intermediate_size, dt)) \
                * _dense(self, "up_proj", x, a.intermediate_size, dt)
        with jax.named_scope("down"):
            return _dense(self, "down_proj", hidden, a.hidden_size, dt)


class _Layer(nn.Module):
    """One layer execution, attention and MLP each between two norms: the
    rematerialised unit."""

    arch: OuroArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a = self.arch
        norm = lambda name: Norm(a.rms_norm_eps, name=name)  # noqa: E731
        attn = Attention(a, self.dtype, rotary=True, window=None, name="attn")
        h = x + norm("attn_out_norm")(attn(norm("input_norm")(x)))
        mlp = GatedMlp(a, self.dtype, name="mlp")
        return h + norm("mlp_out_norm")(mlp(norm("mlp_norm")(h)))


class _Stack(nn.Module):
    """One pass: the layers, each a rematerialised unit, then `norm_f`;
    returns x_t twice, as a scan's body does (carry, output)."""

    arch: OuroArch
    dtype: Any
    remat: bool

    @nn.compact
    def __call__(self, x):
        a = self.arch
        layer_cls = remat_keeping_attention(_Layer) if self.remat else _Layer
        for i in range(a.num_hidden_layers):
            x = layer_cls(a, self.dtype, name=f"layer_{i}")(x)
        x = Norm(a.rms_norm_eps, name="final_norm")(x)
        return x, x


class Ouro(nn.Module):
    arch: OuroArch
    dtype: Any = jnp.bfloat16
    remat: bool = True       # per layer execution: boundaries only
    loss_block: int = 2048   # positions whose logits exist at once

    @nn.compact
    def __call__(self, tokens, targets: Optional[jnp.ndarray] = None,
                 weights: Optional[jnp.ndarray] = None, train: bool = False):
        del train  # no dropout, no batch statistics
        a = self.arch
        embed = self.param("embed", _normal(), (a.vocab_size, a.hidden_size))
        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        # one scan over the passes. The stack is a module of its own, held
        # here as ONE parameter subtree that the scan's body closes over: a
        # single set of layer parameters, the body traced once, every pass's
        # x_t stacked on the way out
        stack = _Stack(a, self.dtype, self.remat, parent=None, name="stack")
        # (`variable`, not `param`: `param` would trace the initialiser once
        # more in every apply to check its shapes)
        shared = self.variable(
            "params", "stack",
            lambda: stack.init(self.make_rng("params"), x)["params"]).value
        _, hidden = jax.lax.scan(
            lambda x, _: stack.apply({"params": shared}, x), x, None,
            length=a.total_ut_steps)
        head = self.param("lm_head", _normal(), (a.hidden_size, a.vocab_size))
        gate = self.param("exit_gate", _normal(), (a.hidden_size,))
        bias = self.param("exit_bias", nn.initializers.zeros, ())
        return looped_lm_outputs(hidden, head, gate, bias, targets, weights,
                                 a.exit_entropy_beta, self.loss_block)

"""Granite-4.0-H: a dense decoder of Mamba-2 and attention layers by a list
of layer kinds, every layer two residual units (a mixer, then a SwiGLU MLP),
with the four muP multipliers of its config.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro (config.json,
`model_type` `granitemoehybrid` with `num_local_experts` 0). Layer i is
`layer_types[i]`: `mamba` (a Mamba-2 mixer, models/mamba2.py) or `attention`
(causal grouped-query attention without positions,
`position_embedding_type` `nope`). With `norm(x) = w * x * rsqrt(mean(x^2) +
eps)` and E the embedding table:

    h = embedding_multiplier * E[tokens]
    layer:  h = h + residual_multiplier * mixer(norm_in(h))
            h = h + residual_multiplier * (silu(u W_g) * (u W_u)) W_d,
                u = norm_post(h), [W_g | W_u] = `input_linear` (halves in
                that order)
    logits = (norm_f(h) / logits_scaling) E^T      (the table is tied)

    mamba:      [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC) + b) = [x | B | C]
                dt = softplus(dt + dt_bias);  y = ssd(x, dt, -exp(A_log), B, C, D)
                out = (rms(y * silu(z)) * w) W_out   (`mamba_n_groups` groups)
    attention:  softmax(attention_multiplier * q k^T) v over keys s <= t,
                heads of hidden_size / num_attention_heads, no biases

Each unit (mixer, MLP) is one rematerialised unit; an attention unit keeps
the flash forward's `o` and `lse` where the kernels run
(`remat_keeping_attention`). The module is told which rows of the vocabulary
it holds (`vocab_size`): the table is embedding and head of that slice, so
its gradient is the sum of both uses. Assumed (config.json has no key): the
Mamba-2 rule's dt range [0.001, 0.1] with floor 1e-4 (Nemotron-H's and the
Mamba-2 defaults), matrices N(0, 0.02) and the table N(0, 0.1) (the Granite
family's `initializer_range`), the conv PyTorch's U(-1/2, 1/2), `A_log` =
log(1..H), `D` = 1. Departures from the published model: no dropout, one
document a sequence.

Scopes of the compiled step (benchmarks/metrics read device time by them):
`mamba/{in_proj,conv,scan,out}`, `attn/{qkv,core,out}`, `mlp/{gate_up,down}`,
`lm_head`, `loss`.
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
    _dense,
    _normal,
    lm_outputs,
    remat_keeping_attention,
)
from pytorchvideo_accelerate_tpu.models.mamba2 import Mamba

# the published layer_types: attention at layers 5, 15, 25 and 35 of 40
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))
TABLE_STD = 0.1  # assumed: the Granite family's initializer_range


@dataclasses.dataclass(frozen=True)
class GraniteHArch:
    """The sizes, under the names of the published config.json."""

    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    vocab_size: int = 100352
    # assumed: the Mamba-2 rule's dt range (no key in config.json)
    time_step: Tuple[float, float, float] = (0.001, 0.1, 1e-4)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class Mlp(nn.Module):
    """The SwiGLU MLP: one `input_linear` of both halves (gate, then up),
    `output_linear` back to the hidden width."""

    arch: GraniteHArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        f = a.shared_intermediate_size
        with jax.named_scope("gate_up"):
            both = _dense(self, "input_linear", x, 2 * f, dt)
            hidden = jax.nn.silu(both[..., :f]) * both[..., f:]
        with jax.named_scope("down"):
            return _dense(self, "output_linear", hidden, a.hidden_size, dt)


class _MixerUnit(nn.Module):
    """h + residual_multiplier * mixer(norm_in(h)): one rematerialised unit."""

    arch: GraniteHArch
    dtype: Any
    kind: str

    @nn.compact
    def __call__(self, x):
        a = self.arch
        normed = Norm(a.rms_norm_eps, name="norm")(x)
        if self.kind == "mamba":
            y = Mamba(a.hidden_size, a.mamba_n_heads, a.mamba_d_head,
                      a.mamba_n_groups, a.mamba_d_state, a.mamba_d_conv,
                      a.mamba_chunk_size, a.rms_norm_eps, a.time_step,
                      self.dtype, name="mamba")(normed)
        else:
            y = Attention(a, self.dtype, rotary=False, window=None,
                          scale=a.attention_multiplier, name="attn")(normed)
        return x + a.residual_multiplier * y


class _MlpUnit(nn.Module):
    """h + residual_multiplier * mlp(norm_post(h)): one rematerialised unit."""

    arch: GraniteHArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a = self.arch
        normed = Norm(a.rms_norm_eps, name="norm")(x)
        return x + a.residual_multiplier * Mlp(a, self.dtype, name="mlp")(normed)


class _Layer(nn.Module):
    """The mixer unit, then the MLP unit."""

    arch: GraniteHArch
    dtype: Any
    kind: str
    remat: bool

    @nn.compact
    def __call__(self, x):
        a = self.arch
        mixer_cls, mlp_cls = _MixerUnit, _MlpUnit
        if self.remat:
            mixer_cls = (remat_keeping_attention(_MixerUnit)
                         if self.kind == "attention" else nn.remat(_MixerUnit))
            mlp_cls = nn.remat(_MlpUnit)
        x = mixer_cls(a, self.dtype, self.kind, name="mixer")(x)
        return mlp_cls(a, self.dtype, name="ffn")(x)


class GraniteH(nn.Module):
    arch: GraniteHArch
    dtype: Any = jnp.bfloat16
    remat: bool = True       # per unit: boundaries only
    loss_block: int = 2048   # positions whose logits exist at once

    @nn.compact
    def __call__(self, tokens, targets: Optional[jnp.ndarray] = None,
                 weights: Optional[jnp.ndarray] = None, train: bool = False):
        del train  # no dropout, no batch statistics
        a = self.arch
        table = self.param("embed", _normal(TABLE_STD),
                           (a.vocab_size, a.hidden_size))
        x = (jnp.take(table, tokens, axis=0)
             * a.embedding_multiplier).astype(self.dtype)
        for i, kind in enumerate(a.layer_types[:a.num_hidden_layers]):
            x = _Layer(a, self.dtype, kind, self.remat, name=f"layer_{i}")(x)
        x = Norm(a.rms_norm_eps, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            x = x / jnp.asarray(a.logits_scaling, x.dtype)
        return lm_outputs(x, table.T, targets, weights, self.loss_block, [])

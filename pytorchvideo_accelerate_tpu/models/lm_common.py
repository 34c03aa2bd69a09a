"""What a token model's module needs whatever its family (as `common.py` is
to the conv nets): the norm, a bias-free projection, the initialiser, causal
grouped-query attention (rotary or not, banded or not), the loss summed a
block of positions at a time (one pass, or a looped model's passes weighted by
its exit distribution), and the arithmetic of the share a chip holds
(docs/TOKENS.md)."""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.ops.attention import (
    causal_gqa_attention,
    keeping_kernel_results,
    rotate_half,
)
from pytorchvideo_accelerate_tpu.ops.pallas_attention import KEPT_NAMES
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island


def _normal(stddev=0.02):
    return nn.initializers.normal(stddev)


def rms(x, eps):
    """x * rsqrt(mean(x^2) + eps): float32 statistics, result in float32."""
    x = f32_island(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """The causal depthwise conv over time of x (B, T, C) by the taps w (K,
    C), oldest first: out_t = sum_i w_i x_{t-K+1+i}, zeros before the start."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(taps))


def _dense(mod, name, x, features, dtype):
    w = mod.param(name, _normal(), (x.shape[-1], features))
    return jnp.dot(x, w.astype(dtype))


class Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return end_island(rms(x, self.eps) * w, x.dtype)


# a remat unit's policy: keep the flash forward's `o` and `lse`, nothing else
KEEP_ATTENTION = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def remat_keeping_attention(cls):
    """`nn.remat(cls)` under `KEEP_ATTENTION`: the backward pass recomputes
    the unit's norms and projections but not the attention's forward kernel,
    which runs once a unit execution. Where the kernels are not taken (the
    CPU, heads not a multiple of 128 wide) no value carries the names, and
    the unit keeps nothing, as a plain `nn.remat` does (docs/KERNELS.md).
    The unit is traced inside `keeping_kernel_results`, for the kept-sites
    gauge."""

    class Unit(cls):
        # not wrapped: flax wraps `cls.__call__` once, as under a plain
        # `nn.remat`, so the unit's scopes are the same
        @nn.nowrap
        def __call__(self, *args, **kwargs):
            with keeping_kernel_results():
                return super().__call__(*args, **kwargs)

    return nn.remat(Unit, policy=KEEP_ATTENTION)


class Attention(nn.Module):
    """Causal grouped-query attention, rotary or not, banded or not. `arch`
    gives `hidden_size`, `num_attention_heads`, `num_key_value_heads`,
    `head_dim` and `rope_theta` (models/smallthinker.py, models/ouro.py).
    `scale`: the softmax's, `head_dim ** -0.5` where None (a family with a
    published multiplier gives its own: models/granite_h.py)."""

    arch: Any
    dtype: Any
    rotary: bool
    window: Optional[int]
    scale: Optional[float] = None

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, _ = x.shape
        hq, hkv, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim
        with jax.named_scope("qkv"):
            q = _dense(self, "q_proj", x, hq * d, dt).reshape(b, t, hq, d)
            k = _dense(self, "k_proj", x, hkv * d, dt).reshape(b, t, hkv, d)
            v = _dense(self, "v_proj", x, hkv * d, dt).reshape(b, t, hkv, d)
            if self.rotary:
                positions = jnp.arange(t)
                q = rotate_half(q, positions, a.rope_theta, d)
                k = rotate_half(k, positions, a.rope_theta, d)
        with jax.named_scope("core"):
            scale = d ** -0.5 if self.scale is None else self.scale
            o = causal_gqa_attention(q, k, v, scale=scale, window=self.window)
        with jax.named_scope("out"):
            return _dense(self, "o_proj", o.reshape(b, t, hq * d),
                          a.hidden_size, dt)


def held_experts(experts_held: int, num_experts: int) -> int:
    """Experts held here; 0 held = all of them."""
    return experts_held or num_experts


def check_share(num_layers: int, period: int, expert_offset: int, held: int,
                num_experts: int) -> None:
    """A share is whole periods of the layer pattern and experts
    [offset, offset + held) among the model's."""
    if num_layers % period:
        raise ValueError(
            f"model.num_layers={num_layers} is not whole periods "
            f"of {period} layers")
    if not 0 <= expert_offset <= num_experts - held:
        raise ValueError(
            f"experts [{expert_offset}, {expert_offset + held}) "
            f"are not among the model's {num_experts}")


def _block_cross_entropy(h, head_kernel, y):
    """A block's per-position cross-entropy (n,) and argmax hits (n,) of `h`
    (n, D) through the head against `y` (n,), float32."""
    with jax.named_scope("lm_head"):
        logits = f32_island(jnp.dot(h, head_kernel.astype(h.dtype)))
    with jax.named_scope("loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        return logz - picked, hit


def next_token_loss(hidden, head_kernel, targets, weight, block: int):
    """Summed cross-entropy of `hidden` (N, D) against `targets` (N,) through
    the head (D, V), each position times its `weight` (N,), float32, `block`
    positions at a time (each block's logits are rematerialised in the
    backward pass); also the weighted number of argmaxes that hit."""
    n, d = hidden.shape
    pad = -n % block
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weight = jnp.pad(weight, (0, pad))
    shape = ((n + pad) // block, block)

    @jax.checkpoint
    def one(carry, xs):
        h, y, w = xs
        ce, hit = _block_cross_entropy(h, head_kernel, y)
        with jax.named_scope("loss"):
            loss, correct = carry
            return (loss + (ce * w).sum(), correct + (hit * w).sum()), None

    zero = jnp.zeros((), jnp.float32)
    (loss, correct), _ = jax.lax.scan(
        one, (zero, zero),
        (hidden.reshape(*shape, d), targets.reshape(shape), weight.reshape(shape)))
    return loss, correct


def lm_outputs(x, head, targets, weights, loss_block: int, shares):
    """What a token model returns after its last norm: the logits (B, T, V)
    in float32 without `targets`, else the dict `make_lm_step` reads.
    `shares`: each mixture layer's (rows by held expert, whether they fit
    the tight row buffers), as `ops/moe.py` `expert_share` returns them;
    where there are none (no layer is a mixture) the dict has no expert
    rows."""
    if targets is None:
        with jax.named_scope("lm_head"):
            return f32_island(jnp.dot(x, head.astype(x.dtype)))
    b, t, d = x.shape
    if weights is None:
        weights = jnp.ones((b, t), jnp.float32)
    loss_sum, correct = next_token_loss(
        x.reshape(b * t, d), head, targets.reshape(b * t),
        weights.reshape(b * t), loss_block)
    out = {"loss_sum": loss_sum, "correct": correct, "count": weights.sum()}
    if shares:
        out["expert_rows"] = jnp.stack([r for r, _ in shares])  # (layers, held)
        out["expert_tight"] = jnp.stack([t for _, t in shares])  # (layers,)
    return out


def exit_weighted_loss(hidden, head_kernel, gate_kernel, gate_bias, targets,
                       weight, beta: float, block: int):
    """The loss of a looped model (models/ouro.py): `hidden` (P, N, D) holds
    the P passes' final hidden states of N positions. Position n exits after
    pass t with p_t = lambda_t prod_{j<t} (1 - lambda_j), lambda_t =
    sigmoid(hidden_t . gate_kernel + gate_bias), the last pass taking what is
    left; its loss is sum_t p_t CE_t - beta H(p), CE_t the cross-entropy of
    pass t's logits against `targets` (N,), H the entropy of p. float32, in
    the blocks of `next_token_loss`: a block's logits exist for one pass at a
    time and are rematerialised in the backward pass.

    Returns the sums over positions, each times its `weight` (N,): the loss,
    the last pass's argmaxes that hit, and per pass (P,) of p_t and of CE_t."""
    passes, n, d = hidden.shape
    pad = -n % block
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weight = jnp.pad(weight, (0, pad))
    blocks = (n + pad) // block

    one = jax.checkpoint(
        lambda h, y: _block_cross_entropy(h, head_kernel, y))
    # one (pass, block) after another: (P * blocks) steps of one scan
    _, (ce, hit) = jax.lax.scan(
        lambda c, xs: (c, one(*xs)), None,
        (hidden.reshape(passes * blocks, block, d),
         jnp.tile(targets.reshape(blocks, block), (passes, 1))))
    ce = ce.reshape(passes, n + pad)
    hit = hit.reshape(passes, n + pad)[-1]
    with jax.named_scope("exit"):
        with jax.named_scope("gate"):
            z = f32_island(jnp.dot(hidden, gate_kernel.astype(hidden.dtype))) \
                + gate_bias
        with jax.named_scope("pdf"):
            # log p_t = log lambda_t + sum_{j<t} log(1 - lambda_j); the last
            # pass takes the rest: log p_P = sum_{j<P} log(1 - lambda_j)
            stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)
            before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
            log_p = before + jnp.concatenate(
                [jax.nn.log_sigmoid(z[:-1]), jnp.zeros_like(z[:1])])
            p = jnp.exp(log_p)
            entropy = -(p * log_p).sum(axis=0)
            loss = (p * ce).sum(axis=0) - beta * entropy
    return {"loss_sum": (loss * weight).sum(),
            "correct": (hit * weight).sum(),
            "ut": {"exit_mass": (p * weight).sum(axis=1),
                   "loss": (ce * weight).sum(axis=1)}}


def looped_lm_outputs(hidden, head, gate_kernel, gate_bias, targets, weights,
                      beta: float, loss_block: int):
    """What a looped token model returns after its passes (`hidden`: (P, B,
    T, D), each after the last norm): the LAST pass's logits (B, T, V) in
    float32 without `targets`, else the dict `make_lm_step` reads: no expert
    rows, and `ut`, the per-pass sums of the exit distribution and of the
    cross-entropies."""
    if targets is None:
        with jax.named_scope("lm_head"):
            return f32_island(jnp.dot(hidden[-1], head.astype(hidden.dtype)))
    passes, b, t, d = hidden.shape
    if weights is None:
        weights = jnp.ones((b, t), jnp.float32)
    out = exit_weighted_loss(
        hidden.reshape(passes, b * t, d), head, gate_kernel, gate_bias,
        targets.reshape(b * t), weights.reshape(b * t), beta, loss_block)
    return {**out, "count": weights.sum()}

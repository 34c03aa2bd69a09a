"""What a token model's module needs whatever its family (as `common.py` is
to the conv nets): the norm's statistics, a bias-free projection, the
initialiser, the loss summed a block of positions at a time, and the
arithmetic of the share a chip holds (docs/TOKENS.md)."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.precision import f32_island


def _normal(stddev=0.02):
    return nn.initializers.normal(stddev)


def rms(x, eps):
    """x * rsqrt(mean(x^2) + eps): float32 statistics, result in float32."""
    x = f32_island(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _dense(mod, name, x, features, dtype):
    w = mod.param(name, _normal(), (x.shape[-1], features))
    return jnp.dot(x, w.astype(dtype))


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
        with jax.named_scope("lm_head"):
            logits = f32_island(jnp.dot(h, head_kernel.astype(h.dtype)))
        with jax.named_scope("loss"):
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
            loss, correct = carry
            return (loss + ((logz - picked) * w).sum(),
                    correct + (hit * w).sum()), None

    zero = jnp.zeros((), jnp.float32)
    (loss, correct), _ = jax.lax.scan(
        one, (zero, zero),
        (hidden.reshape(*shape, d), targets.reshape(shape), weight.reshape(shape)))
    return loss, correct


def lm_outputs(x, head, targets, weights, loss_block: int, rows):
    """What a token model returns after its last norm: the logits (B, T, V)
    in float32 without `targets`, else the dict `make_lm_step` reads."""
    if targets is None:
        with jax.named_scope("lm_head"):
            return f32_island(jnp.dot(x, head.astype(x.dtype)))
    b, t, d = x.shape
    if weights is None:
        weights = jnp.ones((b, t), jnp.float32)
    loss_sum, correct = next_token_loss(
        x.reshape(b * t, d), head, targets.reshape(b * t),
        weights.reshape(b * t), loss_block)
    return {"loss_sum": loss_sum, "correct": correct,
            "count": weights.sum(),
            "expert_rows": jnp.stack(rows)}      # (layers, held)

"""ops/attention.py `causal_gqa_attention` and `rotate_half`: causal softmax
attention with grouped queries, head size 256 and partial rotary, against the
dense masked product written out here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.ops.attention import (
    causal_gqa_attention,
    rotate_half,
)


def _dense(q, k, v, scale):
    """Every query head against its key-value head, one masked product."""
    hq, hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _qkv(t, hq=4, hkv=2, d=256, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (2, t, hq, d)),
            jax.random.normal(ks[1], (2, t, hkv, d)),
            jax.random.normal(ks[2], (2, t, hkv, d)))


# float32 on the CPU: blocks change the order of the softmax's sums only
@pytest.mark.parametrize("t,block_q", [
    pytest.param(96, 512, id="one_dense_block"),
    pytest.param(96, 32, id="three_whole_blocks"),
    pytest.param(100, 32, id="ragged_last_block"),
])
def test_blocks_equal_the_dense_masked_product(t, block_q):
    q, k, v = _qkv(t)
    want = _dense(q, k, v, 256 ** -0.5)
    got = causal_gqa_attention(q, k, v, scale=256 ** -0.5, block_q=block_q)
    assert got.shape == q.shape
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_gradients_equal_the_dense_masked_product():
    q, k, v = _qkv(80, seed=1)
    cot = jax.random.normal(jax.random.key(9), q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    want = jax.grad(loss(lambda *a: _dense(*a, 256 ** -0.5)), (0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda *a: causal_gqa_attention(
        *a, scale=256 ** -0.5, block_q=32)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max() + 1)


def test_token_reads_no_later_key():
    q, k, v = _qkv(64, seed=2)
    base = causal_gqa_attention(q, k, v, block_q=16)
    k2 = k.at[:, 40:].add(3.0)
    v2 = v.at[:, 40:].add(3.0)
    moved = causal_gqa_attention(q, k2, v2, block_q=16)
    assert float(jnp.abs(moved[:, :40] - base[:, :40]).max()) == 0.0
    assert float(jnp.abs(moved[:, 40:] - base[:, 40:]).max()) > 0.1


def test_query_heads_must_divide_over_key_value_heads():
    q, k, v = _qkv(8, hq=3, hkv=2)
    with pytest.raises(ValueError, match="3 query heads over 2"):
        causal_gqa_attention(q, k, v)


def test_partial_rotary_rotates_the_first_dims_only():
    """64 of 256 dims, rotate-half pairing (i with i + 32), theta 1e7,
    against the angles written out in float64."""
    x = jax.random.normal(jax.random.key(3), (1, 50, 2, 256))
    out = rotate_half(x, jnp.arange(50), 1e7, 64)
    assert float(jnp.abs(out[..., 64:] - x[..., 64:]).max()) == 0.0
    xs = np.asarray(x, np.float64)
    freq = 1e7 ** (-np.arange(32) * 2.0 / 64)
    angle = np.arange(50)[:, None] * freq[None, :]
    cos, sin = np.cos(angle)[None, :, None, :], np.sin(angle)[None, :, None, :]
    a, b = xs[..., :32], xs[..., 32:64]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    assert float(np.abs(np.asarray(out[..., :64]) - want).max()) < 1e-4
    # position 0 is not rotated; norms are kept
    assert float(jnp.abs(out[:, 0] - x[:, 0]).max()) == 0.0
    assert float(jnp.abs(jnp.linalg.norm(out, axis=-1)
                         - jnp.linalg.norm(x, axis=-1)).max()) < 1e-4

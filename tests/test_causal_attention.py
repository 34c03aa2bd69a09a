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


# --- a trailing window: query t reads keys s with 0 <= t - s < window ---------

def _dense_banded(q, k, v, scale, window):
    """`_dense` under the band, as one masked product over all keys."""
    hq, hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    delta = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]
    mask = (delta >= 0) & (delta < window)
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


WINDOWS = [
    pytest.param(100, 16, 32, 4, 2, id="longer_than_the_window"),
    pytest.param(64, 16, 64, 4, 2, id="as_long_as_the_window"),
    pytest.param(48, 16, 64, 4, 2, id="shorter_than_the_window"),
    pytest.param(100, 16, 40, 4, 2, id="window_no_multiple_of_the_block"),
    pytest.param(100, 32, 7, 4, 2, id="window_shorter_than_a_block"),
    pytest.param(100, 16, 32, 7, 1, id="seven_query_heads_on_one"),
    pytest.param(100, 512, 32, 7, 1, id="seven_on_one_in_one_block"),
]


@pytest.mark.parametrize("t,block_q,window,hq,hkv", WINDOWS)
def test_window_equals_the_dense_banded_product(t, block_q, window, hq, hkv):
    q, k, v = _qkv(t, hq=hq, hkv=hkv, d=64, seed=4)
    want = _dense_banded(q, k, v, 0.125, window)
    got = causal_gqa_attention(q, k, v, scale=0.125, block_q=block_q,
                               window=window)
    assert got.shape == q.shape
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("t,block_q,window,hq,hkv",
                         [WINDOWS[i] for i in (0, 3, 4, 5)])
def test_window_gradients_equal_the_dense_banded_product(t, block_q, window,
                                                         hq, hkv):
    q, k, v = _qkv(t, hq=hq, hkv=hkv, d=64, seed=5)
    cot = jax.random.normal(jax.random.key(9), q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    want = jax.grad(loss(lambda *a: _dense_banded(*a, 0.125, window)),
                    (0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda *a: causal_gqa_attention(
        *a, scale=0.125, block_q=block_q, window=window)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max() + 1)


@pytest.mark.parametrize("block_q,window", [(16, 24), (16, 32), (512, 24)])
def test_query_reads_no_key_at_a_distance_of_the_window_or_more(block_q, window):
    """A huge value planted in the values up to position 20 reaches every
    query that may read one of them (t < 20 + window) and no other, though
    some of those keys lie in the key blocks the later queries' blocks read."""
    q, k, v = _qkv(96, d=64, seed=6)
    run = lambda v: causal_gqa_attention(  # noqa: E731
        q, k, v, block_q=block_q, window=window)
    base, moved = run(v), run(v.at[:, :21].add(1e4))
    reach = 20 + window
    assert float(jnp.abs(moved[:, reach:] - base[:, reach:]).max()) == 0.0
    assert float(jnp.abs(moved[:, :reach] - base[:, :reach])
                 .max(axis=(0, 2, 3)).min()) > 1.0


def _parent(q, k, v, scale=None, block_q=512):
    """`causal_gqa_attention` as it stood before it took a window, kept here
    word for word: `window=None` must trace to the same jaxpr."""
    from pytorchvideo_accelerate_tpu.precision import f32_island

    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    group = hq // hkv

    @jax.checkpoint
    def block(q_blk, k_seen, v_seen, start):
        n = q_blk.shape[1]
        rows = q_blk.reshape(b, n, hkv, group, d).transpose(0, 2, 3, 1, 4)
        rows = rows.reshape(b, hkv, group * n, d)
        logits = f32_island(jnp.einsum("bhrd,bkhd->bhrk", rows, k_seen)) * scale
        pos = start + jnp.arange(group * n) % n
        seen = jnp.arange(k_seen.shape[1])[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        out = jnp.einsum("bhrk,bkhd->bhrd", probs.astype(q.dtype), v_seen)
        out = out.reshape(b, hkv, group, n, d).transpose(0, 3, 1, 2, 4)
        return out.reshape(b, n, hq, d)

    outs = []
    for start in range(0, t, block_q):
        end = min(start + block_q, t)
        outs.append(block(q[:, start:end], k[:, :end], v[:, :end], start))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("window", [None, 100, 4096],
                         ids=["no_window", "window_of_the_length", "longer"])
def test_no_band_traces_to_the_jaxpr_it_had_before_the_window(window):
    """No window, and a window no shorter than the sequence, lower as the
    function did before: forward and gradient, jaxpr text for jaxpr text."""
    q, k, v = _qkv(100, d=64, seed=7)

    def text(fn):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), (0, 1, 2))
        return str(jax.make_jaxpr(fn)(q, k, v)), str(jax.make_jaxpr(grad)(q, k, v))

    assert text(lambda *a: causal_gqa_attention(
        *a, block_q=32, window=window)) == text(lambda *a: _parent(*a, block_q=32))

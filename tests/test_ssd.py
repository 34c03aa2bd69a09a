"""ops/ssd.py: the chunked state-space scan against the per-token recurrence
(output, last state and every gradient), sequences that are no multiple of
the chunk, heads sharing a group's B and C, and the sites gauge."""

import jax
import jax.numpy as jnp
import pytest

from pytorchvideo_accelerate_tpu.ops import ssd

# 4 heads of 8 in 2 groups of state 16: 2 heads a group
B, H, P, G, N = 2, 4, 8, 2, 16


def _inputs(t, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (B, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, t, H)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = jax.random.normal(k[3], (B, t, G, N))
    c = jax.random.normal(k[4], (B, t, G, N))
    d = jax.random.normal(k[5], (H,))
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("t,chunk", [(150, 32), (64, 64), (40, 128)],
                         ids=["tail_of_22", "one_whole_chunk", "shorter_than_a_chunk"])
def test_chunked_form_equals_the_recurrence(t, chunk):
    args = _inputs(t)
    want_y, want_state = jax.jit(ssd.ssd_recurrence)(*args)
    y, state = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=chunk))(*args)
    assert y.shape == (B, t, H, P) and y.dtype == jnp.float32
    assert _rel(y, want_y) < 1e-5
    # the padded tail leaves the state alone: the last state is the last token's
    assert _rel(state, want_state) < 1e-5


def test_every_gradient_equals_the_recurrences():
    """x, dt, a, b, c and d, through a cotangent that weighs every output
    differently, at T 150 in chunks of 32."""
    args = _inputs(150, seed=1)
    weight = jax.random.normal(jax.random.key(9), (B, 150, H, P))

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a)[0] * weight),
                                argnums=tuple(range(6))))(*args)

    got = grads(lambda *a: ssd.ssd_scan(*a, chunk=32))
    want = grads(ssd.ssd_recurrence)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        assert _rel(g, w) < 2e-5, name


def test_heads_read_their_groups_b_and_c():
    """Head h reads group h // (H / G): moving group 1's B moves heads 2 and 3
    alone, in both forms."""
    x, dt, a, b, c, d = _inputs(96, seed=2)
    moved = b.at[:, :, 1].multiply(3.0)
    for fn in (ssd.ssd_recurrence, lambda *v: ssd.ssd_scan(*v, chunk=32)):
        fn = jax.jit(fn)
        y0, _ = fn(x, dt, a, b, c, d)
        y1, _ = fn(x, dt, a, moved, c, d)
        diff = jnp.abs(y1 - y0).max(axis=(0, 1, 3))      # by head
        assert float(diff[:2].max()) == 0.0
        assert bool(jnp.all(diff[2:] > 1e-3))


def test_bfloat16_operands_keep_float32_state():
    args32 = _inputs(150, seed=3)
    x, dt, a, b, c, d = _inputs(150, seed=3, dtype=jnp.bfloat16)
    y, state = jax.jit(lambda *v: ssd.ssd_scan(*v, chunk=32))(x, dt, a, b, c, d)
    want, _ = jax.jit(ssd.ssd_recurrence)(*args32)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    # operands rounded to 8 bits of mantissa once a product: a few 2^-8
    assert _rel(y.astype(jnp.float32), want) < 3e-2


def test_strong_decay_underflows_and_never_overflows():
    """A step of exp(-400) forgets the state; no exponent is positive, so no
    value is infinite or NaN, forward or backward."""
    x, dt, a, b, c, d = _inputs(96, seed=4)
    dt = dt.at[:, 50].set(400.0)
    a = jnp.full((H,), -1.0)
    y, _ = jax.jit(lambda *v: ssd.ssd_scan(*v, chunk=32))(x, dt, a, b, c, d)
    want, _ = jax.jit(ssd.ssd_recurrence)(x, dt, a, b, c, d)
    assert bool(jnp.all(jnp.isfinite(y))) and _rel(y, want) < 5e-5
    g = jax.jit(jax.grad(
        lambda v: ssd.ssd_scan(x, v, a, b, c, d, chunk=32)[0].sum()))(dt)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_count_sites_collects_every_call():
    args = _inputs(64)
    with ssd.count_sites() as sites:
        jax.eval_shape(lambda *v: ssd.ssd_scan(*v, chunk=32), *args)
        jax.eval_shape(lambda *v: ssd.ssd_scan(*v, chunk=32), *args)
    assert sites == [((B, 64, H, P), (B, 64, G, N))] * 2
    # outside the block nothing is collected
    jax.eval_shape(lambda *v: ssd.ssd_scan(*v, chunk=32), *args)
    assert len(sites) == 2

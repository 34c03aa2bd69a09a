"""ops/gated_delta.py: the chunked gated delta rule against the per-token
recurrence that defines it, forward and every gradient."""

import jax
import jax.numpy as jnp
import pytest

from pytorchvideo_accelerate_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)


def _inputs(t, decay, beta, seed=0, b=2, h=3, dk=16, dv=24):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, t, h), minval=0.1)
    if beta is None:
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    else:
        beta = jnp.full((b, t, h), beta, jnp.float32)
    return q, k, v, g, beta


# float32 on the CPU: the two forms differ by summation order only. 2e-5 is
# ten float32 ulps of the O(1) outputs times the 64-token products; strong
# decay (exp(-20 a token)) multiplies rounding of tiny factors and reads 3e-5
# relative on the gradient of g, so gradients get 2e-4
CASES = [
    pytest.param(128, 1.0, None, id="two_whole_chunks"),
    pytest.param(150, 1.0, None, id="tail_of_22_tokens"),
    pytest.param(37, 1.0, None, id="shorter_than_a_chunk"),
    pytest.param(150, 20.0, None, id="strong_decay"),
    pytest.param(150, 0.01, None, id="weak_decay"),
    pytest.param(150, 1.0, 0.0, id="beta_0_writes_nothing"),
    pytest.param(150, 1.0, 1.0, id="beta_1_full_delta"),
]


@pytest.mark.parametrize("t,decay,beta", CASES)
def test_chunked_forward_equals_recurrence(t, decay, beta):
    args = _inputs(t, decay, beta)
    o_ref, s_ref = gated_delta_recurrence(*args)
    o, s = gated_delta_rule(*args)
    assert o.shape == o_ref.shape == args[2].shape
    assert float(jnp.abs(o - o_ref).max()) < 2e-5
    assert float(jnp.abs(s - s_ref).max()) < 2e-5
    if beta == 0.0:
        assert float(jnp.abs(o).max()) == 0.0  # nothing was ever written


@pytest.mark.parametrize("t,decay,beta", CASES)
def test_chunked_gradients_equal_recurrence(t, decay, beta):
    args = _inputs(t, decay, beta, seed=1)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a)[0] ** 2)

    want = jax.grad(loss(gated_delta_recurrence), argnums=range(5))(*args)
    got = jax.grad(loss(gated_delta_rule), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.abs(b).max()) + 1e-30
        assert float(jnp.abs(a - b).max()) / scale < 2e-4, name


def test_bfloat16_inputs_keep_a_float32_state():
    """The policy's path: bfloat16 q, k, v, float32 decay and state. Against
    the float32 recurrence on the same rounded inputs the outputs differ by
    bfloat16 rounding of the products' operands (2^-8 relative, a few of
    them): 3e-2 of the largest output."""
    q, k, v, g, beta = _inputs(200, 1.0, None, seed=2)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    o, state = gated_delta_rule(qb, kb, vb, g, beta)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    o_ref, _ = gated_delta_recurrence(qb, kb, vb, g, beta)
    assert float(jnp.abs(o.astype(jnp.float32) - o_ref).max()) \
        < 3e-2 * float(jnp.abs(o_ref).max())


def test_chunk_must_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_rule(*_inputs(16, 1.0, None), chunk=48)

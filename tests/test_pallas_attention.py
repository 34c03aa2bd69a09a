"""Flash-attention Pallas kernel vs dense reference (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.ops.attention import dense_attention, dot_product_attention
from pytorchvideo_accelerate_tpu.ops.pallas_attention import flash_attention


def _qkv(B=2, Nq=64, Nk=64, H=2, D=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, Nq, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Nk, H, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Nk, H, D)), dtype)
    return q, k, v


def test_matches_dense_single_block():
    q, k, v = _qkv()
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_matches_dense_multi_block():
    q, k, v = _qkv(Nq=128, Nk=256)
    got = flash_attention(q, k, v, block_q=32, block_k=64)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_ragged_lengths_padded_and_masked():
    # 100 and 177 are not multiples of any block size -> exercises padding+mask
    q, k, v = _qkv(Nq=100, Nk=177)
    got = flash_attention(q, k, v, block_q=32, block_k=64)
    want = dense_attention(q, k, v)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_bf16_in_bf16_out_f32_accumulate():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_router_pallas_backend():
    q, k, v = _qkv(B=1, Nq=32, Nk=32)
    got = dot_product_attention(q, k, v, backend="pallas")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_softmax_stability_large_logits():
    q, k, v = _qkv(B=1, Nq=32, Nk=96, D=16)
    q = q * 30.0  # large logits would overflow a naive softmax in f32 exp-space
    got = flash_attention(q, k, v, block_q=32, block_k=32)
    want = dense_attention(q, k, v)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_router_dense_backend_matches_reference():
    """backend='dense' routes to jax.nn.dot_product_attention — keep it
    pinned to the einsum numerics reference (scale + BNHD layout)."""
    q, k, v = _qkv(B=1, Nq=48, Nk=80, H=4, D=16)
    got = dot_product_attention(q, k, v, backend="dense")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_grad_matches_dense():
    """Backward kernels (custom VJP) vs autodiff through the dense reference."""
    import jax

    q, k, v = _qkv(B=1, Nq=64, Nk=96, H=2, D=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for got, want, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


def test_grad_ragged_lengths():
    import jax

    q, k, v = _qkv(B=1, Nq=50, Nk=77, H=2, D=16)
    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, block_q=32, block_k=32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        dense_attention(q, k, v) ** 2), argnums=(0, 1, 2)))(q, k, v)
    for got, want, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


def _old_flash(spec):
    """The custom VJP `flash_attention` ran before the forward moved out of
    it: the forward kernel in the rule, its `o` and `lse` as residuals."""
    import jax

    from pytorchvideo_accelerate_tpu.ops.pallas_attention import _Calls

    @jax.custom_vjp
    def flash(q, k, v):
        return _Calls(q, k, spec).forward(q, k, v)[0]

    def fwd(q, k, v):
        o, lse = _Calls(q, k, spec).forward(q, k, v)
        return o, (q, k, v, o, lse)

    def bwd(residuals, do):
        q, k, v, o, lse = residuals
        return _Calls(q, k, spec).backward(q, k, v, o, lse, do)

    flash.defvjp(fwd, bwd)
    return flash


def test_vit_output_and_gradient_unchanged_by_the_forward_outside_the_rule():
    """`flash_attention` (the ViT trunks, ragged lengths: padded and masked)
    against the custom VJP it had, on the same folded operands: the same
    output and gradients, bit for bit, and no name a remat policy could
    keep."""
    import jax

    from pytorchvideo_accelerate_tpu.ops import pallas_attention as pa

    q, k, v = _qkv(B=1, Nq=50, Nk=77, H=2, D=16)
    cot = jnp.asarray(np.random.default_rng(3).standard_normal(q.shape),
                      jnp.float32)
    spec = pa.Spec(1, 16 ** -0.5, 32, 32, pa.Mask(keys=77), True)

    def fold(x, block):
        x = x.transpose(0, 2, 1, 3).reshape(2, x.shape[1], 16)
        return pa._pad_rows(x, block)

    def old(q, k, v):
        out = _old_flash(spec)(fold(q, 32), fold(k, 32), fold(v, 32))
        return out[:, :50].reshape(1, 2, 50, 16).transpose(0, 2, 1, 3)

    def new(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=32)

    def both(fn):
        loss = lambda *a: jnp.sum(fn(*a) * cot)  # noqa: E731
        return (jax.jit(fn)(q, k, v),
                *jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v))

    for got, want in zip(both(new), both(old)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(new(*a)), (0, 1, 2)))
    assert " name[" not in str(grad(q, k, v))


@pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
def test_remat_unit_keeping_o_and_lse_runs_the_forward_kernel_once(window):
    """A `jax.checkpoint` unit around `causal_flash_attention`: under the
    policy that keeps `KEPT_NAMES` its gradient's program holds one forward
    kernel and one dq kernel; without a policy the unit's recomputation runs
    the forward again. The gradients agree bit for bit: the kept `o` and
    `lse` are the values the recomputation produced."""
    import jax

    from kept_attention import pallas_calls
    from pytorchvideo_accelerate_tpu.ops import pallas_attention as pa

    rng = np.random.default_rng(17)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 300, h, 128)), jnp.float32)
               for h in (7, 1, 1))
    w = jnp.asarray(rng.standard_normal((128, 128)) / 12, jnp.float32)
    cot = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def unit(q, k, v):  # a projection before the kernel, as a layer has
        return pa.causal_flash_attention(q @ w, k, v, 128 ** -0.5, window,
                                         True, 128, 128)

    def grads(policy):
        kept = jax.checkpoint(unit, policy=policy)
        return jax.grad(lambda *a: jnp.sum(kept(*a) * cot), (0, 1, 2))

    keep = jax.checkpoint_policies.save_only_these_names(*pa.KEPT_NAMES)
    for policy, forwards in ((keep, 1), (None, 2)):
        jaxpr = jax.make_jaxpr(grads(policy))(q, k, v).jaxpr
        assert pallas_calls(jaxpr, "pva_attn_fwd") == forwards
        assert pallas_calls(jaxpr, "pva_attn_dq") == 1
    for got, want in zip(jax.jit(grads(keep))(q, k, v),
                         jax.jit(grads(None))(q, k, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

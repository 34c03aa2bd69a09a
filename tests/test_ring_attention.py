"""Ring attention vs dense reference on the 8-fake-device CPU mesh (SURVEY §4
strategy: real compiled collectives, no TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.config import MeshConfig
from pytorchvideo_accelerate_tpu.ops.attention import dense_attention, dot_product_attention
from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh
from pytorchvideo_accelerate_tpu.parallel.ring_attention import make_ring_attention, ring_attention


def _qkv(B=2, N=32, H=4, D=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, N, H, D)), dtype)
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def cp_mesh(devices8):
    return make_mesh(MeshConfig(data=1, context=8), devices=devices8)


def test_matches_dense(cp_mesh):
    q, k, v = _qkv()
    ring = make_ring_attention(cp_mesh)
    with cp_mesh:
        got = jax.jit(ring)(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_matches_dense_bf16(cp_mesh):
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ring = make_ring_attention(cp_mesh)
    with cp_mesh:
        got = jax.jit(ring)(q, k, v)
    want = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_single_device_axis_degenerates_to_dense(devices8):
    mesh = make_mesh(MeshConfig(data=8, context=1), devices=devices8)
    q, k, v = _qkv(N=16)
    ring = make_ring_attention(mesh)
    with mesh:
        got = jax.jit(ring)(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_inside_shard_map_directly(cp_mesh):
    """The in-shard_map entry point used by shard_map-authored models."""
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(N=64)
    spec = P(None, "context", None, None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v),
        mesh=cp_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    with cp_mesh:
        got = jax.jit(f)(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_router_ring_backend_requires_axis():
    q, k, v = _qkv(B=1, N=8)
    with pytest.raises(ValueError):
        dot_product_attention(q, k, v, backend="ring")


def test_grad_flows(cp_mesh):
    """Ring attention is differentiable (pretraining path uses it under grad)."""
    q, k, v = _qkv(N=16, B=1)
    ring = make_ring_attention(cp_mesh)

    def loss(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    with cp_mesh:
        g = jax.jit(jax.grad(loss))(q, k, v)
    g_ref = jax.grad(dense_loss)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4, rtol=1e-4)


def test_mvit_with_ring_backend_under_jit(cp_mesh):
    """Context-parallel MViT from ordinary jit code: create_model(mesh=...)
    routes attention through a shard_map region over the context axis."""
    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model

    cfg = ModelConfig(name="mvit_b", num_classes=5, attention="ring",
                      dropout_rate=0.0)
    model = create_model(cfg, "fp32", mesh=cp_mesh)
    # tiny clip: 4 frames 32^2 -> token grid (2, 8, 8) = 128 tokens, /8 devices
    x = jnp.zeros((2, 4, 32, 32, 3), jnp.float32)
    variables = jax.jit(model.init)(jax.random.key(0), x)
    with cp_mesh:
        out = jax.jit(lambda v, x: model.apply(v, x))(variables, x)
    assert out.shape == (2, 5)
    assert np.isfinite(np.asarray(out)).all()


def test_mvit_ring_requires_mesh():
    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model

    with pytest.raises(ValueError, match="mesh"):
        create_model(ModelConfig(name="mvit_b", num_classes=5, attention="ring"))


def test_ragged_tokens_padded_and_masked(cp_mesh):
    """Sequence lengths that don't divide the context axis (MViT's pooled
    K/V grids — as small as 2 tokens on an 8-wide axis)."""
    for nq, nk in [(12, 2), (100, 36), (8, 64)]:
        q, k, v = _qkv(B=1, N=nq, H=2, D=8, seed=nq)
        k, v = k[:, :nk], v[:, :nk]
        ring = make_ring_attention(cp_mesh)
        with cp_mesh:
            got = jax.jit(ring)(q, k, v)
        want = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5, err_msg=f"nq={nq} nk={nk}")

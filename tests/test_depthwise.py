"""Depthwise-conv lowering equivalence (ops/depthwise.py): the "shift"
tap-decomposition must be a numerically equivalent drop-in for the XLA
grouped conv — same param tree, same function up to float rounding — for
every site that uses it (X3D conv_b / stem_t, MViT pool convs), in fp32
AND bf16 (the shift path accumulates in f32 like the conv path's MXU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from pytorchvideo_accelerate_tpu.ops.depthwise import (
    DepthwiseConv3D,
    depthwise_conv3d_shift,
)


@pytest.mark.parametrize("stride", [(1, 1, 1), (1, 2, 2), (2, 2, 2)])
@pytest.mark.parametrize("kernel", [(3, 3, 3), (5, 1, 1)])
def test_shift_matches_grouped_conv(stride, kernel):
    x = np.random.default_rng(0).standard_normal((2, 6, 8, 8, 6)).astype(np.float32)
    mc = DepthwiseConv3D(6, kernel, stride, impl="conv")
    ms = DepthwiseConv3D(6, kernel, stride, impl="shift")
    v = jax.jit(mc.init)(jax.random.key(0), jnp.asarray(x))
    # identical param trees: the impl is a lowering choice, not a model change
    assert jax.tree.structure(v) == jax.tree.structure(
        ms.init(jax.random.key(0), jnp.asarray(x)))
    a = jax.jit(mc.apply)(v, jnp.asarray(x))
    b = jax.jit(ms.apply)(v, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_shift_matches_conv_under_bf16():
    """bf16 compute: the shift path must hold its f32 accumulator (26
    chained bf16 adds would drift from the conv path's f32 MXU accumulate)."""
    x = np.random.default_rng(4).standard_normal((2, 4, 8, 8, 16)).astype(np.float32)
    mc = DepthwiseConv3D(16, (3, 3, 3), (1, 1, 1), impl="conv",
                         dtype=jnp.bfloat16)
    ms = DepthwiseConv3D(16, (3, 3, 3), (1, 1, 1), impl="shift",
                         dtype=jnp.bfloat16)
    v = jax.jit(mc.init)(jax.random.key(0), jnp.asarray(x))
    a = np.asarray(jax.jit(mc.apply)(v, jnp.asarray(x)), np.float32)
    b = np.asarray(jax.jit(ms.apply)(v, jnp.asarray(x)), np.float32)
    # both accumulate f32 then round once to bf16: worst case one ulp apart
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
    assert np.mean(a == b) > 0.95  # overwhelmingly identical after rounding


def test_shift_gradients_match():
    x = np.random.default_rng(1).standard_normal((1, 4, 6, 6, 4)).astype(np.float32)
    mc = DepthwiseConv3D(4, (3, 3, 3), (1, 2, 2), impl="conv")
    ms = DepthwiseConv3D(4, (3, 3, 3), (1, 2, 2), impl="shift")
    v = jax.jit(mc.init)(jax.random.key(0), jnp.asarray(x))

    def grad(model):
        return jax.jit(jax.grad(lambda variables: jnp.sum(
            model.apply(variables, jnp.asarray(x)) ** 2)))(v)

    ga = grad(mc)
    gb = grad(ms)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_x3d_model_equivalent_under_both_impls():
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    x = np.random.default_rng(2).standard_normal((1, 4, 16, 16, 3)).astype(np.float32)
    kw = dict(num_classes=5, depths=(1, 1), stem_features=8,
              stage_features=(8, 16), head_features=32, dropout_rate=0.0)
    mc = X3D(depthwise_impl="conv", **kw)
    ms = X3D(depthwise_impl="shift", **kw)
    v = jax.jit(mc.init)(jax.random.key(0), jnp.asarray(x))
    a = jax.jit(mc.apply)(v, jnp.asarray(x))
    b = jax.jit(ms.apply)(v, jnp.asarray(x))  # same variables: same param tree
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_mvit_model_equivalent_under_both_impls():
    from pytorchvideo_accelerate_tpu.models.mvit import MViT

    x = np.random.default_rng(3).standard_normal((1, 4, 16, 16, 3)).astype(np.float32)
    kw = dict(num_classes=5, depth=3, embed_dim=8, num_heads=1,
              stage_starts=(1,), initial_kv_stride=(1, 2, 2),
              drop_path_rate=0.0, dropout_rate=0.0)
    mc = MViT(depthwise_impl="conv", **kw)
    ms = MViT(depthwise_impl="shift", **kw)
    v = jax.jit(mc.init)(jax.random.key(0), jnp.asarray(x))
    a = jax.jit(mc.apply)(v, jnp.asarray(x))
    b = jax.jit(ms.apply)(v, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_asymmetric_padding_semantics():
    """Even kernels pad k//2 both sides like nn.Conv with explicit
    [(k//2, k//2)] — lock the geometry the models rely on."""
    x = np.ones((1, 4, 4, 4, 2), np.float32)
    k = np.ones((3, 3, 3, 1, 2), np.float32)
    out = depthwise_conv3d_shift(jnp.asarray(x), jnp.asarray(k), (1, 1, 1))
    assert out.shape == (1, 4, 4, 4, 2)
    # center voxel sees the full 27-tap sum
    assert float(out[0, 1, 1, 1, 0]) == 27.0
    # corner sees the 8 in-bounds taps
    assert float(out[0, 0, 0, 0, 0]) == 8.0


@pytest.mark.parametrize("kernel", [(3, 3, 3), (5, 1, 1), (1, 3, 3)])
def test_pallas_matches_grouped_conv_stride1(kernel):
    """The halo-tile Pallas lowering (interpret mode on CPU) must match
    the XLA grouped conv at stride 1 for every consumer kernel shape."""
    from pytorchvideo_accelerate_tpu.ops.pallas_depthwise import (
        pallas_depthwise3d_s1,
    )

    rng = np.random.default_rng(4)
    C = 10
    x = jnp.asarray(rng.standard_normal((2, 5, 9, 11, C)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((*kernel, 1, C)) * 0.2, jnp.float32)
    ref = lax.conv_general_dilated(
        x, k, (1, 1, 1), [(d // 2, d // 2) for d in kernel],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        feature_group_count=C)
    got = pallas_depthwise3d_s1(x, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pallas_gradients_match():
    from pytorchvideo_accelerate_tpu.ops.pallas_depthwise import (
        pallas_depthwise3d_s1,
    )

    rng = np.random.default_rng(5)
    C = 8
    x = jnp.asarray(rng.standard_normal((1, 4, 6, 6, C)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 3, 3, 1, C)) * 0.2, jnp.float32)

    def loss_p(x, k):
        return jnp.sum(pallas_depthwise3d_s1(x, k) ** 2)

    def loss_r(x, k):
        y = lax.conv_general_dilated(
            x, k, (1, 1, 1), [(1, 1)] * 3,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
            feature_group_count=C)
        return jnp.sum(y ** 2)

    gp = jax.jit(jax.grad(loss_p, (0, 1)))(x, k)
    gr = jax.jit(jax.grad(loss_r, (0, 1)))(x, k)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]),
                               rtol=1e-4, atol=1e-4)


def test_x3d_model_equivalent_under_pallas_impl():
    """impl='pallas' in a real model: stride-1 blocks ride the Pallas
    kernel, strided stage entries fall back to grouped conv — forward AND
    gradients equal the conv impl on the same variables."""
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    x = np.random.default_rng(6).standard_normal(
        (1, 4, 16, 16, 3)).astype(np.float32)
    kw = dict(num_classes=5, depths=(1, 1), stem_features=8,
              stage_features=(8, 16), head_features=32, dropout_rate=0.0)
    mc = X3D(depthwise_impl="conv", **kw)
    mp = X3D(depthwise_impl="pallas", **kw)
    v = jax.jit(mc.init)(jax.random.key(0), jnp.asarray(x))
    a = jax.jit(mc.apply)(v, jnp.asarray(x))
    b = jax.jit(mp.apply)(v, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)

    def grad(model):
        return jax.jit(jax.grad(lambda variables: jnp.sum(
            model.apply(variables, jnp.asarray(x)) ** 2)))(v)

    ga = grad(mc)
    gb = grad(mp)
    for p, q in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                   rtol=1e-4, atol=1e-4)


def test_pallas_even_kernel_falls_back_to_conv():
    """Even kernels use asymmetric-equivalent (k//2,k//2) conv padding the
    halo kernel doesn't implement — impl='pallas' must fall back to the
    grouped conv, not silently change function."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 5, 8, 8, 4)), jnp.float32)
    mc = DepthwiseConv3D(4, (2, 3, 3), impl="conv")
    mp = DepthwiseConv3D(4, (2, 3, 3), impl="pallas")
    v = jax.jit(mc.init)(jax.random.key(0), x)
    np.testing.assert_allclose(np.asarray(jax.jit(mc.apply)(v, x)),
                               np.asarray(jax.jit(mp.apply)(v, x)),
                               rtol=1e-5, atol=1e-5)

"""X3D + MViT model tests: shapes, param counts, multiscale geometry."""

import jax
import jax.numpy as jnp
import numpy as np

from pytorchvideo_accelerate_tpu.models.mvit import MViT
from pytorchvideo_accelerate_tpu.models.x3d import X3D, _round_width


def _count(params):
    return sum(np.prod(p.shape) for p in jax.tree.leaves(params))


def test_round_width():
    assert _round_width(24, 1.0) == 24
    assert _round_width(54, 0.0625) == 8  # SE bottleneck floor
    assert _round_width(192, 2.25) == 432  # conv5 width


def test_x3d_forward_and_params():
    model = X3D(num_classes=7, depths=(1, 1, 1, 1), dropout_rate=0.0)
    x = jnp.zeros((2, 4, 64, 64, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    out = jax.eval_shape(model.apply, variables, x)
    assert out.shape == (2, 7)


def test_x3d_s_param_count():
    """X3D-S trunk is ~3.8M params (paper Table 3: 3.76M for K400 head);
    sanity band with a 700-class head."""
    model = X3D(num_classes=700)
    x = jnp.zeros((1, 4, 64, 64, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    n = _count(variables["params"])
    assert 3e6 < n < 7e6, n


def test_x3d_l_registry_and_param_count():
    """X3D-L = depth-factor 5.0 trunk (~6.2M params, paper Table 3)."""
    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model

    model = create_model(ModelConfig(name="x3d_l", num_classes=400), "bf16")
    assert model.depths == (5, 10, 25, 15)
    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jnp.zeros((1, 4, 64, 64, 3)))
    n = _count(variables["params"])
    assert 5e6 < n < 8e6, n


def test_mvit_multiscale_geometry():
    """Grid halves spatially at each stage; dims 96->192->384->768."""
    model = MViT(num_classes=5, depth=16, drop_path_rate=0.0, dropout_rate=0.0)
    x = jnp.zeros((1, 8, 64, 64, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    out = jax.eval_shape(model.apply, variables, x)
    assert out.shape == (1, 5)
    p = variables["params"]
    # final block dim = 768 (96 * 2^3)
    assert p["norm"]["scale"].shape == (768,)
    assert p["block14"]["attn"]["qkv"]["kernel"].shape[-1] == 3 * 768
    # patch embed: 96 dims
    assert p["patch_embed"]["kernel"].shape[-1] == 96


def test_pool_heads_normalizes_per_head():
    """The MHPA pooling LayerNorm is torch-exact: one shared (head_dim,)
    parameter set, each head's channel slice normalized SEPARATELY (no
    cross-head statistics)."""
    from pytorchvideo_accelerate_tpu.models.mvit import PoolHeads

    head_dim, heads = 4, 2
    m = PoolHeads(channels=heads * head_dim, stride=(1, 2, 2),
                  head_dim=head_dim)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 2, 4, 4, heads * head_dim)),
        jnp.float32)
    variables = m.init(jax.random.key(0), x)
    assert variables["params"]["norm"]["scale"].shape == (head_dim,)
    out = m.apply(variables, x)

    # LN law: each head slice of the output has ~zero mean / unit var
    # (scale=1, bias=0 at init) — cross-head statistics would break this
    # whenever the heads' input scales differ
    y = np.asarray(out).reshape(1, 2, 2, 2, heads, head_dim)
    np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(y.var(-1), 1.0, atol=1e-3)


def test_mvit_b_param_count():
    """MViT-B/16 is ~36.6M (paper Table 2)."""
    model = MViT(num_classes=400)
    x = jnp.zeros((1, 8, 32, 32, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    n = _count(variables["params"])
    assert 30e6 < n < 45e6, n


def test_mvit_droppath_train_mode():
    model = MViT(num_classes=3, depth=4, stage_starts=(1, 2, 3),
                 drop_path_rate=0.5, dropout_rate=0.5)
    x = jnp.ones((2, 4, 32, 32, 3))
    variables = jax.jit(model.init)(jax.random.key(0), x)
    out = jax.jit(lambda v, x, rng: model.apply(
        v, x, train=True, rngs={"dropout": rng}))(variables, x, jax.random.key(1))
    assert out.shape == (2, 3)
    assert np.all(np.isfinite(np.asarray(out)))


class TestRemat:
    """remat=True must be numerics-neutral (it only trades recompute for
    activation HBM) for both transformer families."""

    def _parity(self, mk):
        import jax
        import jax.numpy as jnp

        x = np.random.default_rng(0).standard_normal(
            (2, 4, 32, 32, 3)).astype(np.float32)
        m0, m1 = mk(False), mk(True)
        v = jax.jit(m0.init)({"params": jax.random.key(0),
                              "mask": jax.random.key(1)}, jnp.asarray(x))

        def loss(m, p):
            out = m.apply({"params": p}, jnp.asarray(x),
                          rngs={"mask": jax.random.key(2)})
            return out["loss"] if isinstance(out, dict) else jnp.sum(out)

        l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss(m0, p)))(v["params"])
        l1, g1 = jax.jit(jax.value_and_grad(lambda p: loss(m1, p)))(v["params"])
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
            g0, g1,
        )

    def test_mvit_remat_parity(self):
        from pytorchvideo_accelerate_tpu.models.mvit import MViT

        self._parity(lambda r: MViT(
            num_classes=5, depth=2, embed_dim=16, num_heads=2,
            stage_starts=(1,), drop_path_rate=0.0, dropout_rate=0.0, remat=r))

    def test_videomae_remat_parity(self):
        from pytorchvideo_accelerate_tpu.models.videomae import (
            VideoMAEForPretraining,
        )

        self._parity(lambda r: VideoMAEForPretraining(
            dim=32, depth=2, num_heads=2, decoder_dim=16, decoder_depth=1,
            decoder_heads=2, remat=r))

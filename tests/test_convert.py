"""torch->Flax weight converter (SURVEY N12): mapping coverage, layout
transposes, npz round-trip, head-swap merge semantics.

The synthetic torch state_dicts are generated from our model param trees via
`torch_key_for` (the converter's inverse, acting as an independent spec of
pytorchvideo's `create_resnet`/`create_slowfast` naming), so the tests prove
key-mapping bijectivity and tensor-layout correctness over every parameter of
the real architectures.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.models.convert import (
    convert_state_dict,
    export_tensor,
    load_converted,
    load_pretrained,
    map_torch_key,
    save_converted,
    torch_key_for,
)
from pytorchvideo_accelerate_tpu.models.resnet3d import SlowR50
from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast


def _leaves(tree, prefix=()):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _fake_torch_sd(variables, model, seed=0):
    """Build a torch-style state_dict covering our full param tree."""
    rng = np.random.default_rng(seed)
    sd = {}
    for coll in ("params", "batch_stats"):
        for path, leaf in _leaves(variables[coll]):
            key = torch_key_for(coll, path, model)
            assert key is not None, f"no torch key for {coll}/{'/'.join(path)}"
            arr = rng.standard_normal(np.shape(leaf)).astype(np.float32)
            sd[key] = export_tensor(path, arr)
    return sd


@pytest.fixture(scope="module")
def slow_vars():
    model = SlowR50(num_classes=7, depths=(1, 1, 1, 1), stem_features=8)
    return jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 2, 32, 32, 3)))


@pytest.fixture(scope="module")
def slowfast_vars():
    model = SlowFast(num_classes=7, depths=(1, 1, 1, 1), stem_features=8)
    return jax.jit(model.init)(
        jax.random.key(0),
        (jnp.zeros((1, 2, 32, 32, 3)), jnp.zeros((1, 8, 32, 32, 3))),
    )


@pytest.fixture(scope="module")
def r2plus1d_vars():
    from pytorchvideo_accelerate_tpu.models.r2plus1d import R2Plus1D

    model = R2Plus1D(num_classes=7, depths=(1, 1), stem_features=8,
                     spatial_strides=(1, 2), temporal_strides=(1, 2))
    return jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 4, 32, 32, 3)))


@pytest.fixture(scope="module")
def csn_vars():
    from pytorchvideo_accelerate_tpu.models.csn import CSN

    model = CSN(num_classes=7, depths=(1, 1), stem_features=8,
                spatial_strides=(1, 2), temporal_strides=(1, 2))
    return jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 4, 32, 32, 3)))


@pytest.mark.parametrize("fixture,model", [
    ("slow_vars", "slow_r50"), ("slowfast_vars", "slowfast_r50"),
    ("r2plus1d_vars", "r2plus1d_r50"), ("csn_vars", "csn_r101"),
])
def test_full_tree_round_trip(fixture, model, request):
    """Every param/batch_stat of the architecture maps torch->flax with the
    right path and layout (values match after the transposes)."""
    variables = request.getfixturevalue(fixture)
    sd = _fake_torch_sd(variables, model)
    converted = convert_state_dict(sd, model)
    assert converted["skipped"] == []

    for coll in ("params", "batch_stats"):
        want = dict(_leaves(variables[coll]))
        got = dict(_leaves(converted[coll]))
        assert set(got) == set(want), (
            f"path mismatch: extra={set(got) - set(want)} "
            f"missing={set(want) - set(got)}"
        )
        for path in want:
            assert got[path].shape == tuple(want[path].shape), path
            # value check: converting the exported tensor returns the original
            key = torch_key_for(coll, path, model)
            np.testing.assert_array_equal(
                got[path],
                np.asarray(sd[key]).transpose(
                    (2, 3, 4, 1, 0) if np.asarray(sd[key]).ndim == 5
                    else (1, 0) if path[-1] == "kernel" else
                    tuple(range(np.asarray(sd[key]).ndim))
                ),
            )


def test_conv_layout_transpose():
    arr = np.arange(2 * 3 * 1 * 7 * 7).reshape(2, 3, 1, 7, 7).astype(np.float32)
    mapped = map_torch_key("blocks.0.conv.weight", "slow_r50")
    assert mapped == ("params", ("stem", "conv", "kernel"))
    from pytorchvideo_accelerate_tpu.models.convert import convert_tensor

    out = convert_tensor(mapped[1], arr)
    assert out.shape == (1, 7, 7, 3, 2)  # DHWIO
    np.testing.assert_array_equal(out[0, :, :, 1, 0], arr[0, 1, 0])


def test_bn_split_params_vs_stats():
    assert map_torch_key("blocks.1.res_blocks.0.branch2.norm_a.weight", "slow_r50") \
        == ("params", ("res2", "block0", "conv_a", "norm", "scale"))
    assert map_torch_key("blocks.1.res_blocks.0.branch2.norm_a.running_var", "slow_r50") \
        == ("batch_stats", ("res2", "block0", "conv_a", "norm", "var"))
    assert map_torch_key("blocks.0.norm.num_batches_tracked", "slow_r50") is None


def test_slowfast_fusion_and_pathways():
    assert map_torch_key(
        "blocks.0.multipathway_blocks.1.conv.weight", "slowfast_r50"
    ) == ("params", ("fast_stem", "conv", "kernel"))
    assert map_torch_key(
        "blocks.2.multipathway_blocks.0.res_blocks.3.branch2.conv_b.weight",
        "slowfast_r50",
    ) == ("params", ("slow_res3", "block3", "conv_b", "conv", "kernel"))
    assert map_torch_key(
        "blocks.1.multipathway_fusion.conv_fast_to_slow.weight", "slowfast_r50"
    ) == ("params", ("fuse_res2", "conv_f2s", "conv", "kernel"))
    assert map_torch_key(
        "blocks.6.proj.weight", "slowfast_r50"
    ) == ("params", ("head", "proj", "kernel"))


def test_npz_round_trip_and_merge(tmp_path, slow_vars):
    sd = _fake_torch_sd(slow_vars, "slow_r50")
    tree = convert_state_dict(sd, "slow_r50")
    path = str(tmp_path / "slow.npz")
    save_converted(tree, path)
    loaded = load_converted(path)
    for coll in ("params", "batch_stats"):
        for p, v in _leaves(tree[coll]):
            np.testing.assert_array_equal(dict(_leaves(loaded[coll]))[p], v)

    merged, report = load_pretrained(path, slow_vars)
    assert not report["kept"], report["kept"]  # same shapes -> all loaded
    got = dict(_leaves(merged["params"]))[("stem", "conv", "kernel")]
    want = dict(_leaves(tree["params"]))[("stem", "conv", "kernel")]
    np.testing.assert_allclose(np.asarray(got), want)


def test_head_swap_keeps_fresh_head(tmp_path, slow_vars):
    """Pretrain head (7 classes here) must NOT overwrite a different-size
    fine-tune head — reference head-swap semantics (run.py:109,117)."""
    sd = _fake_torch_sd(slow_vars, "slow_r50")
    tree = convert_state_dict(sd, "slow_r50")
    path = str(tmp_path / "slow.npz")
    save_converted(tree, path)

    target = jax.jit(SlowR50(num_classes=11, depths=(1, 1, 1, 1),
                             stem_features=8).init)(
        jax.random.key(1), jnp.zeros((1, 2, 32, 32, 3))
    )
    merged, report = load_pretrained(path, target)
    # the artifact HAS a head, at the pretrain label count -> "mismatched"
    # (distinct from "kept" = absent), the expected head-swap signal
    mism = set(report["mismatched"])
    assert mism == {"params/head/proj/kernel", "params/head/proj/bias"}, mism
    assert report["kept"] == []
    got_head = dict(_leaves(merged["params"]))[("head", "proj", "kernel")]
    np.testing.assert_array_equal(
        np.asarray(got_head),
        np.asarray(dict(_leaves(target["params"]))[("head", "proj", "kernel")]),
    )
    # backbone still loaded
    got_stem = dict(_leaves(merged["params"]))[("stem", "conv", "kernel")]
    np.testing.assert_allclose(
        np.asarray(got_stem), dict(_leaves(tree["params"]))[("stem", "conv", "kernel")]
    )


@pytest.fixture(scope="module")
def x3d_vars():
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    model = X3D(num_classes=7, depths=(1, 1, 1, 1))
    return jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 4, 32, 32, 3)))


def test_x3d_full_tree_round_trip(x3d_vars):
    """Every X3D param/batch_stat maps pytorchvideo-create_x3d-key -> flax
    path with the right layout (VERDICT r2 missing #3)."""
    sd = _fake_torch_sd(x3d_vars, "x3d_s")
    converted = convert_state_dict(sd, "x3d_s")
    assert converted["skipped"] == []
    for coll in ("params", "batch_stats"):
        want = dict(_leaves(x3d_vars[coll]))
        got = dict(_leaves(converted[coll]))
        assert set(got) == set(want), (
            f"extra={set(got) - set(want)} missing={set(want) - set(got)}"
        )
        for path in want:
            assert got[path].shape == tuple(want[path].shape), path


def test_x3d_key_spot_checks():
    assert map_torch_key("blocks.0.conv.conv_t.weight", "x3d_s") \
        == ("params", ("stem_xy", "kernel"))  # conv_t slot holds the SPATIAL conv
    assert map_torch_key("blocks.0.conv.conv_xy.weight", "x3d_s") \
        == ("params", ("stem_t", "kernel"))
    assert map_torch_key(
        "blocks.1.res_blocks.0.branch2.norm_b.1.fc1.weight", "x3d_s"
    ) == ("params", ("res2_block0", "se", "fc1", "kernel"))
    assert map_torch_key(
        "blocks.1.res_blocks.0.branch2.norm_b.0.running_mean", "x3d_s"
    ) == ("batch_stats", ("res2_block0", "norm_b", "mean"))
    # non-SE blocks carry a plain BN at norm_b
    assert map_torch_key(
        "blocks.1.res_blocks.1.branch2.norm_b.weight", "x3d_s"
    ) == ("params", ("res2_block1", "norm_b", "scale"))
    assert map_torch_key("blocks.5.pool.post_conv.weight", "x3d_s") \
        == ("params", ("head_conv", "kernel"))
    assert map_torch_key("blocks.5.proj.bias", "x3d_s") \
        == ("params", ("proj", "bias"))


def test_x3d_merge_head_swap(tmp_path, x3d_vars):
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    sd = _fake_torch_sd(x3d_vars, "x3d_s")
    tree = convert_state_dict(sd, "x3d_s")
    path = str(tmp_path / "x3d.npz")
    save_converted(tree, path)
    target = jax.jit(X3D(num_classes=11, depths=(1, 1, 1, 1)).init)(
        jax.random.key(1), jnp.zeros((1, 4, 32, 32, 3))
    )
    merged, report = load_pretrained(path, target)
    mism = set(report["mismatched"])
    assert mism == {"params/proj/kernel", "params/proj/bias"}, mism


class TestMViTConvert:
    """MViT conversion: pos-embed synthesis from separable tables, per-head
    pool tiling, qkv/proj/mlp mapping (VERDICT r2 missing #3; deviations
    documented at convert.py's MViT section)."""

    T, S = 4, 32  # input -> token grid (2, 8, 8) after stride (2,4,4)

    def _model(self, num_classes=7):
        from pytorchvideo_accelerate_tpu.models.mvit import MViT

        return MViT(num_classes=num_classes, depth=2, embed_dim=16,
                    num_heads=2, stage_starts=(), initial_kv_stride=(1, 2, 2),
                    drop_path_rate=0.0, dropout_rate=0.0)

    def _fake_sd(self, seed=0):
        """pytorchvideo-style state_dict for the tiny config above."""
        rng = np.random.default_rng(seed)
        dim, heads, head_dim = 16, 2, 8
        t, h, w = 2, 8, 8

        def randn(*shape):
            return rng.standard_normal(shape).astype(np.float32)

        sd = {
            "patch_embed.patch_model.weight": randn(dim, 3, 3, 7, 7),
            "patch_embed.patch_model.bias": randn(dim),
            "cls_positional_encoding.pos_embed_spatial": randn(1, h * w, dim),
            "cls_positional_encoding.pos_embed_temporal": randn(1, t, dim),
            "cls_positional_encoding.pos_embed_class": randn(1, 1, dim),
            "norm.weight": randn(dim),
            "norm.bias": randn(dim),
            "head.proj.weight": randn(7, dim),
            "head.proj.bias": randn(7),
        }
        for i in range(2):
            p = f"blocks.{i}"
            sd.update({
                f"{p}.norm1.weight": randn(dim),
                f"{p}.norm1.bias": randn(dim),
                f"{p}.attn.qkv.weight": randn(3 * dim, dim),
                f"{p}.attn.qkv.bias": randn(3 * dim),
                f"{p}.attn.pool_k.weight": randn(head_dim, 1, 3, 3, 3),
                f"{p}.attn.norm_k.weight": randn(head_dim),
                f"{p}.attn.norm_k.bias": randn(head_dim),
                f"{p}.attn.pool_v.weight": randn(head_dim, 1, 3, 3, 3),
                f"{p}.attn.norm_v.weight": randn(head_dim),
                f"{p}.attn.norm_v.bias": randn(head_dim),
                f"{p}.attn.proj.weight": randn(dim, dim),
                f"{p}.attn.proj.bias": randn(dim),
                f"{p}.norm2.weight": randn(dim),
                f"{p}.norm2.bias": randn(dim),
                f"{p}.mlp.fc1.weight": randn(4 * dim, dim),
                f"{p}.mlp.fc1.bias": randn(4 * dim),
                f"{p}.mlp.fc2.weight": randn(dim, 4 * dim),
                f"{p}.mlp.fc2.bias": randn(dim),
            })
        return sd

    def test_pos_embed_outer_sum(self):
        sd = self._fake_sd()
        tree = convert_state_dict(sd, "mvit_b")
        pos = dict(_leaves(tree["params"]))[("pos_embed",)]
        assert pos.shape == (1, 2, 8, 8, 16)
        s = sd["cls_positional_encoding.pos_embed_spatial"]
        t = sd["cls_positional_encoding.pos_embed_temporal"]
        np.testing.assert_allclose(
            pos[0, 1, 3, 5], t[0, 1] + s[0, 3 * 8 + 5], rtol=1e-6)

    def test_pool_tiling_is_exact(self):
        sd = self._fake_sd()
        tree = convert_state_dict(sd, "mvit_b")
        leaves = dict(_leaves(tree["params"]))
        k = leaves[("block0", "attn", "pool_k", "pool", "kernel")]
        assert k.shape == (3, 3, 3, 1, 16)  # tiled heads*head_dim
        src = sd["blocks.0.attn.pool_k.weight"]
        # channel h*head_dim+c carries the same kernel as channel c
        np.testing.assert_array_equal(k[..., 0, 8 + 3], src[3, 0])
        # pooling LN params stay (head_dim,) — PoolHeads applies them
        # per head, matching torch exactly (no tiling)
        ln = leaves[("block0", "attn", "pool_k", "norm", "scale")]
        np.testing.assert_array_equal(ln, sd["blocks.0.attn.norm_k.weight"])

    def test_stage_transition_block_fully_maps(self, tmp_path):
        """Every tensor of a stage-transition schedule loads — the flax MViT
        follows torch's dim-change-in-MLP block layout exactly (mvit.py)."""
        from pytorchvideo_accelerate_tpu.models.mvit import MViT

        rng = np.random.default_rng(3)

        def randn(*shape):
            return rng.standard_normal(shape).astype(np.float32)

        t, h, w = 2, 8, 8
        # block0: dim 16, heads 2, kv stride (1,2,2), dim_out 32 (MLP) + proj
        # block1: dim 32, heads 4, q stride (1,2,2), kv stride -> (1,1,1)
        sd = {
            "patch_embed.patch_model.weight": randn(16, 3, 3, 7, 7),
            "patch_embed.patch_model.bias": randn(16),
            "cls_positional_encoding.pos_embed_spatial": randn(1, h * w, 16),
            "cls_positional_encoding.pos_embed_temporal": randn(1, t, 16),
            "norm.weight": randn(32), "norm.bias": randn(32),
            "head.proj.weight": randn(7, 32), "head.proj.bias": randn(7),
            "blocks.0.norm1.weight": randn(16), "blocks.0.norm1.bias": randn(16),
            "blocks.0.attn.qkv.weight": randn(48, 16),
            "blocks.0.attn.qkv.bias": randn(48),
            "blocks.0.attn.pool_k.weight": randn(8, 1, 3, 3, 3),
            "blocks.0.attn.norm_k.weight": randn(8),
            "blocks.0.attn.norm_k.bias": randn(8),
            "blocks.0.attn.pool_v.weight": randn(8, 1, 3, 3, 3),
            "blocks.0.attn.norm_v.weight": randn(8),
            "blocks.0.attn.norm_v.bias": randn(8),
            "blocks.0.attn.proj.weight": randn(16, 16),
            "blocks.0.attn.proj.bias": randn(16),
            "blocks.0.norm2.weight": randn(16), "blocks.0.norm2.bias": randn(16),
            "blocks.0.mlp.fc1.weight": randn(64, 16),
            "blocks.0.mlp.fc1.bias": randn(64),
            "blocks.0.mlp.fc2.weight": randn(32, 64),
            "blocks.0.mlp.fc2.bias": randn(32),
            "blocks.0.proj.weight": randn(32, 16),
            "blocks.0.proj.bias": randn(32),
            "blocks.1.norm1.weight": randn(32), "blocks.1.norm1.bias": randn(32),
            "blocks.1.attn.qkv.weight": randn(96, 32),
            "blocks.1.attn.qkv.bias": randn(96),
            "blocks.1.attn.pool_q.weight": randn(8, 1, 3, 3, 3),
            "blocks.1.attn.norm_q.weight": randn(8),
            "blocks.1.attn.norm_q.bias": randn(8),
            # kv stride is (1,1,1) here but pytorchvideo still pools K/V
            # (the 3^3 pool_kvq_kernel applies to every block once adaptive
            # kv striding is configured) — real checkpoints carry these
            "blocks.1.attn.pool_k.weight": randn(8, 1, 3, 3, 3),
            "blocks.1.attn.norm_k.weight": randn(8),
            "blocks.1.attn.norm_k.bias": randn(8),
            "blocks.1.attn.pool_v.weight": randn(8, 1, 3, 3, 3),
            "blocks.1.attn.norm_v.weight": randn(8),
            "blocks.1.attn.norm_v.bias": randn(8),
            "blocks.1.attn.proj.weight": randn(32, 32),
            "blocks.1.attn.proj.bias": randn(32),
            "blocks.1.norm2.weight": randn(32), "blocks.1.norm2.bias": randn(32),
            "blocks.1.mlp.fc1.weight": randn(128, 32),
            "blocks.1.mlp.fc1.bias": randn(128),
            "blocks.1.mlp.fc2.weight": randn(32, 128),
            "blocks.1.mlp.fc2.bias": randn(32),
        }
        tree = convert_state_dict(sd, "mvit_b")
        assert tree["skipped"] == [], tree["skipped"]
        path = str(tmp_path / "mvit_trans.npz")
        save_converted(tree, path)
        model = MViT(num_classes=7, depth=2, embed_dim=16, num_heads=2,
                     stage_starts=(1,), initial_kv_stride=(1, 2, 2),
                     drop_path_rate=0.0, dropout_rate=0.0)
        variables = jax.jit(model.init)(jax.random.key(0),
                                        jnp.zeros((1, 4, 32, 32, 3)))
        merged, report = load_pretrained(path, variables)
        assert report["kept"] == [], report["kept"]

    def test_merge_into_model(self, tmp_path):
        sd = self._fake_sd()
        tree = convert_state_dict(sd, "mvit_b")
        assert tree["skipped"] == [], tree["skipped"]
        path = str(tmp_path / "mvit.npz")
        save_converted(tree, path)
        model = self._model()
        variables = jax.jit(model.init)(jax.random.key(0),
                                        jnp.zeros((1, self.T, self.S, self.S, 3)))
        merged, report = load_pretrained(path, variables)
        loaded = set(report["loaded"])
        for want in ("params/block0/attn/qkv/kernel",
                     "params/block0/attn/pool_k/pool/kernel",
                     "params/block1/mlp_fc2/kernel",
                     "params/pos_embed",
                     "params/patch_embed/kernel",
                     "params/head/kernel"):
            assert want in loaded, (want, sorted(report["kept"]))


def test_torch_pt_on_the_fly(tmp_path, slow_vars):
    torch = pytest.importorskip("torch")
    sd = {k: torch.from_numpy(np.asarray(v))
          for k, v in _fake_torch_sd(slow_vars, "slow_r50").items()}
    p = str(tmp_path / "hub.pth")
    torch.save(sd, p)
    merged, report = load_pretrained(p, slow_vars, model="slow_r50")
    assert not report["kept"]

"""Model zoo tests: shapes, param counts, head/backbone split, registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.config import ModelConfig
from pytorchvideo_accelerate_tpu.models import available_models, create_model
from pytorchvideo_accelerate_tpu.models.resnet3d import SlowR50
from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast


def _count(params):
    return sum(np.prod(p.shape) for p in jax.tree.leaves(params))


def test_slow_r50_forward_and_param_count():
    model = SlowR50(num_classes=10)
    x = jnp.zeros((2, 8, 64, 64, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    out = jax.eval_shape(model.apply, variables, x)
    assert out.shape == (2, 10)
    n = _count(variables["params"])
    # 3D ResNet-50 backbone is ~31.7M; head adds 2048*10. Sanity band.
    assert 25e6 < n < 40e6, n


def test_slow_r50_feature_widths():
    """res5 output must be 2048-wide: the reference head's in_features=2048
    (run.py:117) is an architectural invariant we must match for weight
    porting."""
    model = SlowR50(num_classes=4)
    x = jnp.zeros((1, 4, 32, 32, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    kernel = variables["params"]["head"]["proj"]["kernel"]
    assert kernel.shape == (2048, 4)


def test_slowfast_forward_and_head_width():
    model = SlowFast(num_classes=7)
    slow = jnp.zeros((2, 2, 64, 64, 3))
    fast = jnp.zeros((2, 8, 64, 64, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), (slow, fast))
    out = jax.eval_shape(model.apply, variables, (slow, fast))
    assert out.shape == (2, 7)
    # concat(2048 slow, 256 fast) = 2304 = reference in_features (run.py:109)
    kernel = variables["params"]["head"]["proj"]["kernel"]
    assert kernel.shape == (2304, 7)
    n = _count(variables["params"])
    assert 30e6 < n < 45e6, n  # slowfast_r50 ~34M


def test_slowfast_temporal_shapes_respect_alpha():
    """Fast T must be alpha x slow T; lateral fusion time-stride aligns them."""
    model = SlowFast(num_classes=3, alpha=4)
    slow = jnp.zeros((1, 2, 32, 32, 3))
    fast = jnp.zeros((1, 8, 32, 32, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), (slow, fast))
    out = jax.eval_shape(model.apply, variables, (slow, fast))
    assert out.shape == (1, 3)


def test_dropout_train_mode_needs_rng():
    model = SlowR50(num_classes=5, dropout_rate=0.5)
    x = jnp.ones((1, 4, 32, 32, 3))
    variables = jax.jit(model.init)(jax.random.key(0), x)
    out, updates = jax.jit(lambda v, x: model.apply(
        v,
        x,
        train=True,
        rngs={"dropout": jax.random.key(1)},
        mutable=["batch_stats"],
    ))(variables, x)
    assert out.shape == (1, 5)
    assert "batch_stats" in updates


def test_batch_stats_update_in_train_mode():
    model = SlowR50(num_classes=2)
    x = jnp.ones((2, 4, 32, 32, 3)) * 3.0
    variables = jax.jit(model.init)(jax.random.key(0), x)
    _, updates = jax.jit(lambda v, x: model.apply(
        v, x, train=True,
        rngs={"dropout": jax.random.key(1)}, mutable=["batch_stats"],
    ))(variables, x)
    before = variables["batch_stats"]["stem"]["norm"]["mean"]
    after = updates["batch_stats"]["stem"]["norm"]["mean"]
    assert not np.allclose(np.asarray(before), np.asarray(after))


def test_registry():
    assert "slow_r50" in available_models()
    assert "slowfast_r50" in available_models()
    model = create_model(ModelConfig(name="slow_r50", num_classes=4), "bf16")
    assert model.dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        create_model(ModelConfig(name="nope", num_classes=4))


def test_backbone_filter():
    assert SlowR50.backbone_param_filter(("res2", "block0"))
    assert not SlowR50.backbone_param_filter(("head", "proj"))
    assert SlowFast.backbone_param_filter(("fuse_stem",))
    assert not SlowFast.backbone_param_filter(("head",))


def test_bf16_compute_fp32_params():
    model = create_model(ModelConfig(name="slow_r50", num_classes=3), "bf16")
    x = jnp.zeros((1, 4, 32, 32, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    # params stay fp32; logits come out fp32 (head projects in fp32)
    assert variables["params"]["stem"]["conv"]["kernel"].dtype == jnp.float32
    out = jax.eval_shape(model.apply, variables, x)
    assert out.dtype == jnp.float32


def test_r2plus1d_forward_param_count_and_geometry():
    """Full-size R(2+1)D-50: published param count ~28.11M; strides must
    take 16x224^2 input to the 4x7x7 pre-pool grid the hub head's fixed
    AvgPool3d(4,7,7) implies (eval_shape only — no full-size forward)."""
    from pytorchvideo_accelerate_tpu.models.r2plus1d import R2Plus1D

    model = R2Plus1D(num_classes=400)
    spec = jax.ShapeDtypeStruct((1, 16, 224, 224, 3), jnp.float32)
    variables = jax.eval_shape(model.init, jax.random.key(0), spec)
    n = _count(variables["params"])
    assert 27e6 < n < 29.5e6, n

    # tiny real forward: all strides exercised, output well-formed
    tiny = R2Plus1D(num_classes=6, depths=(1, 1), stem_features=8,
                    spatial_strides=(2, 2), temporal_strides=(1, 2))
    x = jnp.zeros((2, 4, 32, 32, 3))
    v = jax.jit(tiny.init)(jax.random.key(0), x)
    out = jax.jit(tiny.apply)(v, x)
    assert out.shape == (2, 6)
    assert tiny.backbone_param_filter(("res2_block0", "conv_a"))
    assert not tiny.backbone_param_filter(("head", "proj"))


def test_r2plus1d_in_registry():
    cfg = ModelConfig(name="r2plus1d_r50", num_classes=11)
    model = create_model(cfg, mixed_precision="fp32")
    x = jnp.zeros((1, 4, 32, 32, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    assert variables["params"]["head"]["proj"]["kernel"].shape == (2048, 11)


def test_csn_r101_forward_param_count_and_geometry():
    """Full-size ir-CSN-101: published param count ~22.21M; strides take
    32x224^2 input to the 4x7x7 pre-pool grid (eval_shape only)."""
    from pytorchvideo_accelerate_tpu.models.csn import CSN

    model = CSN(num_classes=400)
    spec = jax.ShapeDtypeStruct((1, 32, 224, 224, 3), jnp.float32)
    variables = jax.eval_shape(model.init, jax.random.key(0), spec)
    n = _count(variables["params"])
    assert 21.3e6 < n < 23e6, n

    tiny = CSN(num_classes=6, depths=(1, 1), stem_features=8,
               spatial_strides=(1, 2), temporal_strides=(1, 2))
    x = jnp.zeros((2, 4, 32, 32, 3))
    v = jax.jit(tiny.init)(jax.random.key(0), x)
    out = jax.jit(tiny.apply)(v, x)
    assert out.shape == (2, 6)
    assert tiny.backbone_param_filter(("res2", "block0", "conv_b"))
    assert not tiny.backbone_param_filter(("head", "proj"))


def test_csn_in_registry_with_depthwise_knob():
    cfg = ModelConfig(name="csn_r101", num_classes=9, depthwise_impl="shift")
    model = create_model(cfg, mixed_precision="fp32")
    assert model.depthwise_impl == "shift"
    x = jnp.zeros((1, 4, 32, 32, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    assert variables["params"]["head"]["proj"]["kernel"].shape == (2048, 9)


def test_c2d_r50_param_count_and_detection():
    """c2d_r50 = create_resnet with zero temporal taps: published count
    ~24.33M; its state_dict must auto-detect as c2d (kernel-1 conv_a at
    the res4 entry where slow_r50 carries (3,1,1))."""
    from pytorchvideo_accelerate_tpu.models import convert

    model = SlowR50(num_classes=400, temporal_kernels=(1, 1, 1, 1))
    spec = jax.ShapeDtypeStruct((1, 8, 64, 64, 3), jnp.float32)
    variables = jax.eval_shape(model.init, jax.random.key(0), spec)
    n = _count(variables["params"])
    assert 23.5e6 < n < 25.5e6, n

    sys_path_probe = {
        "blocks.3.res_blocks.0.branch2.conv_a.weight":
            np.zeros((256, 512, 1, 1, 1), np.float32),
    }
    assert convert.detect_model(sys_path_probe) == "c2d_r50"
    slow_probe = {
        "blocks.3.res_blocks.0.branch2.conv_a.weight":
            np.zeros((256, 512, 3, 1, 1), np.float32),
    }
    assert convert.detect_model(slow_probe) == "slow_r50"

    # the builder's stage-1 temporal max-pool halves T after res2 (the hub
    # head's AvgPool3d(4,7,7) at 8-frame sampling needs 8->4); it is
    # parameterless, so weights are unaffected
    tiny = SlowR50(num_classes=3, depths=(1, 1), stem_features=8,
                   temporal_kernels=(1, 1), stage1_temporal_pool=True,
                   dropout_rate=0.0)
    x = jnp.zeros((1, 4, 32, 32, 3))
    v = jax.jit(tiny.init)(jax.random.key(0), x)
    out = jax.jit(tiny.apply)(v, x)
    assert out.shape == (1, 3)

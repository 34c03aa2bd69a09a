"""Golden-value transform tests (SURVEY §4.3): replaces the pytorchvideo unit
tests the reference silently leans on. Parity for resize is asserted against
the installed torch-cpu (same bilinear spec the reference stack uses)."""

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.data.transforms import (
    center_crop,
    div255,
    horizontal_flip,
    make_transform,
    normalize,
    pack_pathway,
    random_crop,
    short_side_scale,
    uniform_temporal_subsample,
)


def test_uniform_temporal_subsample_truncated_linspace():
    frames = np.arange(10)[:, None, None, None] * np.ones((10, 2, 2, 3))
    out = uniform_temporal_subsample(frames, 4)
    # linspace(0, 9, 4) = [0, 3, 6, 9] after truncation
    np.testing.assert_array_equal(out[:, 0, 0, 0], [0, 3, 6, 9])


def test_uniform_temporal_subsample_upsamples_by_repeat():
    frames = np.arange(3)[:, None, None, None] * np.ones((3, 1, 1, 1))
    out = uniform_temporal_subsample(frames, 6)
    # linspace(0,2,6) = [0,.4,.8,1.2,1.6,2] -> [0,0,0,1,1,2]
    np.testing.assert_array_equal(out[:, 0, 0, 0], [0, 0, 0, 1, 1, 2])


def test_div255_normalize_golden():
    frames = np.full((2, 2, 2, 3), 255, np.uint8)
    x = normalize(div255(frames), (0.45, 0.45, 0.45), (0.225, 0.225, 0.225))
    np.testing.assert_allclose(x, (1.0 - 0.45) / 0.225, rtol=1e-6)
    zeros = normalize(div255(np.zeros((1, 1, 1, 3), np.uint8)), (0.45,) * 3, (0.225,) * 3)
    np.testing.assert_allclose(zeros, -2.0, rtol=1e-6)


def test_short_side_scale_shapes_and_ar():
    frames = np.random.rand(2, 100, 200, 3).astype(np.float32)
    out = short_side_scale(frames, 50)
    assert out.shape == (2, 50, 100, 3)  # AR preserved
    tall = short_side_scale(np.zeros((1, 200, 100, 3), np.float32), 50)
    assert tall.shape == (1, 100, 50, 3)


def test_short_side_scale_matches_torch_bilinear():
    """cv2 INTER_LINEAR vs torch F.interpolate(bilinear, align_corners=False)
    — the spec the reference's ShortSideScale uses [external]."""
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F

    rng = np.random.default_rng(0)
    frames = rng.random((3, 64, 96, 3), dtype=np.float32)
    ours = short_side_scale(frames, 32)
    ref = F.interpolate(
        torch.from_numpy(frames).permute(0, 3, 1, 2),
        size=(32, 48), mode="bilinear", align_corners=False,
    ).permute(0, 2, 3, 1).numpy()
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, atol=2e-2)
    assert np.mean(np.abs(ours - ref)) < 1e-3


def test_crops():
    frames = np.arange(2 * 10 * 10 * 1, dtype=np.float32).reshape(2, 10, 10, 1)
    c = center_crop(frames, 4)
    assert c.shape == (2, 4, 4, 1)
    np.testing.assert_array_equal(c, frames[:, 3:7, 3:7])
    rng = np.random.default_rng(1)
    r = random_crop(frames, 4, rng)
    assert r.shape == (2, 4, 4, 1)


def test_horizontal_flip():
    frames = np.arange(8, dtype=np.float32).reshape(1, 1, 8, 1)
    flipped = horizontal_flip(frames, p=1.1, rng=np.random.default_rng(0))
    np.testing.assert_array_equal(flipped[0, 0, :, 0], frames[0, 0, ::-1, 0])
    same = horizontal_flip(frames, p=-0.1, rng=np.random.default_rng(0))
    np.testing.assert_array_equal(same, frames)


def test_pack_pathway_reference_semantics():
    """run.py:56-65: fast = all frames; slow = linspace(0, T-1, T//alpha)."""
    frames = np.arange(32)[:, None, None, None] * np.ones((32, 1, 1, 3))
    out = pack_pathway(frames, alpha=4)
    assert out["fast"].shape[0] == 32
    assert out["slow"].shape[0] == 8
    # linspace(0, 31, 8) truncated = [0, 4, 8, 13, 17, 22, 26, 31]
    np.testing.assert_array_equal(
        out["slow"][:, 0, 0, 0], np.linspace(0, 31, 8).astype(np.int64)
    )


def test_make_transform_train_pipeline_shapes():
    rng = np.random.default_rng(0)
    frames = (np.random.rand(64, 120, 160, 3) * 255).astype(np.uint8)
    tf = make_transform(num_frames=32, training=True, is_slowfast=True,
                        slowfast_alpha=4, crop_size=64,
                        min_short_side_scale=64, max_short_side_scale=80)
    out = tf(frames, rng)
    assert set(out) == {"slow", "fast"}
    assert out["fast"].shape == (32, 64, 64, 3)
    assert out["slow"].shape == (8, 64, 64, 3)
    assert out["fast"].dtype == np.float32


def test_make_transform_val_deterministic():
    frames = (np.random.rand(16, 120, 160, 3) * 255).astype(np.uint8)
    tf = make_transform(num_frames=8, training=False, crop_size=64,
                        min_short_side_scale=64)
    a = tf(frames)
    b = tf(frames)
    np.testing.assert_array_equal(a["video"], b["video"])
    assert a["video"].shape == (8, 64, 64, 3)


def test_train_transform_requires_rng():
    tf = make_transform(training=True)
    with pytest.raises(ValueError):
        tf(np.zeros((8, 64, 64, 3), np.uint8), None)


def test_bf16_output_matches_fp32_cast():
    """output_dtype="bfloat16" must equal the fp32 pipeline cast at the end
    (the model casts on device anyway — host cast only moves the rounding)."""
    import ml_dtypes

    from pytorchvideo_accelerate_tpu.data.transforms import make_transform

    rng_frames = np.random.default_rng(0)
    frames = (rng_frames.random((12, 48, 64, 3)) * 255).astype(np.uint8)
    kw = dict(num_frames=4, training=True, crop_size=32,
              min_short_side_scale=36, max_short_side_scale=40,
              is_slowfast=True)
    a = make_transform(**kw)(frames, np.random.default_rng(7))
    b = make_transform(output_dtype="bfloat16", **kw)(
        frames, np.random.default_rng(7))
    for k in a:
        assert b[k].dtype == np.dtype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(
            a[k].astype(ml_dtypes.bfloat16), b[k])


def test_normalize_u8_matches_unfused_pair():
    """The fused hot path must equal normalize(div255(x)) within float
    rounding for uint8 input — it's the same math refactored."""
    from pytorchvideo_accelerate_tpu.data.transforms import normalize_u8

    rng = np.random.default_rng(0)
    frames = rng.integers(0, 256, (4, 24, 32, 3), dtype=np.uint8)
    mean, std = (0.45, 0.43, 0.41), (0.225, 0.24, 0.26)
    a = normalize(div255(frames), mean, std)
    b = normalize_u8(frames, mean, std)
    assert b.dtype == np.float32
    np.testing.assert_allclose(b, a, atol=2e-6)


def test_u8_through_path_matches_host_normalize():
    """output_dtype='uint8' defers normalization to the device step; the
    eval pipeline (deterministic) must produce the same final tensor as
    the fp32 host path once the affine is applied — bilinear resize
    commutes with the normalize affine up to uint8 rounding (±0.5 LSB)."""
    rng = np.random.default_rng(0)
    frames = rng.integers(0, 255, (12, 48, 64, 3), np.uint8)
    kw = dict(num_frames=8, training=False, crop_size=32,
              min_short_side_scale=40, max_short_side_scale=40)
    f32 = make_transform(output_dtype="float32", **kw)
    u8 = make_transform(output_dtype="uint8", **kw)
    assert f32.device_normalize is None
    mean, std = u8.device_normalize
    a = f32(frames)["video"]
    raw = u8(frames)["video"]
    assert raw.dtype == np.uint8
    b = (raw.astype(np.float32) / 255.0 - np.float32(mean)) / np.float32(std)
    # uint8 resize rounds to integers: bound the delta by ~1 LSB in
    # normalized units (1/255/std ≈ 0.0174) — tight enough to catch any
    # ordering or scaling mistake, loose enough for the rounding
    np.testing.assert_allclose(a, b, atol=1.5 / 255.0 / 0.225)


def test_u8_through_training_keeps_uint8_and_geometry():
    rng = np.random.default_rng(1)
    frames = rng.integers(0, 255, (16, 48, 64, 3), np.uint8)
    tf = make_transform(num_frames=4, training=True, is_slowfast=True,
                        slowfast_alpha=2, crop_size=32,
                        min_short_side_scale=36, max_short_side_scale=44,
                        output_dtype="uint8")
    out = tf(frames, np.random.default_rng(0))
    assert out["slow"].dtype == np.uint8 and out["fast"].dtype == np.uint8
    assert out["fast"].shape == (4, 32, 32, 3)
    assert out["slow"].shape == (2, 32, 32, 3)
    assert tf.device_normalize is not None


def test_device_normalize_batch_matches_host_values():
    import jax
    import jax.numpy as jnp

    from pytorchvideo_accelerate_tpu.trainer.steps import (
        device_normalize_batch,
    )

    rng = np.random.default_rng(2)
    clip = rng.integers(0, 255, (2, 4, 8, 8, 3), np.uint8)
    mean, std = (0.45, 0.45, 0.45), (0.225, 0.225, 0.225)
    batch = {"video": jnp.asarray(clip), "label": jnp.zeros(2, jnp.int32)}
    out = device_normalize_batch(batch, (mean, std))
    want = (clip.astype(np.float32) / 255.0 - 0.45) / 0.225
    np.testing.assert_allclose(np.asarray(out["video"]), want, rtol=1e-6,
                               atol=1e-6)
    assert out["label"] is batch["label"]
    # no-op contracts: norm=None, and float inputs pass through untouched
    assert device_normalize_batch(batch, None) is batch
    fbatch = {"video": jnp.ones((1, 2, 2, 2, 3), jnp.float32)}
    np.testing.assert_array_equal(
        np.asarray(device_normalize_batch(fbatch, (mean, std))["video"]),
        np.ones((1, 2, 2, 2, 3), np.float32))


def test_resize_on_calling_thread_keeps_the_pixels():
    """cv2's own thread pool is a matter of speed alone: with it off (what a
    multi-worker `ClipLoader` sets, its decode pool being the parallelism)
    the scaled frames are the same bytes."""
    import cv2

    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader,
        SyntheticClipSource,
    )
    from pytorchvideo_accelerate_tpu.data.transforms import make_transform

    frames = np.random.default_rng(3).random((5, 72, 96, 3)).astype(np.float32)
    before = cv2.getNumThreads()
    try:
        cv2.setNumThreads(4)
        threaded = short_side_scale(frames, 205)
        tf = make_transform(num_frames=2, crop_size=16, min_short_side_scale=16)
        ClipLoader(SyntheticClipSource(tf, num_videos=4), 2,
                   num_workers=1).close()
        assert cv2.getNumThreads() == 4  # one worker: cv2's pool stays
        ClipLoader(SyntheticClipSource(tf, num_videos=4), 2,
                   num_workers=2).close()
        assert cv2.getNumThreads() == 1
        assert short_side_scale(frames, 205).tobytes() == threaded.tobytes()
    finally:
        cv2.setNumThreads(before)

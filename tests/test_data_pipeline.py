"""Data pipeline tests: manifest scan, decode, samplers, loader sharding,
mid-epoch resume, padded tails — incl. a real-decode 4-video fixture
(BASELINE config 1's "4-video Kinetics subset" equivalent, SURVEY §4.4)."""

import os

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.data.decode import decode_span, probe
from pytorchvideo_accelerate_tpu.data.manifest import scan_directory
from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.data.pipeline import (
    ClipLoader,
    LoaderState,
    SyntheticClipSource,
    VideoClipSource,
    assemble_batch,
)
from pytorchvideo_accelerate_tpu.data.samplers import random_clip, uniform_clips
from pytorchvideo_accelerate_tpu.data.transforms import make_transform


@pytest.fixture(scope="module")
def video_dir(tmp_path_factory):
    """dir-per-class layout: 2 classes x 2 videos, 2s @ 10fps, 64x48."""
    import cv2

    root = tmp_path_factory.mktemp("kinetics_subset")
    for split in ["train", "val"]:
        for cls, base in [("archery", 40), ("bowling", 160)]:
            cdir = root / split / cls
            cdir.mkdir(parents=True)
            for v in range(2):
                path = str(cdir / f"{cls}_{v}.avi")
                w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"MJPG"), 10.0, (64, 48))
                assert w.isOpened()
                rng = np.random.default_rng(hash((cls, v)) % 2**32)
                for i in range(20):
                    frame = (rng.random((48, 64, 3)) * 40 + base).astype(np.uint8)
                    w.write(frame)
                w.release()
    return str(root)


def test_manifest_scan(video_dir):
    m = scan_directory(os.path.join(video_dir, "train"))
    assert m.num_classes == 2
    assert m.class_names == ["archery", "bowling"]  # sorted = label order
    assert m.num_videos == 4
    labels = sorted(e.label for e in m.entries)
    assert labels == [0, 0, 1, 1]


def test_manifest_missing_dir():
    with pytest.raises(FileNotFoundError):
        scan_directory("/nonexistent/dir")


def test_probe_and_decode(video_dir):
    m = scan_directory(os.path.join(video_dir, "train"))
    meta = probe(m.entries[0].path)
    assert meta.fps == 10.0
    assert meta.frame_count == 20
    assert abs(meta.duration - 2.0) < 1e-6
    frames = decode_span(m.entries[0].path, 0.5, 1.5)
    assert frames.shape == (10, 48, 64, 3)
    assert frames.dtype == np.uint8


def test_decode_short_video_clamps(video_dir):
    m = scan_directory(os.path.join(video_dir, "train"))
    frames = decode_span(m.entries[0].path, 1.5, 5.0)  # beyond end
    assert 1 <= frames.shape[0] <= 6


def test_samplers():
    rng = np.random.default_rng(0)
    spans = [random_clip(10.0, 2.0, rng) for _ in range(50)]
    assert all(0.0 <= s.start <= 8.0 and abs((s.end - s.start) - 2.0) < 1e-9 for s in spans)
    assert len({round(s.start, 3) for s in spans}) > 10  # actually random

    u = uniform_clips(10.0, 2.0, 1)
    assert u[0].start == 4.0  # centered single clip
    u3 = uniform_clips(10.0, 2.0, 3)
    assert [s.start for s in u3] == [0.0, 4.0, 8.0]
    short = uniform_clips(1.0, 2.0, 1)
    assert short[0].start == 0.0 and short[0].end == 1.0


def test_video_source_end_to_end(video_dir):
    m = scan_directory(os.path.join(video_dir, "train"))
    tf = make_transform(num_frames=4, training=True, crop_size=32,
                        min_short_side_scale=32, max_short_side_scale=40)
    src = VideoClipSource(m, tf, clip_duration=1.0, training=True, seed=7)
    s = src.get(0, epoch=0)
    assert s["video"].shape == (4, 32, 32, 3)
    assert s["label"] == 0
    # deterministic per (epoch, index); distinct across epochs
    s2 = src.get(0, epoch=0)
    np.testing.assert_array_equal(s["video"], s2["video"])
    s3 = src.get(0, epoch=1)
    assert not np.array_equal(s["video"], s3["video"])


def test_synthetic_source_label_coded():
    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    src = SyntheticClipSource(tf, num_videos=8, num_classes=4)
    s0, s5 = src.get(0, 0), src.get(5, 0)
    assert s0["label"] == 0 and s5["label"] == 1
    # brightness coding: higher label -> higher mean
    assert s5["video"].mean() > s0["video"].mean()


def _loader(n_videos=16, bs=8, **kw):
    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    src = SyntheticClipSource(tf, num_videos=n_videos, num_classes=4)
    return ClipLoader(src, global_batch_size=bs, num_workers=2, **kw)


def test_loader_basic_epoch():
    loader = _loader(n_videos=16, bs=8)
    batches = list(loader.epoch(0))
    assert len(batches) == 2 == loader.batches_per_epoch()
    assert batches[0]["video"].shape == (8, 4, 32, 32, 3)
    assert batches[0]["label"].shape == (8,)
    assert "mask" not in batches[0]
    loader.close()


def test_loader_accum_shaping():
    loader = _loader(n_videos=16, bs=4, accum_steps=2)
    batches = list(loader.epoch(0))
    assert len(batches) == 2
    assert batches[0]["video"].shape == (2, 4, 4, 32, 32, 3)
    loader.close()


def test_loader_padded_tail_mask():
    loader = _loader(n_videos=10, bs=8, drop_last=False)
    batches = list(loader.epoch(0))
    assert len(batches) == 2
    assert "mask" not in batches[0]
    assert batches[1]["mask"].tolist() == [1, 1, 0, 0, 0, 0, 0, 0]
    loader.close()


def test_loader_host_sharding_partitions():
    """Two fake hosts see disjoint, covering index sets (DistributedSampler
    semantics without padding duplicates)."""
    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    src = SyntheticClipSource(tf, num_videos=16, num_classes=4)
    l0 = ClipLoader(src, global_batch_size=8, process_index=0, process_count=2,
                    num_workers=1, shuffle=True, seed=3)
    l1 = ClipLoader(src, global_batch_size=8, process_index=1, process_count=2,
                    num_workers=1, shuffle=True, seed=3)
    i0 = l0._epoch_indices(0)
    i1 = l1._epoch_indices(0)
    assert len(i0) == len(i1) == 8
    assert set(i0) | set(i1) == set(range(16))
    assert set(i0).isdisjoint(i1)
    # local batch = global/process_count
    b0 = next(iter(l0.epoch(0)))
    assert b0["video"].shape[0] == 4
    l0.close(); l1.close()


def test_loader_shuffle_changes_across_epochs():
    loader = _loader(n_videos=16, bs=8, shuffle=True)
    i0 = loader._epoch_indices(0)
    i1 = loader._epoch_indices(1)
    assert not np.array_equal(i0, i1)
    assert sorted(i0) == sorted(i1) == list(range(16))
    loader.close()


def test_loader_mid_epoch_resume():
    """Restore {epoch, position} -> identical remaining batches (O(1)
    fast-forward replacing the reference's skip-loop, run.py:246-249)."""
    loader = _loader(n_videos=32, bs=8, shuffle=True)
    it = loader.epoch(0)
    first = next(it)
    saved = loader.state.to_dict()
    rest_a = [b["label"] for b in it]

    loader2 = _loader(n_videos=32, bs=8, shuffle=True)
    loader2.state = LoaderState.from_dict(saved)
    rest_b = [b["label"] for b in loader2.epoch(0)]
    assert len(rest_a) == len(rest_b) == 3
    for a, b in zip(rest_a, rest_b):
        np.testing.assert_array_equal(a, b)
    # epoch rolls over after exhaustion
    assert loader2.state.epoch == 1 and loader2.state.position == 0
    loader.close(); loader2.close()

def test_epoch_items_yields_state_without_mutating():
    """The device-prefetch contract: epoch_items never touches self.state,
    pairs every batch with its post-consumption position, and ends with a
    (None, rollover) marker."""
    loader = _loader(n_videos=16, bs=8)
    items = list(loader.epoch_items(0))
    assert loader.state == LoaderState(epoch=0, position=0)  # untouched
    assert [s.to_dict() for _, s in items] == [
        {"epoch": 0, "position": 1}, {"epoch": 0, "position": 2},
        {"epoch": 1, "position": 0}]
    assert items[-1][0] is None  # rollover marker carries no batch
    # and epoch() (the state-assigning wrapper) yields the same batches
    loader2 = _loader(n_videos=16, bs=8)
    batches = list(loader2.epoch(0))
    assert len(batches) == len(items) - 1
    for (a, _), b in zip(items[:-1], batches):
        np.testing.assert_array_equal(a["label"], b["label"])
    assert loader2.state == LoaderState(epoch=1, position=0)
    loader.close(); loader2.close()


def test_early_break_cancels_pending_decode_work():
    """Closing an epoch generator early (limit_train_batches) must cancel
    queued fetch_batch futures — not leave them decoding whole batches into
    a dead queue."""
    import threading

    calls = []
    gate = threading.Event()

    class SlowSource(SyntheticClipSource):
        def get(self, index, epoch):
            calls.append(index)
            gate.wait(0.05)  # slow enough that prefetch stays queued
            return super().get(index, epoch)

    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    src = SlowSource(tf, num_videos=64, num_classes=4)
    loader = ClipLoader(src, global_batch_size=8, num_workers=1,
                        prefetch_batches=4)
    it = loader.epoch(0)
    next(it)
    it.close()  # GeneratorExit -> cancel queued futures
    gate.set()  # release any in-flight get() immediately
    import time as _t
    _t.sleep(0.2)  # let the (at most one) in-flight fetch_batch drain
    # running fetch_batches may finish their batch; the queued ones must
    # never start: well under the 64 gets a full epoch would issue
    seen = len(calls)
    _t.sleep(0.3)
    assert len(calls) == seen, "decode work kept flowing after close"
    assert len(calls) <= 24  # 1 consumed + <=2 in-flight batches of 8
    loader.close()


def test_loader_eval_from_start_after_early_break():
    """Eval contract (VERDICT r3 weak #6): an early-broken pass (e.g.
    limit_val_batches) leaves a mid-epoch position; the next eval pass over
    the SAME epoch number must start from batch 0, not silently resume."""
    loader = _loader(n_videos=32, bs=8)
    it = loader.epoch(0)
    next(it)  # early break after one of four batches
    del it
    assert loader.state.position == 1
    full = list(loader.epoch(0, from_start=True))
    assert len(full) == 4  # all batches, not the remaining 3
    # and the non-from_start call keeps its resume semantics
    loader.state = LoaderState(epoch=0, position=1)
    assert len(list(loader.epoch(0))) == 3
    loader.close()


# --- rows written in place (thread path) -------------------------------------


class _OldSignatureSource(SyntheticClipSource):
    """A subclass written before `get` took `out`: returns its own arrays."""

    def get(self, index, epoch):
        return super().get(index, epoch)


def _row_counts():
    reg = obs.get_registry()
    return (reg.counter("pva_loader_rows_in_place").total(),
            reg.counter("pva_loader_rows_copied").total())


# (id, transform options, source class, source options, loader options, videos)
_IN_PLACE_CASES = [
    ("video-bf16-train", dict(training=True, output_dtype="bfloat16"),
     SyntheticClipSource, {}, {}, 16),
    ("video-f32-train", dict(training=True, output_dtype="float32"),
     SyntheticClipSource, {}, {}, 16),
    ("video-u8-train", dict(training=True, output_dtype="uint8"),
     SyntheticClipSource, {}, {}, 16),
    ("slowfast-bf16-train",
     dict(training=True, is_slowfast=True, output_dtype="bfloat16"),
     SyntheticClipSource, {}, {}, 16),
    ("slowfast-f32-train",
     dict(training=True, is_slowfast=True, output_dtype="float32"),
     SyntheticClipSource, {}, {}, 16),
    ("slowfast-u8-train",
     dict(training=True, is_slowfast=True, output_dtype="uint8"),
     SyntheticClipSource, {}, {}, 16),
    ("video-bf16-val-padded-tail", dict(output_dtype="bfloat16"),
     SyntheticClipSource, {}, dict(drop_last=False), 11),
    ("slowfast-f32-val-padded-tail",
     dict(is_slowfast=True, output_dtype="float32"),
     SyntheticClipSource, {}, dict(drop_last=False), 11),
    ("video-f32-accum2", dict(training=True, output_dtype="float32"),
     SyntheticClipSource, {}, dict(accum_steps=2), 16),
    ("slowfast-bf16-accum2-padded-tail",
     dict(is_slowfast=True, output_dtype="bfloat16"),
     SyntheticClipSource, {}, dict(accum_steps=2, drop_last=False), 13),
    ("video-bf16-val-3clips-padded-tail", dict(output_dtype="bfloat16"),
     SyntheticClipSource, dict(num_clips=3), dict(drop_last=False), 10),
    ("slowfast-u8-val-3clips", dict(is_slowfast=True, output_dtype="uint8"),
     SyntheticClipSource, dict(num_clips=3), {}, 8),
    ("video-f32-val-2clips-3crops",
     dict(output_dtype="float32", num_spatial_crops=3),
     SyntheticClipSource, dict(num_clips=2), dict(drop_last=False), 6),
    ("old-signature-video-bf16", dict(training=True, output_dtype="bfloat16"),
     _OldSignatureSource, {}, {}, 16),
    ("old-signature-slowfast-f32-padded-tail",
     dict(is_slowfast=True, output_dtype="float32"),
     _OldSignatureSource, dict(num_clips=3), dict(drop_last=False), 7),
]


@pytest.mark.parametrize(
    "tf_kw,source_cls,source_kw,loader_kw,n_videos",
    [c[1:] for c in _IN_PLACE_CASES], ids=[c[0] for c in _IN_PLACE_CASES])
def test_thread_path_rows_are_the_stack_byte_for_byte(
        tf_kw, source_cls, source_kw, loader_kw, n_videos):
    """The thread path writes each sample into its row of the batch; what it
    yields is, key by key and byte by byte, `assemble_batch` of the samples
    the source returns on its own — and the two counters say who wrote the
    rows (the source, or the worker copying in a returned sample)."""
    tf = make_transform(num_frames=8, crop_size=32, min_short_side_scale=36,
                        max_short_side_scale=44, slowfast_alpha=4, **tf_kw)
    src = source_cls(tf, num_videos=n_videos, num_classes=4, seed=5,
                     **source_kw)
    loader = ClipLoader(src, global_batch_size=4, num_workers=3, shuffle=True,
                        seed=9, **loader_kw)
    spy = loader.samples_per_yield
    before = _row_counts()
    rows = 0
    for epoch in (0, 1):  # the second epoch reuses the rows' learnt shapes
        indices = loader._epoch_indices(epoch)
        got = list(loader.epoch(epoch))
        assert len(got) == loader.batches_per_epoch()
        for b, batch in enumerate(got):
            chunk = indices[b * spy:(b + 1) * spy]
            want = assemble_batch(
                [src.get(int(i), epoch) for i in chunk], spy,
                accum_steps=loader.accum_steps,
                local_batch_size=loader.local_batch_size)
            rows += len(chunk)
            assert list(batch) == list(want)
            for k in want:
                assert batch[k].dtype == want[k].dtype, k
                assert batch[k].shape == want[k].shape, k
                assert batch[k].tobytes() == want[k].tobytes(), k
    in_place, copied = (a - b for a, b in zip(_row_counts(), before))
    if source_cls is _OldSignatureSource:
        assert (in_place, copied) == (0, rows)
    else:  # all but the loader's first sample ever, which told the shapes
        assert (in_place, copied) == (rows - 1, 1)
    loader.close()


def test_thread_path_real_videos_with_a_substituted_decode(video_dir, tmp_path):
    """`VideoClipSource` through the rows: a corrupt file's substitute is
    written into the same row, and the batch is still the stack."""
    import shutil

    root = tmp_path / "train"
    shutil.copytree(os.path.join(video_dir, "train"), root)
    (root / "archery" / "archery_9.avi").write_bytes(b"not a video")
    manifest = scan_directory(str(root))
    tf = make_transform(num_frames=4, training=True, crop_size=32,
                        min_short_side_scale=36, max_short_side_scale=40,
                        output_dtype="bfloat16")

    def source():
        return VideoClipSource(manifest, tf, clip_duration=1.0, training=True,
                               seed=3, retry_base_delay_s=0.0)

    loader = ClipLoader(source(), global_batch_size=5, num_workers=2,
                        drop_last=False)
    (batch,) = list(loader.epoch(0))
    assert loader.source._failed  # the corrupt file was met and replaced
    plain = source()
    want = assemble_batch([plain.get(i, 0) for i in range(5)], 5)
    for k in want:
        assert batch[k].tobytes() == want[k].tobytes(), k
    loader.close()


def test_a_sample_that_does_not_fit_the_rows_is_refused():
    class Ragged(SyntheticClipSource):
        def get(self, index, epoch):
            sample = super().get(index, epoch)
            if index == 5:
                sample["video"] = sample["video"][:, :16]
            return sample

    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    loader = ClipLoader(Ragged(tf, num_videos=8, num_classes=4),
                        global_batch_size=4, num_workers=2)
    it = loader.epoch(0)
    next(it)
    with pytest.raises(ValueError, match="shape"):
        next(it)
    loader.close()


def test_no_barrier_between_batches_and_bounded_run_ahead():
    """While a batch's last sample is unfinished the pool already decodes
    the next batch's samples (no per-batch barrier), never more than
    `prefetch_batches` batches ahead of the one being yielded, and an early
    close still cancels what is queued."""
    import threading
    import time as _t

    started, gate = [], threading.Event()

    class Gated(SyntheticClipSource):
        def get(self, index, epoch, out=None):
            started.append(index)
            _t.sleep(0.05)
            if index == 3:  # batch 0's last sample
                gate.wait(10.0)
            return super().get(index, epoch, out=out)

    def wait_for(cond, timeout=10.0):
        deadline = _t.time() + timeout
        while not cond() and _t.time() < deadline:
            _t.sleep(0.005)
        return cond()

    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    loader = ClipLoader(Gated(tf, num_videos=64, num_classes=4),
                        global_batch_size=4, num_workers=2,
                        prefetch_batches=2)
    it = loader.epoch(0)
    got = []
    consumer = threading.Thread(target=lambda: got.append(next(it)))
    consumer.start()
    try:
        # batch 1 (indices 4..7) decodes while index 3 is still unfinished
        assert wait_for(lambda: {4, 5, 6, 7} <= set(started))
        assert not got
        _t.sleep(0.2)
        # ...and nothing further: batches 0 and 1 are the window
        assert max(started) == 7
    finally:
        gate.set()
        consumer.join(10.0)
    assert len(got) == 1
    # batch 0 yielded: batch 2 (8..11) was submitted, batch 3 never is
    it.close()
    _t.sleep(0.4)
    seen = list(started)
    _t.sleep(0.2)
    assert started == seen, "decode work kept flowing after close"
    assert max(started) <= 11
    assert len(started) < 12, "close cancelled none of the queued samples"
    loader.close()


def test_rows_in_place_under_thread_pressure():
    """More decode threads than cores and a short switch interval: every
    worker still writes its own row and no other (a lost or crossed write
    would break the byte identity)."""
    import sys

    tf = make_transform(num_frames=2, training=True, crop_size=16,
                        min_short_side_scale=18, max_short_side_scale=22,
                        output_dtype="bfloat16")
    src = SyntheticClipSource(tf, num_videos=96, num_classes=4,
                              raw_frames=4, raw_size=(24, 32), seed=11)
    loader = ClipLoader(src, global_batch_size=8, shuffle=True, seed=2,
                        num_workers=4 * (os.cpu_count() or 4),
                        prefetch_batches=3)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for epoch in range(3):
            indices = loader._epoch_indices(epoch)
            for b, batch in enumerate(loader.epoch(epoch)):
                want = assemble_batch(
                    [src.get(int(i), epoch)
                     for i in indices[b * 8:(b + 1) * 8]], 8)
                for k in want:
                    assert batch[k].tobytes() == want[k].tobytes(), (epoch, b, k)
    finally:
        sys.setswitchinterval(interval)
        loader.close()

"""DevicePrefetcher contract on the 8-device CPU mesh: batch-order parity
with the non-prefetched path, bounded on-device residency, mid-epoch
LoaderState resume, deterministic shutdown on early break / exception, and
the consumed-position checkpoint semantics the prefetch thread must not
break. Plus the satellites that ride the same PR: cached NamedSharding
construction and one-step-delayed tracker logging."""

import threading
import time

import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.data.device_prefetch import DevicePrefetcher
from pytorchvideo_accelerate_tpu.data.pipeline import (
    ClipLoader,
    LoaderState,
    SyntheticClipSource,
)
from pytorchvideo_accelerate_tpu.data.transforms import make_transform
from pytorchvideo_accelerate_tpu.parallel.sharding import (
    batch_sharding,
    shard_batch,
)


def _loader(n_videos=32, bs=8, **kw):
    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    src = SyntheticClipSource(tf, num_videos=n_videos, num_classes=4)
    return ClipLoader(src, global_batch_size=bs, num_workers=2, **kw)


def _assert_batches_equal(dev_batch, host_batch):
    assert set(dev_batch) == set(host_batch)
    for k in host_batch:
        np.testing.assert_array_equal(np.asarray(dev_batch[k]), host_batch[k])


def _no_prefetch_threads(timeout=5.0):
    """True once every device-prefetch worker thread has exited."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if not [t for t in threading.enumerate()
                if t.name == "device-prefetch" and t.is_alive()]:
            return True
        time.sleep(0.02)
    return False


def test_order_parity_with_inline_path(mesh8):
    """The prefetched stream is exactly the inline shard_batch stream."""
    plain, pre = _loader(), _loader()
    want = [shard_batch(mesh8, b) for b in plain.epoch(0)]
    pf = DevicePrefetcher(pre, mesh8, depth=2)
    got = list(pf.epoch(0))
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        for k in w:
            np.testing.assert_array_equal(np.asarray(g[k]), np.asarray(w[k]))
        assert g["video"].sharding == w["video"].sharding
    plain.close(); pre.close()


def test_micro_dim_parity(mesh8):
    """accum batches (accum, B, ...) keep the scan axis unsharded."""
    plain = _loader(bs=8, accum_steps=2)
    pre = _loader(bs=8, accum_steps=2)
    want = [shard_batch(mesh8, b, micro_dim=True) for b in plain.epoch(0)]
    got = list(DevicePrefetcher(pre, mesh8, depth=2, micro_dim=True).epoch(0))
    assert len(got) == len(want) == 2
    assert got[0]["video"].shape[:2] == (2, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g["video"]),
                                      np.asarray(w["video"]))
        assert g["video"].sharding == w["video"].sharding
    plain.close(); pre.close()


def test_depth_zero_is_synchronous_and_equal(mesh8):
    """depth=0: no thread, inline placement, identical stream + wait metric."""
    plain, pre = _loader(), _loader()
    want = [shard_batch(mesh8, b) for b in plain.epoch(0)]
    pf = DevicePrefetcher(pre, mesh8, depth=0)
    got = list(pf.epoch(0))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g["video"]),
                                      np.asarray(w["video"]))
    assert _no_prefetch_threads(timeout=0.1)  # none were ever started
    assert pf.pop_wait() > 0.0  # placement time is input wait in sync mode
    assert pf.pop_wait() == 0.0  # drained
    plain.close(); pre.close()


def test_bounded_residency(mesh8):
    """A slow consumer can never have more than `depth` placed-but-unconsumed
    batches resident — run-ahead is capped by the slot semaphore, not by how
    fast the host can decode."""
    loader = _loader(n_videos=64)  # 8 batches
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    n = 0
    for _ in pf.epoch(0):
        time.sleep(0.03)  # let the producer run as far ahead as it can
        n += 1
    assert n == 8
    assert 1 <= pf.max_resident <= 2
    loader.close()


def test_placed_batch_survives_the_loader_running_on(mesh8):
    """No yielded buffer is ever written again: on the CPU backend a
    `device_put` may alias the host array, so a placed batch must read the
    same after the loader has produced the next `prefetch_batches + depth +
    1` batches into fresh buffers — and the ring still never holds more than
    `depth`."""
    depth, ahead = 2, 2
    want = list(_loader(n_videos=96).epoch(0))  # 12 batches
    loader = _loader(n_videos=96, prefetch_batches=ahead)
    pf = DevicePrefetcher(loader, mesh8, depth=depth)
    held = []
    for placed in pf.epoch(0):
        held.append(placed)
        time.sleep(0.02)  # let the loader and the ring run ahead
    assert len(held) == len(want) == 12
    assert ahead + depth + 1 < len(held)
    for placed, host in zip(held, want):  # read only now, all produced
        _assert_batches_equal(placed, host)
    assert 1 <= pf.max_resident <= depth
    loader.close()


def test_loader_state_tracks_consumption_not_prefetch(mesh8):
    """THE checkpoint-correctness property: while the prefetch thread runs
    ahead, `loader.state` must report the consumed position — a checkpoint
    taken between steps must not skip the prefetched-but-unconsumed batches
    on resume."""
    loader = _loader(n_videos=64, shuffle=True)  # 8 batches
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    it = pf.epoch(0)
    next(it)
    time.sleep(0.3)  # prefetch thread fills its ring well past batch 1
    assert loader.state == LoaderState(epoch=0, position=1)
    next(it)
    assert loader.state == LoaderState(epoch=0, position=2)
    it.close()
    loader.close()


def test_resume_mid_epoch_matches_plain_path(mesh8):
    """Restore a checkpointed LoaderState into a fresh loader+prefetcher:
    the remaining stream equals the plain path's remaining stream."""
    loader = _loader(n_videos=64, shuffle=True)
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    it = pf.epoch(0)
    next(it); next(it)
    saved = loader.state.to_dict()
    it.close()
    loader.close()

    plain = _loader(n_videos=64, shuffle=True)
    plain.state = LoaderState.from_dict(saved)
    want = [b["label"] for b in plain.epoch(0)]

    resumed = _loader(n_videos=64, shuffle=True)
    resumed.state = LoaderState.from_dict(saved)
    got = [np.asarray(b["label"])
           for b in DevicePrefetcher(resumed, mesh8, depth=2).epoch(0)]
    assert len(got) == len(want) == 6
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # full drain rolled the epoch over, same as the plain path
    assert resumed.state == LoaderState(epoch=1, position=0)
    assert plain.state == LoaderState(epoch=1, position=0)
    plain.close(); resumed.close()


def test_early_break_shuts_down_cleanly(mesh8):
    """limit_train_batches semantics: closing the epoch generator after one
    batch stops and joins the worker thread (no orphaned prefetch thread
    spinning device_puts) and leaves the consumed position in state."""
    loader = _loader(n_videos=64)
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    it = pf.epoch(0)
    next(it)
    it.close()
    assert _no_prefetch_threads(), "prefetch worker survived generator close"
    assert loader.state == LoaderState(epoch=0, position=1)
    loader.close()


def test_source_exception_propagates_and_cleans_up(mesh8):
    """A failure inside the host pipeline crosses the thread boundary and
    raises in the step loop, with the worker shut down."""

    class Exploding(SyntheticClipSource):
        def get(self, index, epoch):
            if index >= 16:
                raise RuntimeError("decode blew up")
            return super().get(index, epoch)

    tf = make_transform(num_frames=4, training=False, crop_size=32,
                        min_short_side_scale=32)
    src = Exploding(tf, num_videos=32, num_classes=4)
    loader = ClipLoader(src, global_batch_size=8, num_workers=2)
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    with pytest.raises(RuntimeError, match="decode blew up"):
        list(pf.epoch(0))
    assert _no_prefetch_threads(), "prefetch worker survived the error"
    loader.close()


def test_eval_from_start_via_prefetcher(mesh8):
    """The eval contract holds through the prefetcher: from_start ignores a
    stale mid-epoch position left by an early-broken pass."""
    loader = _loader(n_videos=32)
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    it = pf.epoch(0)
    next(it)
    it.close()
    assert loader.state.position == 1
    assert len(list(pf.epoch(0, from_start=True))) == 4
    loader.close()


def test_wait_metric_accumulates_and_pops(mesh8):
    loader = _loader()
    pf = DevicePrefetcher(loader, mesh8, depth=2)
    list(pf.epoch(0))
    w = pf.pop_wait()
    assert w > 0.0  # at minimum, the wait for the first batch
    assert pf.pop_wait() == 0.0
    loader.close()


def test_invalid_depth_rejected(mesh8):
    loader = _loader()
    try:
        with pytest.raises(ValueError, match="depth"):
            DevicePrefetcher(loader, mesh8, depth=-1)
    finally:
        loader.close()


# --- satellite: cached NamedSharding construction --------------------------

def test_batch_sharding_is_memoized(mesh8):
    """Same mesh -> the SAME NamedSharding object (not merely equal): the
    per-step rebuild the memo removes."""
    assert batch_sharding(mesh8) is batch_sharding(mesh8)


# --- satellite: one-step-delayed tracker logging ---------------------------

class _RecordingHub:
    def __init__(self):
        self.calls = []

    def log(self, values, step):
        self.calls.append((dict(values), step))


def test_deferred_step_logger_delays_and_converts():
    from pytorchvideo_accelerate_tpu.trainer.tracking import DeferredStepLogger

    hub = _RecordingHub()
    d = DeferredStepLogger(hub)
    d.flush()  # nothing pending: no-op
    assert hub.calls == []
    d.defer({"loss": np.float32(1.5)}, step=10)
    assert hub.calls == []  # NOT logged on the critical path
    d.flush()
    assert hub.calls == [({"loss": 1.5}, 10)]
    assert isinstance(hub.calls[0][0]["loss"], float)
    d.flush()  # idempotent
    assert len(hub.calls) == 1


def test_deferred_step_logger_never_drops_on_back_to_back_defers():
    from pytorchvideo_accelerate_tpu.trainer.tracking import DeferredStepLogger

    hub = _RecordingHub()
    d = DeferredStepLogger(hub)
    d.defer({"loss": 1.0}, step=1)
    d.defer({"loss": 2.0}, step=2)  # flushes step 1 first
    d.flush()
    assert [s for _, s in hub.calls] == [1, 2]

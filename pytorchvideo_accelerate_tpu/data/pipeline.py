"""Host-side clip pipeline: sources, sharded batching, prefetch, state.

TPU-native replacement for the reference's loader stack (SURVEY §2.1
R8-R10, §2.2-A4): `Kinetics` iterable dataset + `LimitDataset` + torch
`DataLoader(num_workers=8, pin_memory)` + accelerate's `BatchSamplerShard`
become:

- a `ClipSource` (real videos via manifest+cv2, or synthetic fixture),
- deterministic per-epoch shuffling from the shared seed (identical on all
  hosts — no cross-rank RNG sync needed, SURVEY A11),
- per-host index interleaving `idx[process_index::process_count]` (the
  `DistributedSampler`/`BatchSamplerShard` equivalent, without padding
  duplicates: val tail batches carry an explicit mask instead),
- a thread-pool decode pool (cv2 releases the GIL; threads give native
  decode parallelism without fork overhead) with one-batch-ahead prefetch
  (`DataLoaderShard.__iter__` prefetch semantics, data_loader.py:576-610),
- checkpointable iterator state {epoch, position} (extends checkpoint
  capability A8 to data, replacing the reference's skip-batches resume at
  run.py:246-249 with an O(1) index fast-forward).

Conscious fixes of catalogued reference quirks (SURVEY §2.1): the reference's
`LimitDataset` shares one iterator across epochs and workers (duplicated
streams, shuffle=True shuffles nothing); here every (epoch, index) maps to an
independent deterministic sample.
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from queue import Empty, Queue
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.reliability.retry import retry_call
from pytorchvideo_accelerate_tpu.utils.sync import make_lock
from pytorchvideo_accelerate_tpu.data import decode as decode_mod
from pytorchvideo_accelerate_tpu.data.manifest import Manifest, Quarantine
from pytorchvideo_accelerate_tpu.data.samplers import (
    random_clip,
    substitute_indices,
    uniform_clips,
)

logger = logging.getLogger(__name__)


class _DecodeFailure(Exception):
    """Tag for decode-layer failures crossing the transform boundary —
    keeps VideoClipSource's substitution from swallowing transform bugs."""


class ClipSource:
    """A deterministic map (epoch, index) -> sample dict of numpy arrays."""

    num_classes: int

    def __len__(self) -> int:  # pragma: no cover - interface
        raise NotImplementedError

    def get(self, index: int, epoch: int) -> Dict[str, np.ndarray]:  # pragma: no cover
        raise NotImplementedError


def sample_views(read_span: Callable, transform: Callable, duration: float,
                 clip_duration: float, training: bool,
                 rng: np.random.Generator, num_clips: int) -> Dict[str, np.ndarray]:
    """Shared span-selection + multi-view stacking for every clip source.

    Train: ONE random span. Eval: `num_clips` evenly-spaced spans — times
    the transform's `num_spatial_crops` when it declares one (the papers'
    30-view protocol: 10 temporal x 3 spatial) — each transformed and
    stacked on ONE leading view axis, temporal-major (the eval step
    view-averages the logits; reference uniform tiling, run.py:163).
    `read_span(start_sec, end_sec) -> (T, H, W, 3) uint8`.
    """
    # training transforms can't carry spatial crops (make_transform forbids
    # it), so the attribute alone decides — this also serves sources that
    # use train-style random spans with an eval transform (SyntheticClipSource
    # at num_clips=1)
    n_spatial = max(getattr(transform, "num_spatial_crops", 1), 1)
    if training:
        spans = [random_clip(duration, clip_duration, rng)]
    else:
        spans = uniform_clips(duration, clip_duration, num_clips)
    if n_spatial > 1:
        # decode AND pre-crop once per span; spatial_views applies the
        # n_spatial crops to the shared scaled frames
        views = []
        for s in spans:
            views.extend(transform.spatial_views(read_span(s.start, s.end)))
    else:
        views = [transform(read_span(s.start, s.end), rng) for s in spans]
    if len(views) == 1:  # no view axis for the single-view case
        return views[0]
    return {k: np.stack([v[k] for v in views]) for k in views[0]}


class VideoClipSource(ClipSource):
    """Real videos: manifest entry -> clip span -> cv2 decode -> transform.

    `training=True` samples a random span with an RNG derived from
    (seed, epoch, index) — reproducible across restarts, distinct across
    epochs (what the reference's shared-iterator design failed to provide).

    Unreadable/corrupt videos (real Kinetics trees always have some) are
    substituted, not fatal: up to `_MAX_CONSECUTIVE_FAILURES` replacement
    indices, each drawn from its own attempt-keyed RNG stream
    ((seed, 0xBAD, epoch, index, attempt)) so the substitution is
    reproducible across restarts regardless of how many draws a failed
    decode consumed or whether a known-bad path was skipped outright;
    failed paths are remembered and a warning logged once per file.
    Mirrors pytorchvideo LabeledVideoDataset's retry semantics (the
    reference's decode-failure behavior, run.py:151-168 [external]); the
    label always comes from the video actually decoded. Only DECODE
    failures substitute — transform errors propagate (a transform bug must
    not silently skew the data distribution).

    With a `quarantine` (`data/manifest.Quarantine`), every exhausted-retry
    failure also counts against that clip's persisted failure budget;
    past it the path is quarantined — excluded at the SAMPLER level
    (`quarantined_indices()` feeds `samplers.substitute_indices`, so the
    clip never reaches the decode pool again, this run or the next) —
    instead of paying the retry + substitution dance every epoch or, after
    `_MAX_CONSECUTIVE_FAILURES`, raising through and killing the run.
    """

    def __init__(
        self,
        manifest: Manifest,
        transform: Callable,
        clip_duration: float,
        training: bool,
        seed: int = 42,
        num_clips: int = 1,
        decode_retries: int = 2,
        retry_base_delay_s: float = 0.05,
        quarantine: Optional[Quarantine] = None,
    ):
        self.manifest = manifest
        self.transform = transform
        self.clip_duration = clip_duration
        self.training = training
        self.seed = seed
        # total decode attempts per read before substitution: transient
        # I/O (cold NFS, flaky storage) recovers via reliability/retry.py;
        # a genuinely corrupt file still exhausts the budget fast and
        # falls through to the substitution path below
        self.decode_retries = max(int(decode_retries), 1)
        self.retry_base_delay_s = retry_base_delay_s
        # eval-only multi-view: `num_clips` evenly-spaced views per video,
        # stacked on a leading axis; the eval step view-averages the logits
        # in-graph (reference uniform-sampler tiling, run.py:163)
        self.num_clips = max(num_clips, 1) if not training else 1
        self.num_classes = manifest.num_classes
        self.quarantine = quarantine
        self._meta_cache: Dict[str, decode_mod.VideoMeta] = {}
        self._meta_lock = make_lock("VideoClipSource._meta_lock")
        self._failed: set = set()

    _MAX_CONSECUTIVE_FAILURES = 10  # pytorchvideo LabeledVideoDataset parity

    def __len__(self) -> int:
        return len(self.manifest)

    def quarantined_indices(self) -> set:
        """Manifest indices of quarantined paths — the sampler-exclusion
        input (`ClipLoader._epoch_indices` remaps them onto clean clips
        via `samplers.substitute_indices`). Empty without a quarantine."""
        if self.quarantine is None or len(self.quarantine) == 0:
            return set()
        bad = self.quarantine.paths()
        return {i for i, e in enumerate(self.manifest.entries)
                if e.path in bad}

    def _meta(self, path: str) -> decode_mod.VideoMeta:
        with self._meta_lock:
            meta = self._meta_cache.get(path)
        if meta is None:
            meta = decode_mod.probe(path)
            with self._meta_lock:
                self._meta_cache[path] = meta
        return meta

    def get(self, index: int, epoch: int) -> Dict[str, np.ndarray]:
        idx = index
        for attempt in range(self._MAX_CONSECUTIVE_FAILURES):
            # each attempt gets its OWN rng stream: reproducibility across
            # restarts must not depend on how many draws a previous attempt
            # consumed before failing, nor on whether a known-bad path was
            # skipped without any decode attempt (self._failed is run-local
            # history; attempt-keyed streams make it invisible to sampling)
            rng = (np.random.default_rng((self.seed, epoch, index))
                   if attempt == 0
                   else np.random.default_rng(
                       (self.seed, epoch, index, attempt)))
            entry = self.manifest.entries[idx]
            with self._meta_lock:
                known_bad = entry.path in self._failed
            if not known_bad and self.quarantine is not None:
                # quarantined clips are skipped without a decode attempt;
                # normally the sampler already excluded them, this covers
                # direct get() callers and just-quarantined paths mid-epoch
                known_bad = self.quarantine.contains(entry.path)
            if not known_bad:
                # only DECODE failures are substitutable; the read_span
                # wrapper tags them so a transform bug raising ValueError
                # inside sample_views can't be mistaken for a corrupt file
                # (which would silently blacklist readable videos)
                def read_span(a, b, _path=entry.path):
                    try:
                        # transient read failures retry with backoff before
                        # the substitution machinery gives up on the file
                        return retry_call(
                            lambda: decode_mod.decode_span(_path, a, b),
                            name="decode.read",
                            attempts=self.decode_retries,
                            retry_on=decode_mod.DECODE_ERRORS,
                            base_delay_s=self.retry_base_delay_s,
                            deadline_s=5.0,
                        )
                    except decode_mod.DECODE_ERRORS as e:
                        raise _DecodeFailure(str(e)) from e

                def mark_failed(e):
                    with self._meta_lock:
                        self._failed.add(entry.path)
                    if self.quarantine is not None:
                        # one exhausted-retry failure against the persisted
                        # budget; crossing it sidelines the clip for good
                        self.quarantine.record(entry.path, e)
                    logger.warning(
                        "skipping unreadable video %s (%s: %s); substituting",
                        entry.path, type(e).__name__, e)

                try:
                    meta = self._meta(entry.path)
                except decode_mod.DECODE_ERRORS as e:
                    mark_failed(e)
                else:
                    try:
                        out = sample_views(
                            read_span, self.transform, meta.duration,
                            self.clip_duration, self.training, rng,
                            self.num_clips,
                        )
                    except _DecodeFailure as e:
                        mark_failed(e)
                    else:
                        out["label"] = np.int32(entry.label)
                        return out
            # deterministic replacement, also attempt-keyed
            idx = int(np.random.default_rng(
                (self.seed, 0xBAD, epoch, index, attempt)
            ).integers(0, len(self.manifest)))
        raise IOError(
            f"{self._MAX_CONSECUTIVE_FAILURES} consecutive unreadable videos "
            f"starting at index {index} (see warnings for paths)")


class SyntheticClipSource(ClipSource):
    """Label-coded synthetic clips — the `RegressionDataset` moral equivalent
    from accelerate's harness (SURVEY §4.4), used by tests and bench; no
    video files, but the full transform stack still runs."""

    def __init__(
        self,
        transform: Callable,
        num_videos: int = 64,
        num_classes: int = 4,
        raw_frames: int = 24,
        raw_size: tuple = (72, 96),
        seed: int = 42,
        num_clips: int = 1,
    ):
        self.transform = transform
        self.num_videos = num_videos
        self.num_classes = num_classes
        self.raw_frames = raw_frames
        self.raw_size = raw_size
        self.seed = seed
        self.num_clips = max(num_clips, 1)

    def __len__(self) -> int:
        return self.num_videos

    def get(self, index: int, epoch: int) -> Dict[str, np.ndarray]:
        label = index % self.num_classes
        rng = np.random.default_rng((self.seed, epoch, index))
        h, w = self.raw_size

        def synth_span(a, b):  # label-coded random frames, span-independent
            frames = (rng.random((self.raw_frames, h, w, 3)) * 60).astype(np.uint8)
            frames += np.uint8(label * (160 // max(self.num_classes - 1, 1)))
            return frames

        out = sample_views(synth_span, self.transform, 1.0, 1.0,
                           training=self.num_clips == 1, rng=rng,
                           num_clips=self.num_clips)
        out["label"] = np.int32(label)
        return out


def stack_samples(arrs: List[np.ndarray]) -> np.ndarray:
    """np.stack via the native multithreaded gather-copy when available
    (GIL-free batch assembly); numpy fallback otherwise. Module-level so
    remote decode workers (dataplane/worker.py) assemble batches with the
    EXACT code path the local loader uses — byte parity by construction."""
    first = np.asarray(arrs[0])
    if first.ndim == 0:
        return np.stack(arrs)
    from pytorchvideo_accelerate_tpu.native.ringbuf import gather_copy

    out = np.empty((len(arrs), *first.shape), first.dtype)
    gather_copy(out, arrs)
    return out


def assemble_batch(samples: List[Dict[str, np.ndarray]], pad_to: int,
                   accum_steps: int = 1,
                   local_batch_size: Optional[int] = None) -> dict:
    """Stack per-sample dicts into one batch dict: padded + masked tail
    (val only) below `pad_to`, reshaped to (accum, B_local, ...) when
    `accum_steps > 1`. The single batch-assembly authority for the local
    loader AND the remote decode workers."""
    n = len(samples)
    keys = samples[0].keys()
    batch = {k: stack_samples([s[k] for s in samples]) for k in keys}
    if n < pad_to:  # padded tail (val only): mask marks real samples
        mask = np.zeros(pad_to, np.float32)
        mask[:n] = 1.0
        for k in list(batch):
            pad_shape = (pad_to - n, *batch[k].shape[1:])
            batch[k] = np.concatenate(
                [batch[k], np.zeros(pad_shape, batch[k].dtype)]
            )
        batch["mask"] = mask
    if accum_steps > 1:
        lb = local_batch_size if local_batch_size else pad_to // accum_steps
        batch = {
            k: v.reshape(accum_steps, lb, *v.shape[1:])
            for k, v in batch.items()
        }
    return batch


@dataclass
class LoaderState:
    """Checkpointable iterator position."""

    epoch: int = 0
    position: int = 0  # batches already yielded this epoch

    def to_dict(self) -> dict:
        return {"epoch": self.epoch, "position": self.position}

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "LoaderState":
        d = d or {}
        return cls(epoch=int(d.get("epoch", 0)), position=int(d.get("position", 0)))


class ClipLoader:
    """Batches a ClipSource for one host of a data-parallel mesh.

    Yields numpy batch dicts shaped (B_local, ...) — or (accum, B_local, ...)
    when `accum_steps > 1` — ready for `parallel.sharding.shard_batch`.
    `global_batch_size` is the whole-mesh batch; B_local is this host's share.
    """

    def __init__(
        self,
        source: ClipSource,
        global_batch_size: int,
        accum_steps: int = 1,
        shuffle: bool = False,
        drop_last: bool = True,
        seed: int = 42,
        num_workers: int = 8,
        process_index: int = 0,
        process_count: int = 1,
        prefetch_batches: int = 2,
        transport: str = "thread",
    ):
        if global_batch_size % process_count:
            raise ValueError(
                f"global_batch_size {global_batch_size} not divisible by "
                f"process_count {process_count}"
            )
        if transport not in ("auto", "thread", "process"):
            raise ValueError(
                f"transport must be auto|thread|process, got {transport!r}")
        self.source = source
        self.global_batch_size = global_batch_size
        self.local_batch_size = global_batch_size // process_count
        self.accum_steps = max(accum_steps, 1)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.num_workers = max(num_workers, 1)
        self.process_index = process_index
        self.process_count = process_count
        self.prefetch_batches = prefetch_batches
        self.state = LoaderState()
        self._pool = ThreadPoolExecutor(max_workers=self.num_workers)
        # "process": forked decode workers + native shm ring (SURVEY N8);
        # falls back to threads when the native lib can't build.
        # "auto" = threads. Every measurement to date says so: cv2 decode
        # and numpy transforms release the GIL, threads beat the forked
        # shm-ring transport 7x on the production decode path and broke
        # even (0.996x) even on a deliberately GIL-bound pure-Python
        # augment stack (bench.py transport_crossover). An earlier >=16-core
        # heuristic here was extrapolation from a 1-core host — a guess,
        # not a measurement — so it is gone: the process transport is an
        # EXPLICIT opt-in for workloads whose transforms hold the GIL
        # (heavy pure-Python per-clip work), where the fork + shm-ring
        # overhead can pay for itself.
        self.transport = "thread" if transport == "auto" else transport
        self._shm_pool = None
        if self.transport == "process":
            import pytorchvideo_accelerate_tpu.native as native

            if native.load() is None:
                self.transport = "thread"

    # --- epoch geometry ---------------------------------------------------

    def _epoch_indices(self, epoch: int) -> np.ndarray:
        idx = np.arange(len(self.source))
        if self.shuffle:
            rng = np.random.default_rng((self.seed, 0xDA7A, epoch))
            rng.shuffle(idx)
        idx = idx[self.process_index :: self.process_count]
        # bad-sample quarantine (data/manifest.Quarantine): sources that
        # track quarantined clips get them remapped onto clean ones HERE,
        # so a sidelined clip never reaches the decode pool and epoch
        # geometry (batch count, loader positions) stays unchanged
        quarantined = getattr(self.source, "quarantined_indices", None)
        if quarantined is not None:
            bad = quarantined()
            if bad:
                idx = substitute_indices(idx, bad, len(self.source),
                                         self.seed, epoch)
        return idx

    @property
    def samples_per_yield(self) -> int:
        return self.local_batch_size * self.accum_steps

    def batches_per_epoch(self) -> int:
        n = len(self.source) // self.process_count
        if self.drop_last:
            return n // self.samples_per_yield
        return -(-n // self.samples_per_yield)

    def steps_per_epoch(self) -> int:
        """Optimizer steps per epoch (one per yielded super-batch)."""
        return self.batches_per_epoch()

    # --- iteration --------------------------------------------------------

    @staticmethod
    def _stack(arrs: List[np.ndarray]) -> np.ndarray:
        return stack_samples(arrs)

    def _assemble(self, samples: List[Dict[str, np.ndarray]], pad_to: int) -> dict:
        return assemble_batch(samples, pad_to, accum_steps=self.accum_steps,
                              local_batch_size=self.local_batch_size)

    def epoch(self, epoch: Optional[int] = None,
              from_start: bool = False) -> Iterator[dict]:
        """Iterate one epoch, honoring and updating `self.state` (resume
        mid-epoch by restoring state before calling).

        `from_start=True` ignores any stored mid-epoch position — the eval
        contract: a previous early-broken pass (limit_val_batches) must not
        make the next pass silently skip its head batches."""
        for batch, state in self.epoch_items(epoch, from_start):
            self.state = state
            if batch is not None:
                yield batch

    def epoch_items(self, epoch: Optional[int] = None,
                    from_start: bool = False) -> Iterator[tuple]:
        """Like `epoch()`, but yields `(batch, LoaderState)` pairs and never
        mutates `self.state` — the post-consumption state rides alongside each
        batch, and a final `(None, rollover_state)` pair marks exhaustion.

        This is the contract the device prefetcher needs: it advances this
        generator from a background thread, so state assignment must happen
        on the CONSUMER side, when the trainer actually takes a batch —
        otherwise a mid-epoch checkpoint would record a position several
        prefetched batches ahead of what training consumed, and resume would
        silently skip them."""
        start_state = self._start_state(epoch, from_start)
        epoch = start_state.epoch
        indices = self._epoch_indices(epoch)
        spy = self.samples_per_yield
        n_batches = self.batches_per_epoch()
        if self.transport == "process":
            yield from self._epoch_process_items(
                epoch, start_state.position, indices, n_batches)
            return

        def fetch_one(i) -> Dict[str, np.ndarray]:
            # obs "decode" span: per-sample decode+transform wall time on
            # the worker threads (background-classed — it overlaps the
            # consumer loop, so it informs, never sums into, window wall)
            with obs.span("decode"):
                return self.source.get(int(i), epoch)

        def fetch_batch(b: int) -> dict:
            # obs "batch" span: one per assembled batch, on the assembly
            # lane's thread (its samples' decodes run on the pool's)
            with obs.span("batch"):
                chunk = indices[b * spy : (b + 1) * spy]
                samples = list(self._pool.map(fetch_one, chunk))
                return self._assemble(samples, spy)

        start = start_state.position
        pending: "Queue[tuple]" = Queue()
        depth = max(self.prefetch_batches, 1)
        next_submit = start
        submitted = 0
        executor = ThreadPoolExecutor(max_workers=1)  # batch-assembly lane
        try:
            while next_submit < n_batches and submitted < depth:
                pending.put((next_submit, executor.submit(fetch_batch, next_submit)))
                next_submit += 1
                submitted += 1
            while not pending.empty():
                b, fut = pending.get()
                batch = fut.result()
                if next_submit < n_batches:
                    pending.put(
                        (next_submit, executor.submit(fetch_batch, next_submit))
                    )
                    next_submit += 1
                yield batch, LoaderState(epoch=epoch, position=b + 1)
            yield None, LoaderState(epoch=epoch + 1, position=0)
        finally:
            # early exit (limit_train_batches break -> GeneratorExit, or an
            # exception upstream): in-flight fetch_batch futures would keep
            # decoding whole batches after the consumer is gone. Cancel
            # everything still queued; shutdown(cancel_futures) catches any
            # race between the drain and a worker picking one up.
            while not pending.empty():
                try:
                    pending.get_nowait()[1].cancel()
                except Empty:  # pragma: no cover - single-consumer queue
                    break
            try:
                executor.shutdown(wait=False, cancel_futures=True)
            except TypeError:  # pragma: no cover - py<3.9 fallback
                executor.shutdown(wait=False)

    def _start_state(self, epoch: Optional[int],
                     from_start: bool) -> LoaderState:
        """Effective starting position for an epoch pass (pure; `epoch()` /
        the prefetcher assign it back to `self.state` batch by batch)."""
        if from_start:
            return LoaderState(
                epoch=self.state.epoch if epoch is None else epoch, position=0)
        if epoch is not None and epoch != self.state.epoch:
            return LoaderState(epoch=epoch, position=0)
        return self.state

    def _epoch_process_items(self, epoch: int, start: int,
                             indices: np.ndarray,
                             n_batches: int) -> Iterator[tuple]:
        """Forked shm workers; batches byte-identical to the thread path.
        Prefetch comes from ring capacity (workers run ahead of assembly)."""
        from pytorchvideo_accelerate_tpu.native.shm_loader import ShmWorkerPool

        spy = self.samples_per_yield
        if self._shm_pool is None:
            # assembly defers slot release until a full batch is collected;
            # worker w contributes ceil(spy/W) samples per batch, so each
            # per-worker ring must hold that many in-flight slots plus
            # prefetch headroom
            per_worker = -(-spy // self.num_workers) + 2
            self._shm_pool = ShmWorkerPool(
                self.source, num_workers=self.num_workers,
                slots_per_worker=per_worker,
            )
        usable = indices[: n_batches * spy] if self.drop_last else indices
        samples, dones = [], []
        b = start

        def flush():
            nonlocal samples, dones
            with obs.span("batch"):
                batch = self._assemble(samples, spy)
            for done in dones:
                done()
            samples, dones = [], []
            return batch

        for sample, done in self._shm_pool.map_epoch(
            usable, epoch, start=start * spy
        ):
            samples.append(sample)
            dones.append(done)
            if len(samples) == spy:
                yield flush(), LoaderState(epoch=epoch, position=b + 1)
                b += 1
        if samples:  # non-drop_last tail, padded + masked
            yield flush(), LoaderState(epoch=epoch, position=b + 1)
        yield None, LoaderState(epoch=epoch + 1, position=0)

    def close(self) -> None:
        self._pool.shutdown(wait=False)
        if self._shm_pool is not None:
            self._shm_pool.close()
            self._shm_pool = None

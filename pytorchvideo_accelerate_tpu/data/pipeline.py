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
  decode parallelism without fork overhead) whose workers write each clip
  straight into its row of the batch, fed `prefetch_batches` batches ahead
  with no barrier between batches (`DataLoaderShard.__iter__` prefetch
  semantics, data_loader.py:576-610),
- checkpointable iterator state {epoch, position} (extends checkpoint
  capability A8 to data, replacing the reference's skip-batches resume at
  run.py:246-249 with an O(1) index fast-forward).

Conscious fixes of catalogued reference quirks (SURVEY §2.1): the reference's
`LimitDataset` shares one iterator across epochs and workers (duplicated
streams, shuffle=True shuffles nothing); here every (epoch, index) maps to an
independent deterministic sample.
"""

from __future__ import annotations

import inspect
import logging
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
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
from pytorchvideo_accelerate_tpu.data.transforms import resize_on_calling_thread

logger = logging.getLogger(__name__)


class _DecodeFailure(Exception):
    """Tag for decode-layer failures crossing the transform boundary —
    keeps VideoClipSource's substitution from swallowing transform bugs."""


class ClipSource:
    """A deterministic map (epoch, index) -> sample dict of numpy arrays."""

    num_classes: int

    def __len__(self) -> int:  # pragma: no cover - interface
        raise NotImplementedError

    def get(self, index: int, epoch: int,
            out: Optional[Dict[str, np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:  # pragma: no cover
        """`out`, where a source takes it: the sample's rows of the batch
        being built, one preallocated array a key (`ClipLoader`'s thread
        path). The source writes what it can into them and returns those
        very arrays; whatever it returns as an array of its own the loader
        copies in. A subclass may keep the two-argument signature."""
        raise NotImplementedError


def label_row(label: int, out: Optional[Dict[str, np.ndarray]]):
    """The sample's label: written into its row where the loader gave one."""
    if out is None:
        return np.int32(label)
    out["label"][...] = label
    return out["label"]


def sample_views(read_span: Callable, transform: Callable, duration: float,
                 clip_duration: float, training: bool,
                 rng: np.random.Generator, num_clips: int,
                 out: Optional[Dict[str, np.ndarray]] = None
                 ) -> Dict[str, np.ndarray]:
    """Shared span-selection + multi-view stacking for every clip source.

    Train: ONE random span. Eval: `num_clips` evenly-spaced spans — times
    the transform's `num_spatial_crops` when it declares one (the papers'
    30-view protocol: 10 temporal x 3 spatial) — each transformed and
    stacked on ONE leading view axis, temporal-major (the eval step
    view-averages the logits; reference uniform tiling, run.py:163).
    `read_span(start_sec, end_sec) -> (T, H, W, 3) uint8`.

    `out` (the loader's rows for this sample, `ClipSource.get`) goes on to a
    transform that `writes_rows`: each view is written where the stack
    would have put it (`out[key][view]`; `out[key]` for the single view)
    and the rows themselves are returned. Any other transform returns its
    own arrays, as without `out`.
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
    n_views = len(spans) * n_spatial
    writes = out is not None and getattr(transform, "writes_rows", False)
    if not writes:
        rows = [None] * n_views
    elif n_views == 1:  # no view axis for the single-view case
        rows = [out]
    else:
        rows = [{k: v[j] for k, v in out.items() if k != "label"}
                for j in range(n_views)]
    if n_spatial > 1:
        # decode AND pre-crop once per span; spatial_views applies the
        # n_spatial crops to the shared scaled frames
        views = []
        for i, s in enumerate(spans):
            frames = read_span(s.start, s.end)
            views.extend(
                transform.spatial_views(
                    frames, rows[i * n_spatial:(i + 1) * n_spatial])
                if writes else transform.spatial_views(frames))
    else:
        views = [transform(read_span(s.start, s.end), rng, out=r) if writes
                 else transform(read_span(s.start, s.end), rng)
                 for s, r in zip(spans, rows)]
    if n_views == 1:
        return views[0]
    if writes:  # every view is already where the stack would put it
        return {k: out[k] for k in views[0]}
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

    def get(self, index: int, epoch: int,
            out: Optional[Dict[str, np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
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
                        # a substitute writes the same rows again: the
                        # transform writes a view only once it is whole
                        sample = sample_views(
                            read_span, self.transform, meta.duration,
                            self.clip_duration, self.training, rng,
                            self.num_clips, out,
                        )
                    except _DecodeFailure as e:
                        mark_failed(e)
                    else:
                        sample["label"] = label_row(entry.label, out)
                        return sample
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

    def get(self, index: int, epoch: int,
            out: Optional[Dict[str, np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
        label = index % self.num_classes
        rng = np.random.default_rng((self.seed, epoch, index))
        h, w = self.raw_size

        def synth_span(a, b):  # label-coded random frames, span-independent
            frames = (rng.random((self.raw_frames, h, w, 3)) * 60).astype(np.uint8)
            frames += np.uint8(label * (160 // max(self.num_classes - 1, 1)))
            return frames

        sample = sample_views(synth_span, self.transform, 1.0, 1.0,
                              training=self.num_clips == 1, rng=rng,
                              num_clips=self.num_clips, out=out)
        sample["label"] = label_row(label, out)
        return sample


class SyntheticTokenSource(ClipSource):
    """Token sequences for a next-token model: `seq_len` int32 ids a sample,
    uniform over the held vocabulary slice [0, vocab_size), one document a
    sequence (no padding, no packing). Each (seed, epoch, index) is a stream
    of its own, written straight into the loader's row where it gives one."""

    def __init__(self, seq_len: int, vocab_size: int, num_sequences: int = 64,
                 seed: int = 42):
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.num_sequences = num_sequences
        self.seed = seed

    def __len__(self) -> int:
        return self.num_sequences

    def get(self, index: int, epoch: int,
            out: Optional[Dict[str, np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, epoch, index))
        ids = rng.integers(0, self.vocab_size, self.seq_len, dtype=np.int32)
        if out is None:
            return {"tokens": ids}
        out["tokens"][...] = ids
        return {"tokens": out["tokens"]}


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


def _takes_out(get: Callable) -> bool:
    """Does a source's `get` accept the rows to write (`out=`)?"""
    try:
        params = inspect.signature(get).parameters
    except (TypeError, ValueError):  # pragma: no cover - a C callable
        return False
    return "out" in params or any(
        p.kind is p.VAR_KEYWORD for p in params.values())


def _row_views(rows: Dict[str, np.ndarray], r: int) -> Dict[str, np.ndarray]:
    """Sample `r`'s rows of a batch's buffers (views; `label`'s is 0-d)."""
    return {k: v[r, ...] for k, v in rows.items() if k != "mask"}


def _fill_rows(rows: Dict[str, np.ndarray],
               sample: Dict[str, np.ndarray]) -> bool:
    """Copy in whatever `sample` holds as arrays of its own; True when it
    held none (the source wrote every row itself). A sample that does not
    fit the loader's rows is refused, as a ragged stack was."""
    if sample.keys() != rows.keys():
        raise ValueError(f"sample keys {sorted(sample)} differ from the "
                         f"loader's rows {sorted(rows)}")
    in_place = True
    for k, row in rows.items():
        v = sample[k]
        if v is row:
            continue
        in_place = False
        if np.shape(v) != row.shape:
            raise ValueError(f"sample[{k!r}] has shape {np.shape(v)}, the "
                             f"loader's rows hold {row.shape}")
        np.copyto(row, v, casting="unsafe")
    return in_place


def _row_counters() -> tuple:
    """`pva_loader_rows_in_place`, `pva_loader_rows_copied`: rows of yielded
    batches that the source wrote itself, and rows a worker copied in from
    arrays the source returned (docs/OBSERVABILITY.md)."""
    reg = obs.get_registry()
    return (reg.counter("pva_loader_rows_in_place",
                        "batch rows written in place by the clip source"),
            reg.counter("pva_loader_rows_copied",
                        "batch rows copied in from arrays the source returned"))


class LoaderRowCounts:
    """The two row counters read window by window (the trainer's per-window
    log, beside `obs/batch_s`)."""

    def __init__(self):
        self._counters = _row_counters()
        self._seen = [c.total() for c in self._counters]

    def window_share(self) -> Optional[float]:
        """Rows written in place over all rows yielded since the last call;
        None when no batch was yielded."""
        now = [c.total() for c in self._counters]
        in_place, copied = (a - b for a, b in zip(now, self._seen))
        self._seen = now
        rows = in_place + copied
        return in_place / rows if rows else None


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
        # thread path: does the source write its sample into the rows it is
        # handed (`get(index, epoch, out=rows)`), or return arrays of its
        # own for the worker to copy in? Decided by what its `get` accepts
        self._source_writes_rows = _takes_out(source.get)
        # {key: (sample shape, dtype)}, learnt from the first sample this
        # loader produces and held: what a batch's buffers are made from
        self._row_spec: Optional[Dict[str, tuple]] = None
        # "process": forked decode workers + native shm ring (SURVEY N8);
        # falls back to threads when the native lib can't build.
        # "auto" = threads. Every measurement to date says so: cv2 decode
        # and numpy transforms release the GIL, threads beat the forked
        # shm-ring transport 7x on the production decode path and broke
        # even (0.996x) even on a deliberately GIL-bound pure-Python
        # augment stack. An earlier >=16-core
        # heuristic here was extrapolation from a 1-core host — a guess,
        # not a measurement — so it is gone: the process transport is an
        # EXPLICIT opt-in for workloads whose transforms hold the GIL
        # (heavy pure-Python per-clip work), where the fork + shm-ring
        # overhead can pay for itself.
        self.transport = "thread" if transport == "auto" else transport
        if self.transport == "thread" and self.num_workers > 1:
            # the decode pool is the parallelism; cv2's own, inside every
            # worker's resize, would oversubscribe a pool that is kept full
            resize_on_calling_thread()
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

    def _assemble(self, samples: List[Dict[str, np.ndarray]], pad_to: int) -> dict:
        return assemble_batch(samples, pad_to, accum_steps=self.accum_steps,
                              local_batch_size=self.local_batch_size)

    def _new_rows(self, n: int) -> Dict[str, np.ndarray]:
        """One fresh buffer a key for a batch of `n` real samples, `mask`
        included where `n` falls short (the padded val tail: unused rows
        zero, as `assemble_batch` pads). Never reused: a yielded batch may
        still be read by an asynchronous transfer, or aliased by a CPU
        `device_put`."""
        spy = self.samples_per_yield
        make = np.empty if n == spy else np.zeros
        rows = {k: make((spy, *shape), dtype)
                for k, (shape, dtype) in self._row_spec.items()}
        if n < spy:
            rows["mask"] = np.zeros(spy, np.float32)
            rows["mask"][:n] = 1.0
        return rows

    def _shaped(self, rows: Dict[str, np.ndarray]) -> dict:
        """The filled buffers as the batch the step takes."""
        if self.accum_steps == 1:
            return rows
        return {k: v.reshape(self.accum_steps, self.local_batch_size,
                             *v.shape[1:]) for k, v in rows.items()}

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

        source, writes_rows = self.source, self._source_writes_rows
        rows_in_place, rows_copied = _row_counters()

        def decode(i: int, rows: Dict[str, np.ndarray]) -> bool:
            # obs "decode" span: per-sample decode+transform wall time on
            # the worker threads (background-classed — it overlaps the
            # consumer loop, so it informs, never sums into, window wall).
            # True: the source wrote every row itself
            with obs.span("decode"):
                sample = (source.get(i, epoch, out=rows) if writes_rows
                          else source.get(i, epoch))
                return _fill_rows(rows, sample)

        def submit(b: int) -> tuple:
            chunk = indices[b * spy : (b + 1) * spy]
            first = None
            if self._row_spec is None:
                # the first sample this loader ever produces tells the
                # rows' shapes and types; made here, then copied into its
                # row like any sample a source returns as its own arrays
                with obs.span("decode"):
                    first = source.get(int(chunk[0]), epoch)
                self._row_spec = {k: (np.shape(v), np.asarray(v).dtype)
                                  for k, v in first.items()}
            rows = self._new_rows(len(chunk))
            skip = 0
            if first is not None:
                _fill_rows(_row_views(rows, 0), first)
                skip = 1
            futures = [self._pool.submit(decode, int(i), _row_views(rows, r))
                       for r, i in enumerate(chunk[skip:], skip)]
            return b, rows, len(chunk), futures

        # per-sample futures of up to `depth` batches ahead, straight on the
        # decode pool (FIFO: a batch's samples start before the next one's,
        # and the pool never idles between batches while the window is open)
        pending: deque = deque()
        depth = max(self.prefetch_batches, 1)
        next_submit = start_state.position
        try:
            while next_submit < n_batches and len(pending) < depth:
                pending.append(submit(next_submit))
                next_submit += 1
            while pending:
                b, rows, n, futures = pending[0]
                # obs "batch" span: one per yielded batch, on the thread
                # that advances this generator: the wait for the batch's
                # rows (its samples' decodes run on the pool's threads)
                with obs.span("batch"):
                    in_place = sum(f.result() for f in futures)
                pending.popleft()
                rows_in_place.inc(in_place)
                rows_copied.inc(n - in_place)
                if next_submit < n_batches:
                    pending.append(submit(next_submit))
                    next_submit += 1
                yield self._shaped(rows), LoaderState(epoch=epoch,
                                                      position=b + 1)
            yield None, LoaderState(epoch=epoch + 1, position=0)
        finally:
            # early exit (limit_train_batches break -> GeneratorExit, an
            # exception upstream, or a sample that failed): the queued
            # samples of the batches ahead would keep decoding after the
            # consumer is gone. Cancel everything that has not started
            for _b, _rows, _n, futures in pending:
                for f in futures:
                    f.cancel()

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

"""Data-plane comparison: local loader vs N remote decode workers.

Same source, same seed, same epoch geometry — the ONLY variable is where
decode happens. The consumer simulates a trainer (a fixed busy-step per
batch) and the lane measures what an operator needs to compare:

- ``dataplane_cps`` / ``local_cps``: end-to-end clips/sec of each path;
- ``dataplane_input_wait_frac`` / ``local_input_wait_frac``: fraction of
  the consume loop blocked waiting for the next batch (the same reading
  `obs/input_wait_frac` gives the real trainer — → 0 means the decode
  plane outruns the consumer);
- ``parity``: the remote batch stream is byte-identical to the local one
  (hash-compared; the non-negotiable correctness gate).

The local loader runs ONE decode worker thread and the remote path runs N
worker PROCESSES, so the comparison shows the actual lever: horizontal
decode scale-out on a fixed trainer host. Host-CPU numbers —
trustworthy on any box, never device claims.

Also the analyze.sh gate step (``python -m
pytorchvideo_accelerate_tpu.dataplane.bench --smoke``): exit 1 on a parity
break or on remote input-wait materially worse than local.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from typing import Callable, List, Optional

from pytorchvideo_accelerate_tpu.data.pipeline import ClipLoader
from pytorchvideo_accelerate_tpu.dataplane import spec as spec_mod
from pytorchvideo_accelerate_tpu.dataplane.feed import RemoteClipFeed

# remote wait must not exceed local wait by more than this (timing noise
# allowance; with N>=2 workers vs 1 local decode thread the remote side is
# structurally ahead, and --smoke asserts it stays that way)
WAIT_FRAC_TOLERANCE = 0.05


def batch_digest(batch: dict) -> str:
    """sha1 over sorted keys + dtype + shape + raw bytes — THE definition
    of 'byte-identical batch stream' (this lane and chaos leg 13 must
    agree on it, so both import this one)."""
    h = hashlib.sha1()
    for key in sorted(batch):
        arr = batch[key]
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _consume(items, step_s: float) -> dict:
    """Drain one epoch_items pass with a simulated train step per batch;
    returns digests + wait/throughput accounting."""
    digests: List[str] = []
    clips = 0
    wait_s = 0.0
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        batch, _state = next(items)
        wait_s += time.perf_counter() - t0
        if batch is None:
            break
        digests.append(batch_digest(batch))
        clips += int(next(iter(batch.values())).shape[0])
        if step_s:
            # sleep, not busy-wait: a real train step runs ON the
            # accelerator — the trainer host is idle while it computes, and
            # a spinning consumer would starve the very decode processes
            # this lane measures (observed 3x distortion on a 2-core host)
            time.sleep(step_s)
    wall = time.perf_counter() - t_start
    return {"digests": digests, "clips": clips, "wall_s": wall,
            "wait_s": wait_s,
            "wait_frac": min(wait_s / wall, 1.0) if wall > 0 else 0.0,
            "cps": round(clips / wall, 2) if wall > 0 else 0.0}


def run_dataplane_bench(smoke: bool = True, workers: int = 2,
                        step_s: Optional[float] = None, trials: int = 3,
                        deadline_s: Optional[float] = None,
                        log: Optional[Callable] = None) -> dict:
    """Run the local-vs-remote comparison; returns the lane dict.

    Shape/sizing rationale: decode is made the bottleneck of the LOCAL
    path (one decode thread, ~2x the simulated step's cost per batch)
    while the remote plane carries strictly more aggregate decode capacity
    (`workers` processes x 2 threads) than the step needs — so clean runs
    land local wait_frac solidly high and remote near zero, an ORDERING
    that survives uniform background-CPU noise because it comes from
    capacity, not from a timing knife-edge. Each side still runs `trials`
    interleaved passes and reports its best (min wait_frac) — the same
    min-of-runs stance the tracer's overhead calibration takes against
    preemption outliers on shared hosts."""
    log = log or (lambda *a: None)
    n_videos = 48 if smoke else 128
    crop = 48 if smoke else 64
    frames = 8
    # sized against this host: raw 48x(144,192) clips cost ~4x the 80 ms
    # simulated step per batch through ONE decode thread (local decode
    # CANNOT hide behind the step) while `workers` processes x 2 threads
    # bring effective decode under the step (remote decode CAN) — the
    # capacity ordering the smoke gate asserts
    step_s = step_s if step_s is not None else 0.08
    tspec = dict(num_frames=frames, training=True, crop_size=crop,
                 min_short_side_scale=crop + 2,
                 max_short_side_scale=crop + 8)
    spec = spec_mod.synthetic_spec(tspec, num_videos=n_videos,
                                   num_classes=4, seed=17, raw_frames=48,
                                   raw_size=[144, 192])

    def make_loader() -> ClipLoader:
        return ClipLoader(spec_mod.build_source(spec), global_batch_size=4,
                          shuffle=True, num_workers=1, prefetch_batches=2,
                          seed=17)

    out: dict = {"dataplane_workers": int(workers), "num_videos": n_videos,
                 "step_s": step_s, "trials": int(trials)}

    def run_local() -> dict:
        loader = make_loader()
        try:
            return _consume(loader.epoch_items(0, from_start=True), step_s)
        finally:
            loader.close()

    def run_remote() -> dict:
        loader = make_loader()
        feed = RemoteClipFeed(loader, spec, spawn=int(workers), credits=2,
                              decode_threads=2, batch_timeout_s=120.0)
        try:
            res = _consume(feed.epoch_items(0, from_start=True), step_s)
            res["stats"] = feed.stats()
            return res
        finally:
            feed.close()
            loader.close()

    locals_, remotes = [], []
    t_deadline = (time.monotonic() + deadline_s) if deadline_s else None
    for i in range(max(int(trials), 1)):  # interleaved: noise hits both
        if t_deadline and i and time.monotonic() > t_deadline:
            # cooperative self-bound: a caller that abandons this function
            # from outside (a harness Future timeout) cannot stop the
            # thread — it must stop ITSELF, or it keeps spawning worker
            # processes under whatever the harness measures next
            log(f"[dataplane] deadline hit after {i} trial(s); "
                "using what completed")
            break
        locals_.append(run_local())
        remotes.append(run_remote())
    local = min(locals_, key=lambda r: r["wait_frac"])
    remote = min(remotes, key=lambda r: r["wait_frac"])
    out["local_cps"] = local["cps"]
    out["local_input_wait_frac"] = round(local["wait_frac"], 4)
    out["dataplane_cps"] = remote["cps"]
    out["dataplane_input_wait_frac"] = round(remote["wait_frac"], 4)
    out["stats"] = remote["stats"]
    out["batches"] = len(local["digests"])
    # parity is checked on EVERY pass, not the best one — byte identity is
    # the correctness gate, noise-independent by construction
    out["parity"] = (len(local["digests"]) > 0
                     and all(r["digests"] == local["digests"]
                             for r in locals_ + remotes))
    log(f"[dataplane] {workers} remote workers: "
        f"{out['dataplane_cps']} clips/s (wait_frac "
        f"{out['dataplane_input_wait_frac']}) vs local "
        f"{out['local_cps']} clips/s (wait_frac "
        f"{out['local_input_wait_frac']}); parity={out['parity']}")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m pytorchvideo_accelerate_tpu.dataplane.bench",
        description="DATA_PLANE lane: local loader vs N remote decode "
                    "workers on the same source/seed "
                    "(docs/INPUT_PIPELINE.md § disaggregated data plane)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + gate asserts (the analyze.sh/CI "
                         "lane): parity must hold and remote input-wait "
                         "must be no worse than local")
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    # the CLI is the analyze.sh/CI gate: always the smoke shapes;
    # --smoke toggles nothing and is kept for flag symmetry with the
    # other gate tools
    out = run_dataplane_bench(smoke=True, workers=args.workers, log=log)
    print(json.dumps({k: v for k, v in out.items() if k != "stats"}))
    if not out["parity"]:
        log("[dataplane] FAIL: remote batch stream diverged from local")
        return 1
    if (out["dataplane_input_wait_frac"]
            > out["local_input_wait_frac"] + WAIT_FRAC_TOLERANCE):
        log("[dataplane] FAIL: remote input-wait worse than local "
            f"({out['dataplane_input_wait_frac']} vs "
            f"{out['local_input_wait_frac']})")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

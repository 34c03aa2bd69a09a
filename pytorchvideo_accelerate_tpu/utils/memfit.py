"""Compile-time batch-size fitting from XLA's own memory accounting.

On a fixed-HBM chip (v5e: 16 GiB) the largest per-chip batch is a hard
deployment parameter, and discovering it by OOM-crashing training jobs is
the GPU-era workflow. XLA knows the answer at compile time:
`compiled.memory_analysis()` reports argument/output/temp/alias bytes for
the exact train-step executable — no step needs to run, and (unlike an OOM
probe) the compile-only estimate on the CPU backend needs no chip at all.

Estimate = arguments + outputs + temps − aliased (donated state buffers
are reused in-place). CPU-backend compiles approximate the TPU numbers
(same logical buffers; TPU tile padding adds a few percent — `margin`
covers it). `find_max_batch` bisects to the largest batch whose estimate
fits the budget.

CLI: python -m pytorchvideo_accelerate_tpu.utils.memfit --model x3d_s \
         --frames 13 --crop 160 [--hbm_gib 16] [--accum 1]
"""

from __future__ import annotations

import json
import sys
from typing import Callable, Optional, Tuple


def step_memory_bytes(model_name: str, batch: int, frames: int, crop: int,
                      num_classes: int = 700, accum: int = 1,
                      overrides: Optional[dict] = None,
                      input_u8: bool = False) -> dict:
    """Compile the train step at `batch` (per chip) and return XLA's
    memory accounting in bytes. Compile-only: nothing executes.
    Pretrain models (videomae_b_pretrain) are handled via the shared
    setup's pretrain branch. `input_u8=False` (default) sizes the fp32
    clip layout — conservative vs the u8-ingest path (whose inputs are
    4x smaller); pass True to fit the `--data.host_cast u8` config."""
    import jax

    from pytorchvideo_accelerate_tpu.utils.bench_setup import build_step_setup

    setup = build_step_setup(
        model_name, frames=frames, crop=crop, batch_per_chip=batch,
        num_classes=num_classes, accum=accum, overrides=overrides,
        devices=jax.devices()[:1], fill="zeros",  # compile-only: no RNG cost
        input_u8=input_u8,
    )
    compiled = setup.step.lower(
        setup.state, setup.device_batch(0), jax.random.key(0)).compile()
    ma = compiled.memory_analysis()
    out = {
        "batch_per_chip": batch,
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    out["estimate_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             + out["temp_bytes"] - out["alias_bytes"])
    out["peak_bytes"] = int(ma.peak_memory_in_bytes)
    return out


def find_max_batch(measure: Callable[[int], int], budget_bytes: int,
                   max_batch: int = 1024) -> Tuple[int, list]:
    """Largest b in [1, max_batch] with measure(b) <= budget_bytes.

    Doubles until overflow, then bisects; `measure` is called O(log n)
    times (each call is a compile). Returns (best, probes) where probes is
    [(batch, bytes)]; best == 0 when even batch 1 overflows."""
    probes = []

    def fits(b):
        n = measure(b)
        probes.append((b, n))
        return n <= budget_bytes

    if not fits(1):
        return 0, probes
    lo = 1  # largest known-fitting
    hi = None  # smallest known-overflowing
    b = 2
    while hi is None and b <= max_batch:
        if fits(b):
            lo = b
            b *= 2
        else:
            hi = b
    if hi is None:
        # doubling passed the cap without overflowing: the answer may be
        # anywhere in (lo, max_batch] — probe the cap itself, bisect only
        # on failure (a power-of-two-only answer would understate up to 2x)
        if lo == max_batch:
            return lo, probes
        if fits(max_batch):
            return max_batch, probes
        hi = max_batch
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid
    return lo, probes


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="slowfast_r50")
    ap.add_argument("--frames", type=int, default=32)
    ap.add_argument("--crop", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--num_classes", type=int, default=700)
    ap.add_argument("--hbm_gib", type=float, default=16.0,
                    help="per-chip HBM budget (v5e: 16)")
    ap.add_argument("--margin", type=float, default=0.9,
                    help="use margin*hbm as the budget (tile padding, "
                         "runtime reserves, CPU-compile underestimate)")
    ap.add_argument("--max_batch", type=int, default=512)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU-backend compile (needs no chip; "
                         "estimates are approximate)")
    ap.add_argument("--inputs", choices=("f32", "u8"), default="f32",
                    help="clip staging to size: f32 (conservative default) "
                         "or the --data.host_cast u8 ingest layout")
    args = ap.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.accum > args.max_batch:
        ap.error(f"--accum {args.accum} exceeds --max_batch {args.max_batch}: "
                 "even one sample per micro-step would overshoot the cap")

    budget = int(args.hbm_gib * args.margin * (1 << 30))

    # with grad accumulation the effective batch must divide into accum
    # micro-steps: bisect over the MICRO batch k, measure k*accum
    def measure(k):
        r = step_memory_bytes(args.model, k * args.accum, args.frames,
                              args.crop, args.num_classes, args.accum,
                              input_u8=args.inputs == "u8")
        print(json.dumps(r), file=sys.stderr, flush=True)
        return r["estimate_bytes"]

    best_micro, probes = find_max_batch(
        measure, budget, max(args.max_batch // args.accum, 1))
    print(json.dumps({
        "model": args.model, "frames": args.frames, "crop": args.crop,
        "accum": args.accum, "hbm_gib": args.hbm_gib, "margin": args.margin,
        "budget_bytes": budget,
        "max_batch_per_chip": best_micro * args.accum,
        "micro_batch_per_chip": best_micro,
        "probes": [{"batch": k * args.accum, "bytes": n} for k, n in probes],
        "backend": jax.devices()[0].platform,
    }))


if __name__ == "__main__":
    main()

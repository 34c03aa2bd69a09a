#!/usr/bin/env python
"""Which span does a long device-idle gap fall in, and is it the profiler's?

One cell's `Trainer.fit()` for a fixed number of steps, with or without a
capture window (`--profile-steps A..B`, the trainer's one capture path), then
`fit()`'s per-iteration records read beside the trace:

* every `pva/*` annotation and the loop's `train` step annotation, by thread;
* the step program's executions on the chip (`XLA Modules`) and the gaps
  between them; for the longest gaps, the loop-thread spans that cover them
  (name, step, milliseconds) and how many steps after `start_trace` returned
  (`pva/capture`) they began;
* each record's `time.time_ns()` start against its `pva/iter` annotation's
  (one clock);
* the host events the profiler recorded a step, and how many are `pva/*`;
* the longest iterations by the records alone, which need no profiler: the
  same run without `--profile-steps` says whether such an iteration exists
  untraced;
* the steady iterations' breakdown per step (`per_step_ms`: `input_wait`,
  `step`, `log`, `iter` self, the ring's fill at the pop) and the workers'
  side of the same steps from the per-window `obs` logs (`per_window`:
  `batch`, `decode`, `h2d` per step, and the share of batch rows that the
  clip source wrote in place).

    python scripts/trace_gap_probe.py --workload x3d_s.train --steps 130 \\
        --profile-steps 50..90 --out chiprun_out/gap_probe_traced.json

The configuration is the benchmark cell's (`benchmarks/`), so the numbers sit
beside the cell's. `--rehearse` runs the cell's toy geometry on the CPU to
walk the script; nothing it prints then is a device number. Without it the
script needs a TPU, as `benchmarks/run.py` does, and writes nothing otherwise.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STEP_PROGRAM = "jit_step"
LONG_ITER_S = 0.3  # an iteration this long is the gap the ledger shows


def read_trace(path):
    """({(name, thread): [(start_ns, end_ns, step_num)]} of the host's `pva/*`
    and `train` annotations, the host's event count, [(start, end)] of the
    step program on the first chip, profile_start_time in epoch ns).

    The planes, lines and names are `benchmarks/lib/xtrace.py`'s. Its `load`
    is not called: it keeps only `xtrace.HOST_NAMES` of the host's events and
    neither their thread nor the trace's start time (the next `benchmark`
    issue widens it to `pva/*`, ROADMAP.md), and this needs all three."""
    from jax.profiler import ProfileData

    from benchmarks.lib import xtrace

    host, host_events, device_steps, start_ns = {}, 0, [], None
    for plane in ProfileData.from_file(path).planes:
        chip = xtrace.DEVICE_PLANE.match(plane.name)
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time")
        elif plane.name == xtrace.HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    host_events += 1
                    if ev.name.startswith("pva/") or ev.name == "train":
                        host.setdefault((ev.name, thread), []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             dict(ev.stats).get("step_num")))
        elif chip and int(chip.group(1)) == 0:
            for line in plane.lines:
                if line.name == xtrace.MODULES_LINE:
                    device_steps = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if STEP_PROGRAM in ev.name)
    return host, host_events, device_steps, start_ns


def analyse(trace_path, records):
    from benchmarks.lib import xtrace

    host, host_events, device_steps, start_ns = read_trace(trace_path)
    by_name = {}
    for (name, thread), events in host.items():
        by_name.setdefault(name, {})[thread] = len(events)
    # by start: where the window crosses an epoch's end, the asking that
    # found no batch left a `pva/iter` with the next step's number before
    # that step's own, and the later one is the iteration
    iters = {step: (thread, a, b) for a, b, step, thread in sorted(
        (a, b, step, thread) for (name, thread), evs in host.items()
        if name == "pva/iter" for a, b, step in evs)}
    loop_thread = next(iter(iters.values()))[0] if iters else None
    capture = min((a for (name, _t), evs in host.items()
                   if name == "pva/capture" for a, _b, _s in evs),
                  default=None)
    by_gstep = {r["gstep"]: r for r in records}
    clock_gap_ms = [abs(by_gstep[step]["t0_ns"] - (start_ns + a)) / 1e6
                    for step, (_t, a, _b) in iters.items()
                    if step in by_gstep and start_ns is not None]
    traced_steps = sorted(s for _a, _b, s in host.get(("train", loop_thread), [])
                          if s is not None)

    gaps = sorted(((b0, a1) for (_a0, b0), (a1, _b1)
                   in zip(device_steps, device_steps[1:])),
                  key=lambda g: g[0] - g[1])[:3]
    longest = []
    for g0, g1 in gaps:
        covering = []
        for (name, thread), evs in host.items():
            if thread != loop_thread:
                continue
            for a, b, step in evs:
                ms = xtrace.overlap(a, b, g0, g1) / 1e6
                if ms > 0:
                    covering.append({"span": name, "step": step,
                                     "overlap_ms": ms,
                                     "span_ms": (b - a) / 1e6})
        covering.sort(key=lambda c: -c["overlap_ms"])
        dispatched = [a for a, _b, _s in host.get(("train", loop_thread), [])
                      if capture is not None and capture <= a <= g0]
        longest.append({
            "gap_ms": (g1 - g0) / 1e6,
            "begins_s_after_capture": (None if capture is None
                                       else (g0 - capture) / 1e9),
            "steps_dispatched_after_capture": len(dispatched),
            "executions_before_it": sum(1 for _a, b in device_steps if b <= g0),
            "loop_thread_spans": covering[:6],
        })
    n_traced = max(len(traced_steps), 1)
    return {
        "annotations_by_thread": by_name, "loop_thread": loop_thread,
        "traced_steps": [traced_steps[0], traced_steps[-1]] if traced_steps else [],
        "step_num_matches_record": all(step in by_gstep for step in iters),
        "record_vs_annotation_start_ms_max": max(clock_gap_ms, default=None),
        "device_executions": len(device_steps),
        "device_gaps_ms_longest": longest,
        "host_events": host_events,
        "host_events_per_step": host_events / n_traced,
        "pva_events_per_step": sum(len(evs) for (name, _t), evs in host.items()
                                   if name.startswith("pva/")) / n_traced,
    }


def long_iterations(records, skip):
    """The steady-state iterations (after `skip`) by the records alone."""
    steady = [r for r in records if r["gstep"] >= skip]
    top = sorted(steady, key=lambda r: -r["iter"])[:5]
    iters = sorted(r["iter"] for r in steady)
    return {
        "steady_records": len(steady),
        "iter_ms_median": 1000 * iters[len(iters) // 2] if iters else None,
        "iter_ms_max": 1000 * iters[-1] if iters else None,
        "iterations_over_0.3s": sum(r["iter"] >= LONG_ITER_S for r in steady),
        "longest": [{k: (round(1000 * v, 3) if k in ("iter", "input_wait",
                                                     "step", "log") else v)
                     for k, v in r.items() if k != "t0_ns"} for r in top],
    }


def per_step(records, skip):
    """Mean and median per steady iteration, ms: where the loop's time goes
    (`self` is `iter` less its three children), and the prefetch ring's fill
    when the loop asked."""
    steady = [r for r in records if r["gstep"] >= skip]
    if not steady:
        return {}
    cols = {k: [1000 * r[k] for r in steady]
            for k in ("iter", "input_wait", "step", "log")}
    cols["self"] = [1000 * (r["iter"] - r["input_wait"] - r["step"] - r["log"])
                    for r in steady]
    ready = [r["ready"] for r in steady]
    out = {k: {"mean": sum(v) / len(v), "median": sorted(v)[len(v) // 2]}
           for k, v in cols.items()}
    out["ready"] = {"mean": sum(ready) / len(ready),
                    "pops_with_ring_empty": sum(r == 0 for r in ready),
                    "pops": len(ready)}
    return out


def per_window(work_dir, skip):
    """The worker threads' spans of the steady log windows, ms per step, from
    the jsonl tracker's `obs/*` lines; `batch` is the loader's wait for a
    batch's rows, `decode` the pool's threads summed (over `num_workers`: the
    least a batch can take), `h2d` the placement; and the trace-time site
    gauges the first window logged (`obs/*_sites`)."""
    lines = []
    for path in glob.glob(os.path.join(work_dir, "runs", "*.jsonl")):
        with open(path) as f:
            lines += [json.loads(line) for line in f]
    windows = sorted((v for v in lines if "obs/window_wall_s" in v),
                     key=lambda v: v["step"])
    steady = [(b["step"] - a["step"], b) for a, b in zip(windows, windows[1:])
              if a["step"] >= skip]
    steps = sum(n for n, _v in steady)
    if not steps:
        return {}
    out = {"windows": len(steady), "steps": steps}
    out.update({k[4:]: v[k] for v in windows for k in v
                if k.startswith("obs/") and k.endswith("_sites")})
    for name in ("batch", "decode", "h2d"):
        out[f"{name}_ms_per_step"] = 1000 * sum(
            v.get(f"obs/{name}_s", 0.0) for _n, v in steady) / steps
    shares = [v["obs/loader_rows_in_place_share"] for _n, v in steady
              if "obs/loader_rows_in_place_share" in v]
    if shares:
        out["loader_rows_in_place_share"] = {"min": min(shares),
                                             "max": max(shares)}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="x3d_s.train")
    ap.add_argument("--steps", type=int, default=130)
    ap.add_argument("--profile-steps", default="")
    ap.add_argument("--seed", type=int, default=2500013)
    ap.add_argument("--skip", type=int, default=30,
                    help="records before this step are compile and warm-up")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmarks.jobs import train_fit
    from benchmarks.lib.spec import Spec
    from benchmarks.run import require_devices
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer
    from pytorchvideo_accelerate_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    # a time from the CPU is no device number: like the benchmark's command,
    # exit non-zero and write nothing where there is no TPU
    require_devices(cell["chips"], args.rehearse)
    enable_compile_cache()
    work_dir = os.path.join(ROOT, ".bench_work", "gap_probe")
    shutil.rmtree(work_dir, ignore_errors=True)
    cfg = train_fit.build_config(spec.config(cell["config"]), cell, args.seed,
                                 work_dir, args.rehearse)
    cfg.data.limit_train_batches = args.steps
    cfg.obs.profile_steps = args.profile_steps
    t0 = time.perf_counter()
    fit = Trainer(cfg).fit()
    records = fit["step_records"]
    import jax

    out = {"workload": args.workload, "profile_steps": args.profile_steps,
           "device": jax.devices()[0].device_kind, "steps": fit["steps"],
           "fit_and_setup_s": time.perf_counter() - t0,
           "rehearsal": bool(args.rehearse),
           "records": long_iterations(records, args.skip),
           "per_step_ms": per_step(records, args.skip),
           "per_window": per_window(work_dir, args.skip)}
    if args.profile_steps:
        pbs = sorted(glob.glob(os.path.join(work_dir, "profile_steps_*", "**",
                                            "*.xplane.pb"), recursive=True))
        if not pbs:
            raise SystemExit("the capture published no trace")
        out["trace_bytes"] = os.path.getsize(pbs[-1])
        out["trace"] = analyse(pbs[-1], records)
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
        with open(os.path.splitext(args.out)[0] + ".records.jsonl", "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in records)
    print(text)
    shutil.rmtree(work_dir, ignore_errors=True)


if __name__ == "__main__":
    main()

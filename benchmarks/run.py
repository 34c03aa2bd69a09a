"""Run one cell of the benchmark once, in this process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with `--trace 0`, its
per-layer metrics with `--trace 1`), `device`, with `--trace 1` `breakdown`,
and last `compared`: every number that decided `correct` beside its limit
(also the last lines of standard error). Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.

`--rehearse` lifts the TPU requirement and runs the cell's toy geometry (the
`rehearse` groups of its files) to walk the whole command on the CPU; its
last line says `"rehearsal": true` and no number in it is a device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--stand-in", action="append", default=[],
                    choices=("control", "half_batch", "state_unchanged"),
                    help="not for benchmark runs: also judge the reference "
                         "put in the program's place, on three weight seeds, "
                         "in the control's precision or with a fault planted "
                         "(PERF.md, limits)")
    return ap.parse_args(argv)


def require_devices(chips, rehearse):
    """The devices the cell runs on; exits non-zero where there is no TPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise SystemExit(f"benchmark needs a TPU; JAX found {platform!r} "
                         f"({len(devices)} device(s)). No result.")
    if len(devices) < chips:
        raise SystemExit(f"cell needs {chips} chip(s); JAX found "
                         f"{len(devices)}. No result.")
    return devices


def per_layer_metrics(spec, cell_name, results):
    """Every per-layer metric of this cell whose reader finds something."""
    from benchmarks.lib.spec import metric_module

    out = {}
    for name in spec.metric_names("per_layer", cell_name):
        value = metric_module(name).read(results)
        if value is not None:
            out[name] = {"value": value, "unit": spec.metric(name)["unit"]}
    return out


def main(argv=None, t_start=None):
    args = parse(argv)
    t_start = T_START if t_start is None else t_start
    from benchmarks.lib.spec import Spec, job_module, quantity

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    seconds = args.seconds if args.seconds is not None else spec.doc["run_seconds"]

    # the program's package has to be there: a directory that holds only the
    # benchmark's files ends here, non-zero and with no result
    from pytorchvideo_accelerate_tpu.utils.compile_cache import enable_compile_cache

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if int(cell["chips"]) > 1:
            flag = f"--xla_force_host_platform_device_count={cell['chips']}"
            if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax

    enable_compile_cache()
    # cache every program, however fast it compiled, so that a second run of
    # a cell compiles nothing; and evict nothing: a cell's programs with the
    # reference's are 120-250 MB, and under a size limit from the environment
    # (the chip tool's machines set 192 MiB) JAX's LRU eviction drops one
    # run's executables while the next are written (PERF.md, Findings)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from benchmarks.lib.compile_counters import CompileCounters

    counters = CompileCounters()
    devices = require_devices(int(cell["chips"]), args.rehearse)

    work_dir = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir, exist_ok=True)
    ctx = {"spec": spec, "cell": cell, "config": config, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace),
           "rehearse": args.rehearse, "t_start": t_start, "work_dir": work_dir,
           "counters": counters,
           "stand_ins": {name: {"control": {"q": "control"},
                                "half_batch": {"fault": "half_batch"},
                                "state_unchanged": {"fault": "state_unchanged"}}[name]
                         for name in args.stand_in}}
    out = job_module(cell["job"]).run(ctx)
    shutil.rmtree(work_dir, ignore_errors=True)

    if args.trace:
        metrics = per_layer_metrics(spec, args.workload, out["results"])
    else:
        metrics = {name: {"value": out["end_to_end"][quantity(name)],
                          "unit": spec.metric(name)["unit"]}
                   for name in spec.metric_names("end_to_end", args.workload)}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"],
              **out["device_extra"]}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out["breakdown"]:
        line["breakdown"] = out["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    if out.get("stand_ins"):
        line["stand_ins"] = {
            name: {n["name"]: n["value"] for n in numbers}
            for name, numbers in out["stand_ins"].items()}
    line["compared"] = {n["name"]: {"value": n["value"], "limit": n["limit"]}
                        for n in out["compared"] if n["limit"] is not None}
    sys.stdout.flush()
    # shown first, the readings that decide nothing; last, those that do
    for n in sorted(out["compared"], key=lambda n: n["limit"] is not None):
        word = "observed" if n["limit"] is None else "compared"
        print(f"{word} {n['name']} value {n['value']!r} limit {n['limit']!r} "
              f"{'ok' if n['ok'] else 'NOT OK'} {n['note']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return line


if __name__ == "__main__":
    main()

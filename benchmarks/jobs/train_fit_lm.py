"""Job `train_fit_lm`: one cell of next-token training through
`Trainer.fit()`, for any family of token model.

`train_fit_tokens`' cell (its tap, its checks of the token batch, Adam's first
moment after one step, `routed_rows_gap`; all imported, nothing there is
edited), with what that job has written in for one family found by the
configuration's `family` instead, as `lib/spec.py` says files are found:

* the plain reference `benchmarks/reference/<family>.py` (`init_variables`,
  `follow`), which may also give `MODEL_SCOPES` (the scope table of a traced
  run), `STAND_INS` (what `--stand-in` may name) and `DIRECTION_LEAVES`;
  without them `train_fit_tokens`' tables are used and no direction is read;
* the work of a step `benchmarks/lib/work_<family>.py` `step_work`;
* the sizes the two read: the configuration file's top-level numbers AND its
  top-level lists of numbers (a layer pattern such as `rope_layout`), the
  toy's over them in a rehearsal.

Where the reference hands back whole leaves of its first gradient
(`grad_leaves`), the program's (Adam's first moment over 1 - b1) are held
against them: `<name>` = the worst such leaf's ||g_program - g_reference|| /
||g_reference||. A leaf's norm cannot tell apart two gradients of one size
(other keys read, another tensor routed on); its direction can.

`python benchmarks/jobs/train_fit_lm.py --stand-in window_ignored ...` takes
`run.py`'s arguments and the family's planted faults.
"""

from __future__ import annotations

import gc
import glob
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:  # run as a script (the stand-ins): see the docstring
    sys.path.insert(0, ROOT)

from benchmarks.jobs import train_fit_tokens as tokens_job  # noqa: E402
from benchmarks.jobs.train_fit import (  # noqa: E402
    STAND_IN_SEEDS,
    BenchTracker,
    _note,
    build_config,
    compiled_step_scopes,
    give_weights,
    memory_peak_bytes,
)
from benchmarks.jobs.train_fit_tokens import (  # noqa: E402
    ADAM_B1,
    TokenTap,
    delta_norms,
    program_grad_norms,
    routed_rows_gap,
    token_input_numbers,
)
from benchmarks.lib import compare, xtrace  # noqa: E402

EXTRA_STAND_INS = {}  # set by `main`: the stand-ins this run also judges


def family_modules(config):
    """(reference, work) of the configuration's family, by name."""
    family = config["family"]
    return (importlib.import_module(f"benchmarks.reference.{family}"),
            importlib.import_module(f"benchmarks.lib.work_{family}"))


def _is_number(v):
    return isinstance(v, (int, float, bool)) and not isinstance(v, str)


def arch_of(config, rehearse):
    """The sizes the reference and the work count read: the configuration
    file's top-level numbers and lists of numbers (the published config.json's
    names, with the share held here), the toy's over them in a rehearsal."""
    arch = {k: v for k, v in config.items()
            if _is_number(v) or (isinstance(v, list) and v
                                 and all(_is_number(x) for x in v))}
    if rehearse:
        arch.update(config["rehearse"]["arch"])
    return arch


def follow_reference(ref_lib, arch, optim, seed, batches, device, q=None,
                     fault=None, note=None):
    import jax

    with jax.default_device(device):
        params0 = jax.device_put(ref_lib.init_variables(arch, seed)["params"],
                                 device)
        return ref_lib.follow(arch, optim, params0,
                              (jax.device_put(b, device) for b in batches),
                              q=q, fault=fault, note=note)


def direction_gaps(program_leaves, ref, names):
    """{reading: worst leaf's ||program - reference|| / ||reference||} over
    the leaves of the reference's `grad_leaves` whose path holds the
    reading's part (`names`: reading -> part). inf where the program has no
    such leaf."""
    import numpy as np

    out = {}
    for reading, part in names.items():
        worst = 0.0
        for path, want in (ref.get("grad_leaves") or {}).items():
            if part not in path.split("/"):
                continue
            got = program_leaves.get(path)
            if got is None:
                worst = float("inf")
                continue
            want = np.asarray(want, np.float64)
            gap = compare._norm(np.asarray(got, np.float64) - want) \
                / max(compare._norm(want), 1e-30)
            worst = max(worst, gap if np.isfinite(gap) else float("inf"))
        out[reading] = worst
    return out


def judge(program, ref, limits, structure, names):
    """`compare.judge`'s readings plus the routing's and the directions'."""
    numbers = compare.judge(
        program["losses"], program["grad_norms"], program["delta_norms"], ref,
        limits, {"routed_rows_gap": routed_rows_gap(program["pairs"],
                                                    ref["pairs"]),
                 **structure})
    for name, value in direction_gaps(program["grad_leaves"], ref,
                                      names).items():
        limit = limits.get(name)
        numbers.append({"name": name, "value": float(value), "limit": limit,
                        "ok": bool(limit is None or value <= limit),
                        "note": "worst leaf, norm of the difference"})
    return numbers


def run(ctx):
    """Run the cell once; returns the pieces of the result line."""
    import jax

    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    spec, cell, config = ctx["spec"], ctx["cell"], ctx["config"]
    rehearse = ctx["rehearse"]
    ref_lib, work_lib = family_modules(config)
    scopes = getattr(ref_lib, "MODEL_SCOPES", tokens_job.MODEL_SCOPES)
    names = getattr(ref_lib, "DIRECTION_LEAVES", {})
    arch = arch_of(config, rehearse)
    if int(cell["chips"]) != 1:
        raise RuntimeError("train_fit_lm runs one-chip cells only")
    cfg = build_config(config, cell, ctx["seed"], ctx["work_dir"], rehearse)
    devices = jax.devices()[:1]
    global_batch, seq_len = cfg.data.batch_size, cfg.data.seq_len
    total_steps = cfg.data.synthetic_num_videos // global_batch
    optim = {"lr": cfg.optim.lr, "weight_decay": cfg.optim.weight_decay,
             "grad_clip_norm": cfg.optim.grad_clip_norm,
             "total_steps": total_steps}

    trace_dir = os.path.join(ctx["work_dir"], "trace") if ctx["trace"] else None
    log_every = cfg.tracking.log_every
    settle = cell["check_steps"] + cell["warmup_steps"]
    plan = {
        "check_steps": int(cell["check_steps"]),
        "log_every": log_every,
        "window_start": -(-settle // log_every) * log_every,
        "seconds": float(ctx["seconds"]),
        "trace_dir": trace_dir,
        "trace_seconds": min(float(cell.get("trace_seconds", 5.0)),
                             float(ctx["seconds"])),
    }
    cfg.tracking.log_every = 1  # until the window starts: every loss is logged

    trainer = Trainer(cfg)
    _note(ctx, "trainer constructed")
    give_weights(trainer, ref_lib.init_variables(arch, ctx["seed"]))
    tap = TokenTap(trainer, plan)
    trainer.train_prefetch = tap
    tracker = BenchTracker()
    trainer.trackers.trackers = trainer.trackers.trackers + [tracker]

    fit = trainer.fit()

    if tap.t0 is None or tap.t1 is None:
        raise RuntimeError("fit() returned before the window opened or closed")
    window_s = tap.t1 - tap.t0
    steps = tap.steps_in_window
    peak = memory_peak_bytes(devices, tap.live_bytes)
    _note(ctx, "fit returned", memory_stats=devices[0].memory_stats())
    compile_snapshot = ctx["counters"].snapshot()
    step_scopes = None
    if ctx["trace"]:
        step_scopes = compiled_step_scopes(trainer, tap.batch_struct)
        _note(ctx, "step's HLO text read", instructions=len(step_scopes))

    # free the program's state before the reference takes the chip
    del trainer.state
    trainer.train_step = trainer.eval_step = None
    del trainer
    gc.collect()
    jax.clear_caches()

    # --- correct ----------------------------------------------------------
    logged = {s: v for s, v in tracker.entries if "train_loss_step" in v}
    checked = range(1, plan["check_steps"] + 1)
    with jax.default_device(devices[0]):
        host_params0 = jax.device_get(
            ref_lib.init_variables(arch, ctx["seed"])["params"])
    parts = set(names.values())
    program = {
        "losses": [logged.get(i, {}).get("train_loss_step") for i in checked],
        "pairs": [logged.get(i, {}).get("moe_local_pairs") for i in checked],
        "grad_norms": program_grad_norms(tap.momentum_after_1),
        "delta_norms": delta_norms(host_params0, tap.params_after_check),
        # the first gradient's leaves the reference hands back whole
        "grad_leaves": {n: v / (1.0 - ADAM_B1) for n, v in
                        compare._flat(tap.momentum_after_1).items()
                        if parts.intersection(n.split("/"))},
    }
    del host_params0
    structure = {
        **token_input_numbers(tap.batches, global_batch, seq_len,
                              arch["vocab_size"]),
        "step_count_gap": abs(int(fit["steps"]) - (plan["window_start"] + steps)),
        "recompiles": fit.get("train_recompiles"),
    }
    limits = cell["limits"]
    if rehearse:
        limits = cell.get("rehearse", {}).get("limits", limits)
    note = lambda what: _note(ctx, what)  # noqa: E731
    reference = follow_reference(ref_lib, arch, optim, ctx["seed"],
                                 tap.batches, devices[0], note=note)
    for line in compare.size_table(program["grad_norms"],
                                   program["delta_norms"], reference):
        note(line)
    numbers = judge(program, reference, limits, structure, names)
    stand_ins = {}
    asked = {**ctx.get("stand_ins", {}), **EXTRA_STAND_INS}
    for k in range(STAND_IN_SEEDS if asked else 0):
        # not part of a benchmark run: the control and the planted faults, on
        # the same placed batches with the weights of seed, seed+1, ...
        seed_k = ctx["seed"] + k
        ref_k = reference if k == 0 else follow_reference(
            ref_lib, arch, optim, seed_k, tap.batches, devices[0])
        for name, kwargs in asked.items():
            if kwargs.get("q") == "control":
                kwargs = {**kwargs, "q": (config["rehearse"] if rehearse
                                          else config)["control_dtype"]}
            other = follow_reference(ref_lib, arch, optim, seed_k,
                                     tap.batches, devices[0], **kwargs)
            other.setdefault("grad_leaves", {})
            numbers_k = judge(other, ref_k, limits, {}, names)
            stand_ins[f"{name}@{seed_k}"] = numbers_k
            _note(ctx, f"stand-in {name} seed {seed_k}",
                  **{n["name"]: n["value"] for n in numbers_k})
    tap.batches = tap.momentum_after_1 = tap.params_after_check = None
    program = None
    _note(ctx, "reference followed", window_s=window_s, steps=steps,
          setup_s=tap.t0 - ctx["t_start"], compile=compile_snapshot,
          slowest=ctx["counters"].slowest())

    # --- metrics ----------------------------------------------------------
    clips = steps * global_batch
    in_window = [v for s, v in tracker.entries if s > plan["window_start"]]
    results = {
        "fit": fit, "window_s": window_s, "steps": steps, "clips": clips,
        "chips": 1, "global_batch": global_batch,
        "setup_s": tap.t0 - ctx["t_start"],
        "spans": tracker.span_totals(plan["window_start"]),
        "window_wait_s": tap.window_wait_s,
        "compile": compile_snapshot, "memory_peak_bytes": peak,
        "tokens_per_s": clips * seq_len / window_s,
        "counters": {"moe_expert_load_max_over_mean": [
            v["obs/moe_expert_load_max_over_mean"] for v in in_window
            if "obs/moe_expert_load_max_over_mean" in v]},
        "peaks": None, "work": None, "trace": None,
    }
    end_to_end = {"setup_s": results["setup_s"],
                  "clips_per_s_per_chip": clips / window_s}
    device_extra, breakdown = {"tokens_per_s": results["tokens_per_s"]}, None
    if ctx["trace"]:
        if not rehearse:
            results["peaks"] = spec.peaks(devices[0].device_kind)
            results["work"] = work_lib.step_work(
                arch, global_batch, seq_len,
                sum(reference["pairs"]) / len(reference["pairs"]),
                results["peaks"], config.get("bytes_per_element", 2))
        pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                               recursive=True))
        if not pbs:
            raise RuntimeError("the profiler wrote no trace")
        results["trace"] = xtrace.reduce(
            xtrace.load(pbs[-1]), step_name=cell.get("step_program", "jit_step"),
            scopes=step_scopes)
        device_extra.update(busy_s=results["trace"]["busy_s"],
                            window_s=results["trace"]["window_s"])
        breakdown = results["trace"]["breakdown"]
        traced = results["trace"]["traced_steps"]
        if breakdown is not None and traced:
            # device ms a step under each scope of the model (an op fused
            # across two scopes counts under both)
            breakdown["scope_ms_per_step"] = [
                [scope, 1e3 * xtrace.scope_seconds(
                    results["trace"]["ops"], "/" + scope) / traced]
                for scope in scopes]
    return {
        "correct": all(n["ok"] for n in numbers),
        "attempted": steps, "failed": 0,
        "end_to_end": end_to_end, "results": results,
        "memory_peak_bytes": peak, "device_extra": device_extra,
        "breakdown": breakdown, "compared": numbers, "stand_ins": stand_ins,
    }


def main(argv=None):
    """`run.py` with the family's stand-ins: the same arguments, `--stand-in`
    taking any name of the reference's `STAND_INS`."""
    import argparse

    from benchmarks import run as bench_run
    from benchmarks.jobs import train_fit_lm as job  # the copy run.py finds
    from benchmarks.lib.spec import Spec

    def parse(args):
        ap = argparse.ArgumentParser(description=main.__doc__)
        ap.add_argument("--workload", required=True)
        ap.add_argument("--seed", type=int, default=0)
        ap.add_argument("--seconds", type=float, default=None)
        ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
        ap.add_argument("--rehearse", action="store_true")
        ap.add_argument("--stand-in", action="append", default=[])
        ns = ap.parse_args(args)
        spec = Spec(ROOT)
        ref_lib, _ = family_modules(spec.config(spec.cell(ns.workload)["config"]))
        known = getattr(ref_lib, "STAND_INS", tokens_job.STAND_INS)
        unknown = [n for n in ns.stand_in if n not in known]
        if unknown:
            ap.error(f"--stand-in {unknown}: this family has {sorted(known)}")
        job.EXTRA_STAND_INS = {name: known[name] for name in ns.stand_in}
        ns.stand_in = []  # run.py's own table does not know them all
        return ns

    bench_run.parse = parse
    return bench_run.main(argv, t_start=bench_run.T_START)


if __name__ == "__main__":
    main()

"""Job `train_fit_tokens`: one cell of next-token training, through
`Trainer.fit()`.

The same harness as `train_fit` (its `FitTap`, `BenchTracker`,
`build_config`, `give_weights`, `compiled_step_scopes`, `memory_peak_bytes`
are imported, nothing there is edited): one `Trainer`, the benchmark's seeded
weights, one `fit()` call whose window drives `SyntheticTokenSource` ->
`ClipLoader` -> `DevicePrefetcher` -> the jitted next-token step -> AdamW ->
the deferred logger. What differs is what a token cell has to read:

* the optimizer is AdamW, so the first gradient is read from Adam's first
  moment after one step (mu / (1 - b1): the gradient as the optimizer got it,
  after the global-norm clip) and the reference writes AdamW out;
* the batch is {"tokens": (B, T) int32}: shape, type, ids inside the held
  vocabulary slice, no two rows alike;
* the program's count of (token, held expert) pairs of each checked step
  (the step's own `moe_local_pairs`, through the logger) against the
  reference's routing of the same batches (`routed_rows_gap`);
* the work of a step comes from `lib/work_qwen3_next.py`, with the expert
  products at the reference's routed rows; a clip is one sequence.

`python benchmarks/jobs/train_fit_tokens.py --stand-in experts_skipped ...`
takes `run.py`'s arguments and this job's further planted faults
(`STAND_INS`), which `run.py`'s own parser does not know.
"""

from __future__ import annotations

import gc
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:  # run as a script (the stand-ins): see the docstring
    sys.path.insert(0, ROOT)

from benchmarks.jobs.train_fit import (  # noqa: E402
    STAND_IN_SEEDS,
    BenchTracker,
    FitTap,
    _note,
    build_config,
    compiled_step_scopes,
    give_weights,
    memory_peak_bytes,
)
from benchmarks.lib import compare, xtrace  # noqa: E402
from benchmarks.lib import work_qwen3_next as work_lib  # noqa: E402

# what `--stand-in` may name here: `run.py`'s three and this job's own
STAND_INS = {
    "control": {"q": "control"},
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
    "experts_skipped": {"fault": "experts_skipped"},
    "bf16_router": {"fault": "bf16_router"},
    "bf16_state": {"fault": "bf16_state"},
}
# the model's scopes as patterns over an op's name stack; under `moe/` the
# layer's `cond` puts its branch's name between
_MOE = "moe/(?:[^| ]+/)?"
MODEL_SCOPES = ("gdn/in_proj/", "gdn/conv/", "gdn/scan/", "gdn/out/",
                "attn/qkv/", "attn/core/", "attn/out/", "moe/router/",
                _MOE + "dispatch/", _MOE + "experts/", _MOE + "combine/",
                "moe/shared/", "lm_head/", "loss/")
EXTRA_STAND_INS = {}  # set by `main`: the stand-ins this run also judges
ADAM_B1 = 0.9


def find_adam_mu(opt_state):
    """Adam's first moment (optax `ScaleByAdamState.mu`) inside an optimizer
    state built as chain(clip_by_global_norm, adamw)."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for sub in opt_state:
            found = find_adam_mu(sub)
            if found is not None:
                return found
    return None


class TokenTap(FitTap):
    """`FitTap`, reading Adam's first moment where it reads SGD's buffer."""

    def on_boundary(self, index, batch):
        super().on_boundary(index, batch)
        if index == 1:
            import jax

            self.momentum_after_1 = jax.device_get(
                find_adam_mu(self.trainer.state.opt_state))


def arch_of(config, rehearse):
    """The sizes the reference and the work count read: the configuration
    file's top-level keys (the published config.json's names, with the share
    held here), the toy's over them in a rehearsal."""
    arch = {k: v for k, v in config.items()
            if isinstance(v, (int, float, bool)) and not isinstance(v, str)}
    if rehearse:
        arch.update(config["rehearse"]["arch"])
    return arch


def token_input_numbers(batches, batch_size, seq_len, vocab_size):
    """The placed batches against what the files state: (B, T) int32 under
    `tokens` and nothing else, ids inside [0, vocab), no two rows alike."""
    import numpy as np

    shape_gap = range_out = dup = 0
    seen = set()
    for batch in batches:
        x = batch.get("tokens")
        if (x is None or set(batch) != {"tokens"}
                or tuple(x.shape) != (batch_size, seq_len)
                or str(x.dtype) != "int32"):
            shape_gap += 1
            continue
        x = np.asarray(x)
        range_out += int(np.sum((x < 0) | (x >= vocab_size)))
        for row in x:
            h = hash(row.tobytes())
            dup += h in seen
            seen.add(h)
    return {"input_shape_gap": shape_gap, "input_range_out": range_out,
            "duplicate_rows": dup}


def program_grad_norms(mu_after_1):
    """Per-leaf norms of the first gradient as Adam's first moment holds it
    after one step: mu = (1 - b1) g."""
    return {n: compare._norm(v) / (1.0 - ADAM_B1)
            for n, v in compare._flat(mu_after_1).items()}


def delta_norms(params0, params_after):
    import numpy as np

    p0, pn = compare._flat(params0), compare._flat(params_after)
    return {n: compare._norm(np.asarray(pn[n], np.float64)
                             - np.asarray(p0[n], np.float64)) for n in p0}


def routed_rows_gap(program_pairs, ref_pairs):
    """The worst checked step's |program - reference| / reference count of
    (token, held expert) pairs; inf where the program logged none."""
    if len(program_pairs) < len(ref_pairs) or None in program_pairs:
        return float("inf")
    return max(abs(p - r) / max(r, 1) for p, r in zip(program_pairs, ref_pairs))


def follow_reference(arch, optim, seed, batches, device, q=None, fault=None,
                     note=None):
    import jax

    from benchmarks.reference import qwen3_next as ref

    with jax.default_device(device):
        params0 = jax.device_put(ref.init_variables(arch, seed)["params"], device)
        return ref.follow(arch, optim, params0,
                          (jax.device_put(b, device) for b in batches),
                          q=q, fault=fault, note=note)


def judge(program, ref, limits, structure):
    """`compare.judge`'s readings plus the routing's."""
    return compare.judge(
        program["losses"], program["grad_norms"], program["delta_norms"], ref,
        limits, {"routed_rows_gap": routed_rows_gap(program["pairs"],
                                                    ref["pairs"]),
                 **structure})


def run(ctx):
    """Run the cell once; returns the pieces of the result line."""
    import jax

    from benchmarks.reference import qwen3_next as ref_lib
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    spec, cell, config = ctx["spec"], ctx["cell"], ctx["config"]
    rehearse = ctx["rehearse"]
    arch = arch_of(config, rehearse)
    if int(cell["chips"]) != 1:
        raise RuntimeError("train_fit_tokens runs one-chip cells only")
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
    program = {
        "losses": [logged.get(i, {}).get("train_loss_step") for i in checked],
        "pairs": [logged.get(i, {}).get("moe_local_pairs") for i in checked],
        "grad_norms": program_grad_norms(tap.momentum_after_1),
        "delta_norms": delta_norms(host_params0, tap.params_after_check),
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
    reference = follow_reference(arch, optim, ctx["seed"], tap.batches,
                                 devices[0], note=note)
    for line in compare.size_table(program["grad_norms"],
                                   program["delta_norms"], reference):
        note(line)
    numbers = judge(program, reference, limits, structure)
    stand_ins = {}
    asked = {**ctx.get("stand_ins", {}), **EXTRA_STAND_INS}
    for k in range(STAND_IN_SEEDS if asked else 0):
        # not part of a benchmark run: the control and the planted faults, on
        # the same placed batches with the weights of seed, seed+1, ...
        seed_k = ctx["seed"] + k
        ref_k = reference if k == 0 else follow_reference(
            arch, optim, seed_k, tap.batches, devices[0])
        for name, kwargs in asked.items():
            if kwargs.get("q") == "control":
                kwargs = {**kwargs, "q": (config["rehearse"] if rehearse
                                          else config)["control_dtype"]}
            other = follow_reference(arch, optim, seed_k, tap.batches,
                                     devices[0], **kwargs)
            numbers_k = judge(other, ref_k, limits, {})
            stand_ins[f"{name}@{seed_k}"] = numbers_k
            _note(ctx, f"stand-in {name} seed {seed_k}",
                  **{n["name"]: n["value"] for n in numbers_k})
    tap.batches = tap.momentum_after_1 = tap.params_after_check = None
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
            # device ms a step under each scope of the token model (an op
            # fused across two scopes counts under both)
            breakdown["scope_ms_per_step"] = [
                [scope, 1e3 * xtrace.scope_seconds(
                    results["trace"]["ops"], "/" + scope) / traced]
                for scope in MODEL_SCOPES]
    return {
        "correct": all(n["ok"] for n in numbers),
        "attempted": steps, "failed": 0,
        "end_to_end": end_to_end, "results": results,
        "memory_peak_bytes": peak, "device_extra": device_extra,
        "breakdown": breakdown, "compared": numbers, "stand_ins": stand_ins,
    }


def main(argv=None):
    """`run.py` with this job's stand-ins: the same arguments, `--stand-in`
    taking any of `STAND_INS`."""
    import argparse

    from benchmarks import run as bench_run
    from benchmarks.jobs import train_fit_tokens as job  # the copy run.py finds

    def parse(args):
        ap = argparse.ArgumentParser(description=main.__doc__)
        ap.add_argument("--workload", required=True)
        ap.add_argument("--seed", type=int, default=0)
        ap.add_argument("--seconds", type=float, default=None)
        ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
        ap.add_argument("--rehearse", action="store_true")
        ap.add_argument("--stand-in", action="append", default=[],
                        choices=sorted(STAND_INS))
        ns = ap.parse_args(args)
        job.EXTRA_STAND_INS = {name: STAND_INS[name] for name in ns.stand_in}
        ns.stand_in = []  # run.py's own table does not know them all
        return ns

    bench_run.parse = parse
    return bench_run.main(argv, t_start=bench_run.T_START)


if __name__ == "__main__":
    main()

"""Job `train_fit`: one cell of training, through `Trainer.fit()`.

The job builds a `TrainConfig` from the configuration's and the cell's files
as `run.main` would from flags, constructs ONE `Trainer`, gives it the
benchmark's seeded weights, and calls `fit()` once, for one long epoch. What
the window drives is that call: `SyntheticClipSource` through the transform
stack and `ClipLoader`, `DevicePrefetcher`, the jitted step, the optimizer,
the deferred logger.

The harness reaches into `fit()` at two places only, both objects the trainer
already calls every step:

* `trainer.train_prefetch` is wrapped by `FitTap`. `fit()` asks it for the
  next batch between two steps, so the tap sees every step boundary: it
  copies the first batches and the state after steps 1 and 3 to the host
  (for `correct`), starts the window after a value fetch, starts and stops
  the profiler, and ends the epoch by setting `cfg.data.limit_train_batches`,
  which `fit()` reads afresh every step. `fit()` then closes the epoch with
  its own value fetch (`fetch_loss`) and calls `pop_wait()`, where the tap
  reads the clock: that is the end of the window.
* a tracker object is appended to `trainer.trackers`, so every flush of the
  deferred logger (losses, and the `obs` spans of each log window) reaches it.

No step loop of the harness's own, no second trainer. Every batch comes from
the program's own pipeline. A cell whose file sets `resident_batches` to K
keeps the K batches placed last before the window starts on the device,
closes the prefetcher's epoch there (its worker ends and the loader's pending
decodes are cancelled) and hands `fit()` those K in turn for every step of
the window: the step donates its state only, so a placed batch can be fed
again. The window then shows the step, the dispatch and the logger with no
input to wait for; the checked steps and the warm-up are fed as in any cell.
"""

from __future__ import annotations

import collections
import gc
import glob
import os
import threading
import time

from benchmarks import reference
from benchmarks.lib import compare, flops, xtrace


class BenchTracker:
    """Receives what `fit()` logs: step metrics and per-window `obs` spans."""

    name = "bench"

    def __init__(self):
        self.entries = []  # (step, {key: float})

    def start(self, run_name, config):
        pass

    def log(self, values, step):
        self.entries.append((int(step), dict(values)))

    def finish(self):
        pass

    def losses(self):
        return {s: v["train_loss_step"] for s, v in self.entries
                if "train_loss_step" in v}

    def span_totals(self, after_step):
        """Sums of the `obs/<span>_s` entries of the log windows that closed
        after `after_step` (the windows inside the measured window)."""
        out = {}
        for step, values in self.entries:
            if step > after_step and "obs/window_wall_s" in values:
                for k, v in values.items():
                    if k.startswith("obs/") and k.endswith("_s"):
                        out[k[4:-2]] = out.get(k[4:-2], 0.0) + float(v)
        return out


def find_momentum(opt_state):
    """The momentum buffer (optax `TraceState.trace`) inside an optimizer
    state built as chain(add_decayed_weights, sgd(momentum))."""
    if hasattr(opt_state, "trace"):
        return opt_state.trace
    if isinstance(opt_state, (tuple, list)):
        for sub in opt_state:
            found = find_momentum(sub)
            if found is not None:
                return found
    return None


class FitTap:
    """Stands where `trainer.train_prefetch` stood; see the module docstring."""

    def __init__(self, trainer, plan):
        self.trainer = trainer
        self.inner = trainer.train_prefetch
        self.plan = plan
        self.batches = []  # host copies of the first `check_steps` batches
        self.momentum_after_1 = None
        self.params_after_check = None
        self.t0 = self.t1 = None
        self.wait_at_t0 = 0.0
        self.window_wait_s = None
        self.steps_in_window = 0
        self.ending = False
        self.tracing = False
        self.trace_t0 = self.trace_t1 = None
        self.pop_wait_calls = 0
        self.pulled = 0  # batches taken from the prefetcher
        self.worker_ended = None  # a thread ended where the epoch was closed
        self.live_bytes = 0  # `bytes_in_use` at the window's ends, the larger

    def __getattr__(self, name):
        return getattr(self.inner, name)

    # --- the two calls fit() makes ---------------------------------------

    def epoch(self, epoch=None, from_start=False):
        import jax

        it = self.inner.epoch(epoch, from_start)
        start = self.plan["window_start"]
        keep = self.plan.get("resident_batches", 0)
        kept = collections.deque(maxlen=keep)  # the batches placed last
        index = 0
        try:
            while True:
                if keep and index >= start:
                    if index == start:
                        before = set(threading.enumerate())
                        it.close()  # the worker ends, pending decodes go
                        self.worker_ended = any(
                            not t.is_alive() for t in before)
                    batch = kept[(index - start) % len(kept)]
                else:
                    with jax.profiler.TraceAnnotation("bench/prefetch_next"):
                        try:
                            batch = next(it)
                        except StopIteration:
                            raise RuntimeError(
                                "the epoch ran out of clips before the window "
                                "closed: raise synthetic_num_videos") from None
                    self.pulled += 1
                    kept.append(batch)
                self.on_boundary(index, batch)
                yield batch
                index += 1
        finally:
            it.close()
            if self.tracing:
                self.stop_trace()

    def pop_wait(self):
        self.pop_wait_calls += 1
        if self.pop_wait_calls == 2:
            # fit() has just fetched the last step's loss: the window's end
            self.t1 = time.perf_counter()
            self.window_wait_s = self.inner.wait_s - self.wait_at_t0
            if self.tracing:
                self.stop_trace()
            self.live_bytes = max(self.live_bytes, live_bytes_in_use())
        return self.inner.pop_wait()

    # --- step boundaries --------------------------------------------------

    def on_boundary(self, index, batch):
        """`index` steps have been dispatched; `batch` feeds the next one."""
        import jax

        plan, trainer = self.plan, self.trainer
        if index == 0:
            self.batch_struct = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), batch)
        if index < plan["check_steps"]:
            self.batches.append(jax.device_get(batch))
        if index == 1:
            self.momentum_after_1 = jax.device_get(
                find_momentum(trainer.state.opt_state))
        if index == plan["check_steps"]:
            self.params_after_check = jax.device_get(trainer.state.params)
        if index == plan["window_start"]:
            trainer.cfg.tracking.log_every = plan["log_every"]
            # value fetch: every step dispatched so far has run
            int(jax.device_get(trainer.state.step))
            if plan["trace_dir"]:
                options = jax.profiler.ProfileOptions()
                # host annotations only: the Python tracer and the runtime's
                # level-2 host events (futex waits, half a million a second)
                # would make the trace gigabytes
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                options.enable_hlo_proto = False  # the step's text is read apart
                jax.profiler.start_trace(plan["trace_dir"],
                                         profiler_options=options)
                self.tracing = True
                self.trace_t0 = time.perf_counter()
            self.live_bytes = live_bytes_in_use()
            self.t0 = time.perf_counter()
            self.wait_at_t0 = self.inner.wait_s
        if self.t0 is not None and not self.ending:
            now = time.perf_counter()
            self.steps_in_window += 1  # the step this batch feeds
            if self.tracing:
                if now - self.trace_t0 >= plan["trace_seconds"]:
                    self.stop_trace()
            if now - self.t0 >= plan["seconds"]:
                # fit() dispatches the step for this batch, then leaves the
                # epoch through its own value fetch
                trainer.cfg.data.limit_train_batches = index + 1
                self.ending = True

    def stop_trace(self):
        import jax

        # the traced steps have to have run before the trace is read
        int(jax.device_get(self.trainer.state.step))
        self.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.tracing = False


def build_config(config, cell, seed, work_dir, rehearse):
    """The `TrainConfig`, from the configuration's and the cell's files."""
    from pytorchvideo_accelerate_tpu.config import config_from_dict

    cfg = config_from_dict(config["train_config"], source="config file")
    cfg = config_from_dict(cell.get("train_config", {}), base=cfg,
                           source="workload file")
    if rehearse:
        cfg = config_from_dict(config["rehearse"]["train_config"], base=cfg,
                               source="config file, rehearse")
        cfg = config_from_dict(cell.get("rehearse", {}).get("train_config", {}),
                               base=cfg, source="workload file, rehearse")
    cfg.seed = int(seed) % (2 ** 31 - 1)
    cfg.mesh.data = 1
    cfg.data.synthetic = True
    cfg.optim.num_epochs = 1
    cfg.data.limit_train_batches = -1
    cfg.data.limit_val_batches = 0
    cfg.checkpoint.output_dir = work_dir
    cfg.checkpoint.checkpointing_steps = ""
    cfg.tracking.with_tracking = True
    cfg.tracking.trackers = "jsonl"
    cfg.tracking.logging_dir = os.path.join(work_dir, "runs")
    return cfg


def give_weights(trainer, variables):
    """Put the benchmark's seeded leaves where the trainer's own stood, leaf
    by leaf by path, with the sharding and type the trainer chose."""
    import jax

    def swap(tree, new_tree, what):
        flat_new = {jax.tree_util.keystr(p): v for p, v in
                    jax.tree_util.tree_flatten_with_path(new_tree)[0]}
        flat_old = jax.tree_util.tree_flatten_with_path(tree)[0]
        names = {jax.tree_util.keystr(p) for p, _ in flat_old}
        if names != set(flat_new):
            odd = sorted(names ^ set(flat_new))[:6]
            raise RuntimeError(f"the reference's {what} tree and the "
                               f"program's differ, e.g. at {odd}")

        def put(path, old):
            new = flat_new[jax.tree_util.keystr(path)]
            if new.shape != old.shape:
                raise RuntimeError(f"{what}{jax.tree_util.keystr(path)}: "
                                   f"{new.shape} vs the program's {old.shape}")
            return jax.device_put(new.astype(old.dtype), old.sharding)

        return jax.tree_util.tree_map_with_path(put, tree)

    state = trainer.state
    trainer.state = state.replace(
        params=swap(state.params, variables["params"], "params"),
        batch_stats=swap(state.batch_stats, variables["batch_stats"],
                         "batch_stats"))


def compiled_step_scopes(trainer, batch_struct):
    """{instruction: scopes} of the step program that ran, from its compiled
    text (a read of the compile cache: same function, shapes and shardings)."""
    import jax

    from benchmarks.lib import hlo

    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        trainer.state)
    compiled = trainer.train_step.lower(
        state, batch_struct, trainer.rng.step_key(0)).compile()
    return hlo.scopes(compiled.as_text())


def live_bytes_in_use():
    """`bytes_in_use` of the fullest chip, now: the arrays that are alive."""
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


def memory_peak_bytes(devices, live_bytes=0):
    """Peak HBM held at once on the fullest chip. On this runtime
    `peak_bytes_in_use` counts live arrays only; the running program's
    scratch (the step's temp buffers, 9.4 GiB for slowfast_r50 at batch 8)
    is `peak_bytes_reserved`, which matches the compiler's `memory_analysis()`
    to 0.4% (PERF.md, Findings). The two peaks need not fall together (the
    token cell's live arrays peak while its weights are made, before any step
    runs), so they are not added: the peak is the larger of the live arrays'
    own peak and what the window held, its live arrays (`live_bytes`, read by
    the tap at the window's two ends) with the largest scratch beside them."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         int(live_bytes)
                         + int(stats.get("peak_bytes_reserved", 0))))
    return max(peaks)


def _note(ctx, what, **facts):
    """A progress line on standard error (never the result)."""
    import sys

    print(f"[bench {time.perf_counter() - ctx['t_start']:8.2f}s] {what} "
          + " ".join(f"{k}={v}" for k, v in facts.items()), file=sys.stderr,
          flush=True)


STAND_IN_SEEDS = 3  # weight seeds (seed, seed+1, ...) a stand-in is read on


def run(ctx):
    """Run the cell once; returns the pieces of the result line."""
    import jax

    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    spec, cell, config = ctx["spec"], ctx["cell"], ctx["config"]
    rehearse = ctx["rehearse"]
    arch = dict(config["arch"])
    if rehearse:
        arch.update(config["rehearse"].get("arch", {}))
    if int(cell["chips"]) != 1:
        # the mesh, the global batch and a reference that follows it come
        # with the first four-chip cell (PERF.md, Open questions)
        raise RuntimeError("train_fit runs one-chip cells only")
    cfg = build_config(config, cell, ctx["seed"], ctx["work_dir"], rehearse)
    devices = jax.devices()[:1]
    global_batch = cfg.data.batch_size
    total_steps = cfg.data.synthetic_num_videos // global_batch
    optim = {"lr": cfg.optim.lr, "momentum": cfg.optim.momentum,
             "weight_decay": cfg.optim.weight_decay, "total_steps": total_steps}

    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(ctx["work_dir"], "trace")
    log_every = cfg.tracking.log_every
    settle = cell["check_steps"] + cell["warmup_steps"]
    toy = cell.get("rehearse", {}) if rehearse else {}
    plan = {
        "check_steps": int(cell["check_steps"]),
        "log_every": log_every,
        # the first boundary after the warm-up at which a log window closes
        "window_start": -(-settle // log_every) * log_every,
        "seconds": float(ctx["seconds"]),
        "trace_dir": trace_dir,
        "trace_seconds": min(float(cell.get("trace_seconds", 5.0)),
                             float(ctx["seconds"])),
        "resident_batches": int(toy.get("resident_batches",
                                        cell.get("resident_batches", 0))),
    }
    cfg.tracking.log_every = 1  # until the window starts: every loss is logged

    trainer = Trainer(cfg)
    _note(ctx, "trainer constructed")
    give_weights(trainer, reference.init_variables(config["family"], arch,
                                                   ctx["seed"]))
    tap = FitTap(trainer, plan)
    trainer.train_prefetch = tap
    tracker = BenchTracker()
    trainer.trackers.trackers = trainer.trackers.trackers + [tracker]

    fit = trainer.fit()

    if tap.t0 is None or tap.t1 is None:
        raise RuntimeError("fit() returned before the window opened or closed")
    window_s = tap.t1 - tap.t0
    steps = tap.steps_in_window
    clips = steps * global_batch
    peak = memory_peak_bytes(devices, tap.live_bytes)
    _note(ctx, "fit returned", memory_stats=devices[0].memory_stats())
    compile_snapshot = ctx["counters"].snapshot()

    step_scopes = None
    if ctx["trace"]:
        step_scopes = compiled_step_scopes(trainer, tap.batch_struct)
        _note(ctx, "step's HLO text read", instructions=len(step_scopes))

    # free the program's state before the reference takes the chip
    batch_shapes = {k: (v.shape, str(v.dtype)) for k, v in tap.batches[0].items()}
    del trainer.state
    trainer.train_step = trainer.eval_step = None
    del trainer
    gc.collect()
    jax.clear_caches()

    # --- correct ----------------------------------------------------------
    losses = tracker.losses()
    program = {
        "losses": [losses.get(i + 1) for i in range(plan["check_steps"])],
        "momentum_after_1": tap.momentum_after_1,
        "params_after": tap.params_after_check,
    }
    fam = reference.family(config["family"])
    clips_spec = dict(cell["clips"])
    if rehearse:
        clips_spec.update(cell.get("rehearse", {}).get("clips", {}))
    mean, std = clips_spec["mean"], clips_spec["std"]
    structure = {
        **compare.input_numbers(tap.batches, {
            "shapes": fam.expected_inputs(arch, global_batch,
                                          cfg.data.num_frames, cfg.data.crop_size),
            "dtype": (config["rehearse"] if rehearse else config)["compute_dtype"],
            "num_classes": arch["num_classes"],
            "low": (clips_spec["pixel_low"] / 255.0 - mean) / std,
            "high": (clips_spec["pixel_high"] / 255.0 - mean) / std,
            "mean": ((clips_spec["pixel_low"] + clips_spec["pixel_high"])
                     / 2.0 / 255.0 - mean) / std,
            "derive": lambda batch: fam.derived_inputs(batch, arch)}),
        "duplicate_rows": compare.duplicate_rows(tap.batches),
        "step_count_gap": abs(int(fit["steps"]) - (plan["window_start"] + steps)),
        "recompiles": fit.get("train_recompiles"),
    }
    if plan["resident_batches"]:
        # the window was fed the kept batches only, and the prefetcher's
        # worker ended where its epoch was closed
        structure["window_batches_from_loader"] = tap.pulled - plan["window_start"]
        structure["loader_worker_left"] = 0 if tap.worker_ended else None
    limits = cell["limits"]
    if rehearse:
        limits = cell.get("rehearse", {}).get("limits", limits)
    numbers, ref = compare.training_numbers(
        config["family"], arch, optim, ctx["seed"], tap.batches, program,
        limits, structure, devices[0], note=lambda what: _note(ctx, what))
    stand_ins = {}
    for k in range(STAND_IN_SEEDS if ctx.get("stand_ins") else 0):
        # not part of a benchmark run: the control and the planted faults,
        # read in this process because set-up is long (run.py --stand-in), on
        # the same placed batches with the weights of seed, seed+1, ...
        seed_k = ctx["seed"] + k
        ref_k = ref if k == 0 else compare.follow_reference(
            config["family"], arch, optim, seed_k, tap.batches, devices[0])
        for name, kwargs in ctx["stand_ins"].items():
            if kwargs.get("q") == "control":
                kwargs = {**kwargs, "q": (config["rehearse"] if rehearse
                                          else config)["control_dtype"]}
            _note(ctx, f"stand-in {name} seed {seed_k}: gaps by leaf size")
            numbers_k = compare.stand_in_numbers(
                config["family"], arch, optim, seed_k, tap.batches, ref_k,
                limits, devices[0], note=lambda what: _note(ctx, what),
                **kwargs)
            stand_ins[f"{name}@{seed_k}"] = numbers_k
            _note(ctx, f"stand-in {name} seed {seed_k}",
                  **{n["name"]: n["value"] for n in numbers_k})
    tap.batches = tap.momentum_after_1 = tap.params_after_check = None
    _note(ctx, "reference followed", window_s=window_s, steps=steps,
          setup_s=tap.t0 - ctx["t_start"], compile=compile_snapshot,
          slowest=ctx["counters"].slowest())

    # --- metrics ----------------------------------------------------------
    results = {
        "fit": fit, "window_s": window_s, "steps": steps, "clips": clips,
        "chips": 1, "global_batch": global_batch,
        "setup_s": tap.t0 - ctx["t_start"],
        "spans": tracker.span_totals(plan["window_start"]),
        "window_wait_s": tap.window_wait_s,
        "compile": compile_snapshot, "memory_peak_bytes": peak,
        "peaks": None, "work": None, "trace": None,
    }
    end_to_end = {
        "setup_s": results["setup_s"],
        "clips_per_s_per_chip": clips / window_s,
    }
    device_extra, breakdown = {}, None
    if ctx["trace"]:
        kind = devices[0].device_kind
        if not rehearse:
            results["peaks"] = spec.peaks(kind)
            results["work"] = flops.reference_work(
                config["family"], arch, batch_shapes, results["peaks"],
                config.get("bytes_per_element", 2))
        pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                               recursive=True))
        if not pbs:
            raise RuntimeError("the profiler wrote no trace")
        planes = xtrace.load(pbs[-1])
        results["trace"] = xtrace.reduce(
            planes, step_name=cell.get("step_program", "jit_step"),
            scopes=step_scopes)
        device_extra = {"busy_s": results["trace"]["busy_s"],
                        "window_s": results["trace"]["window_s"]}
        breakdown = results["trace"]["breakdown"]
    return {
        "correct": all(n["ok"] for n in numbers),
        "attempted": steps, "failed": 0,
        "end_to_end": end_to_end, "results": results,
        "memory_peak_bytes": peak, "device_extra": device_extra,
        "breakdown": breakdown, "compared": numbers, "stand_ins": stand_ins,
    }

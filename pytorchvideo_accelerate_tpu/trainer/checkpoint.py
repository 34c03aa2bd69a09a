"""Checkpoint/resume engine (orbax).

TPU-native replacement for accelerate's checkpoint engine (SURVEY §2.2-A8,
§3.6): `save_state`/`load_state` writing model.safetensors / optimizer.bin /
scheduler.bin / scaler.pt / random_states_{rank}.pkl becomes ONE orbax
composite per step: the whole TrainState pytree (params + BN stats + optax
state, sharding-aware, async) plus a JSON `extra` record (epoch, kind,
data-iterator state, config snapshot).

What the reference saves that we deliberately do NOT:
- GradScaler state — no scaler under bf16 (SURVEY §2.3-N7).
- Scheduler object — the LR schedule is a pure function of the step already
  inside `opt_state`.
- Per-process RNG pickles (checkpointing.py:154-179) — all randomness is
  derived from (seed, step/epoch) via fold_in (utils/rng.py), so resume
  re-derives identical streams from the restored step; the data-iterator
  position lives in `extra["data_state"]`.

Naming/resume semantics kept from the reference (run.py:123-133, 203-224):
`checkpointing_steps` = int | "epoch"; a checkpoint knows whether it was an
epoch-end or mid-epoch step save (`extra["kind"]`), and `resume="auto"`
scans for the latest — fixing the unreachable auto-find branch at
run.py:208-212.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional, Tuple

import jax
import orbax.checkpoint as ocp

from pytorchvideo_accelerate_tpu.parallel.distributed import is_main_process
from pytorchvideo_accelerate_tpu.parallel.hangcheck import collective_section
from pytorchvideo_accelerate_tpu.reliability.atomic import (
    atomic_write,
    atomic_write_json,
)
from pytorchvideo_accelerate_tpu.reliability.retry import retry_call
from pytorchvideo_accelerate_tpu.utils.logging import get_logger

logger = get_logger("pva_tpu")

# serving artifact layout (export_inference/load_inference): a directory of
#   weights.npz  — flat {params/..., batch_stats/...} numpy arrays
#   meta.json    — format tag, step, ema_resolved, num_classes, model name,
#                  and the resolved TrainConfig dict
INFERENCE_FORMAT = "pva-tpu-inference-v1"
_WEIGHTS_FILE = "weights.npz"
_META_FILE = "meta.json"


def export_inference(path: str, state, config=None,
                     meta: Optional[dict] = None,
                     quantization: str = "off") -> str:
    """Write a params-only serving artifact: the checkpoint-to-endpoint
    handoff (serving/engine.py `InferenceEngine.from_artifact`).

    EMA-RESOLVED: when the state carries `ema_params` (--optim.ema_decay),
    those are the weights exported — the same weights `evaluate()` scores —
    so serving top-1 matches eval by construction. BN `batch_stats` ride
    along; optimizer state does NOT (the engine never builds an optimizer,
    and the artifact is a fraction of a full checkpoint's size). Plain-numpy
    npz + JSON: loadable with no orbax and no training stack.

    Both files land ATOMICALLY (tmp + fsync + os.replace, with write
    retries): a kill or disk hiccup mid-export can never leave a truncated
    artifact where a serving engine would find it — the exact failure the
    `ckpt.write` fault point injects in `pva-tpu-chaos`.

    `quantization="int8"` bakes a per-channel-absmax int8 weight artifact
    (serving/quantize.py): 4x smaller on disk and over the hot-swap wire,
    recorded in `meta.quantization` so the engine knows the fp weights no
    longer exist. "off" writes the full-precision artifact unchanged.
    """
    from pytorchvideo_accelerate_tpu.models.convert import save_converted
    from pytorchvideo_accelerate_tpu.serving.quantize import (
        QUANT_MODES,
        quantize_tree,
    )

    if quantization not in QUANT_MODES:
        raise ValueError(
            f"export quantization must be one of {QUANT_MODES}, got "
            f"{quantization!r}")
    # every host participates in the value fetch (device_get of sharded
    # leaves is a collective), but the artifact hits the shared directory
    # from process 0 ONLY — N hosts racing atomic_write on the same path
    # is artifact corruption (spmd-divergence ckpt-discipline)
    params = state.ema_params if state.ema_params is not None else state.params
    tree = jax.device_get({"params": params,
                           "batch_stats": state.batch_stats or {}})
    if quantization == "int8":
        tree["params"], n_q = quantize_tree(tree["params"])
        logger.info("export: quantized %d weight leaves to int8", n_q)
    info = {
        "format": INFERENCE_FORMAT,
        "step": int(jax.device_get(state.step)),
        "ema_resolved": state.ema_params is not None,
        "quantization": quantization,
        **(meta or {}),
    }
    if config is not None:
        info["config"] = config.to_dict()
    if is_main_process():
        os.makedirs(path, exist_ok=True)
        retry_call(
            lambda: atomic_write(os.path.join(path, _WEIGHTS_FILE),
                                 lambda tmp: save_converted(tree, tmp)),
            name="ckpt.write", retry_on=(OSError,))
        retry_call(
            lambda: atomic_write_json(os.path.join(path, _META_FILE), info),
            name="ckpt.write", retry_on=(OSError,))
        logger.info("exported inference artifact to %s (step %d, ema=%s)",
                    path, info["step"], info["ema_resolved"])
    return path


def load_inference(path: str) -> Tuple[dict, dict, dict]:
    """Load an `export_inference` artifact -> (params, batch_stats, meta)."""
    from pytorchvideo_accelerate_tpu.models.convert import load_converted

    meta_path = os.path.join(path, _META_FILE)
    if not os.path.exists(meta_path):
        raise FileNotFoundError(
            f"{path} is not an inference artifact (no {_META_FILE}). "
            "Produce one with Trainer.export_inference / "
            "--export_inference PATH; a full training checkpoint dir "
            "cannot be served directly."
        )
    with open(meta_path) as f:
        meta = json.load(f)
    if meta.get("format") != INFERENCE_FORMAT:
        raise ValueError(
            f"unknown inference artifact format {meta.get('format')!r} "
            f"in {path} (expected {INFERENCE_FORMAT})"
        )
    tree = load_converted(os.path.join(path, _WEIGHTS_FILE))
    return tree["params"], tree["batch_stats"], meta


class Checkpointer:
    """Step-indexed checkpoint manager over `output_dir`.

    Retention (`max_to_keep`) covers accelerate's
    `ProjectConfiguration.total_limit` semantics (accelerator.py:3622-3646);
    async saving overlaps the write with the next train steps and is fenced
    by `wait()`/`close()`.
    """

    def __init__(self, directory: str, max_to_keep: int = 0,
                 use_async: bool = True, retries: int = 3,
                 retry_base_delay_s: float = 0.05,
                 retry_max_delay_s: float = 2.0,
                 retry_deadline_s: float = 30.0):
        self.directory = os.path.abspath(directory)
        # total attempts per save dispatch: transient filesystem failures
        # (ENOSPC races, cold network mounts) retry with backoff instead
        # of killing the run mid-epoch (reliability/retry.py); the shape
        # kwargs mirror --reliability.retry_{base_delay,max_delay,deadline}_s
        self.retries = max(int(retries), 1)
        self.retry_base_delay_s = float(retry_base_delay_s)
        self.retry_max_delay_s = float(retry_max_delay_s)
        self.retry_deadline_s = float(retry_deadline_s)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep if max_to_keep > 0 else None,
            enable_async_checkpointing=use_async,
            create=True,
        )
        self._mgr = ocp.CheckpointManager(self.directory, options=options)

    def save(self, step: int, state: Any, extra: Optional[dict] = None) -> None:
        step = int(step)

        def save_once():
            # orbax's save(step) is NOT idempotent (it refuses a duplicate
            # step), so a retry must first check whether the failed attempt
            # actually committed — otherwise a transient OSError after the
            # commit point would turn attempt 2 into a misleading
            # "step already exists" crash instead of a recovery. (With
            # async checkpointing the dispatch below rarely fails itself —
            # background write errors surface in wait()/close(); the retry
            # mainly protects the sync path, e.g. the emergency save.)
            if step in (self._mgr.all_steps() or ()):
                return
            # the orbax save dispatch is a cross-host barrier (all
            # processes coordinate the composite write): attributed +
            # schedule-recorded like every host-blocking collective
            with collective_section("ckpt_save", step=step):
                self._mgr.save(
                    step,
                    args=ocp.args.Composite(
                        state=ocp.args.StandardSave(state),
                        extra=ocp.args.JsonSave(extra or {}),
                    ),
                )

        retry_call(save_once, name="ckpt.save", attempts=self.retries,
                   retry_on=(OSError,),
                   base_delay_s=self.retry_base_delay_s,
                   max_delay_s=self.retry_max_delay_s,
                   deadline_s=self.retry_deadline_s)

    def restore(
        self, state_template: Any, step: Optional[int] = None, mesh=None,
        tp: bool = True,
    ) -> Tuple[Any, dict, int]:
        """Restore `(state, extra, step)`; `state_template` (a matching
        pytree, e.g. a freshly-initialized TrainState) drives dtypes/shapes.
        Pass `mesh` to restore arrays directly into their mesh placement —
        without it, restored arrays are committed to the default device and
        will clash with mesh-sharded batches inside jit.

        MESH-PORTABLE: the restore target's layout comes from the CURRENT
        mesh, never the one the checkpoint was written under — orbax
        reshards at read time — so a run saved on a (1, N) train mesh
        resumes on (N, 1) or single-chip at the same step with identical
        values (docs/PARALLELISM.md runbook). The pipeline knob rides the
        same contract: a (data, P)-pipelined run's param tree is
        byte-identical to the unpipelined model's (parallel/pipeline.py),
        so it restores unpipelined on any shape — and back — with no
        conversion (tests/test_zpipeline.py round-trip; chaos leg
        preempt_pipeline). When a template leaf is
        already a committed array on this mesh (the trainer's
        freshly-built, shard_state-settled state), its OWN sharding is the
        target — that keeps every leaf bit-identical in layout to the
        no-resume path (per-family tp/cp decisions included), so the first
        post-resume step hits the same executable a fresh run compiles.
        Leaves without a usable sharding (host arrays, foreign-mesh relics)
        fall back to the parallel.sharding rules — `tp` mirrors the
        caller's per-family model-axis decision there, so a fallback can
        never TP-shard params a context-parallel or conv-family run keeps
        replicated."""
        if mesh is not None and state_template is not None:
            from jax.sharding import NamedSharding as _NS

            from pytorchvideo_accelerate_tpu.parallel.sharding import (
                state_sharding_like,
            )
            import jax.numpy as jnp

            computed = state_sharding_like(mesh, state_template, tp=tp)

            def target_sharding(x, fallback):
                s = getattr(x, "sharding", None)
                if isinstance(s, _NS) and s.mesh == mesh:
                    return s
                return fallback

            shardings = jax.tree.map(target_sharding, state_template,
                                     computed)
            state_template = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(
                    jnp.shape(x), jnp.asarray(x).dtype, sharding=s
                ),
                state_template,
                shardings,
            )
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint found in {self.directory}")
        # A truncated/partially-deleted step dir (files swept by a quota
        # job, a torn network mount) used to surface as a raw orbax
        # traceback and kill the resume. Instead: walk BACK through older
        # steps — losing the newest interval is recoverable, losing the
        # run is not — warning (+ flight-ring event) per unreadable step,
        # and only error cleanly when no intact step remains.
        candidates = sorted((s for s in (self._mgr.all_steps() or ())
                             if s <= int(step)), reverse=True) or [int(step)]
        if candidates[0] != int(step):
            logger.warning(
                "requested checkpoint step %s not present in %s; trying "
                "latest earlier step %s", step, self.directory,
                candidates[0])
        restored = None
        first_err: Optional[BaseException] = None
        for i, s in enumerate(candidates):
            try:
                with collective_section("ckpt_restore", step=int(s)):
                    restored = self._mgr.restore(
                        int(s),
                        args=ocp.args.Composite(
                            state=ocp.args.StandardRestore(state_template),
                            extra=ocp.args.JsonRestore(),
                        ),
                    )
            except Exception as e:  # noqa: BLE001 - classified below
                first_err = first_err or e
                # The walk-back is a SINGLE-PROCESS recovery: readability
                # is per-host (a torn mount on one host), so on a pod the
                # hosts could pick DIFFERENT fallback steps and wedge in
                # mismatched ckpt_restore collectives. Multi-process, fail
                # loudly instead — the gate is uniform (process_count), so
                # every surviving host raises out of its own restore or
                # times out attributably in the hangcheck section above.
                if i + 1 < len(candidates) and jax.process_count() == 1:
                    logger.warning(
                        "checkpoint step %s in %s is unreadable (%s: %s); "
                        "falling back to step %s",
                        s, self.directory, type(e).__name__,
                        str(e)[:200], candidates[i + 1])
                    try:
                        from pytorchvideo_accelerate_tpu.obs import (
                            get_recorder,
                        )

                        get_recorder().warn(
                            "checkpoint fallback", step=int(s),
                            next_step=int(candidates[i + 1]),
                            error=f"{type(e).__name__}: {e}"[:200])
                    except Exception:  # pragma: no cover - obs optional
                        pass
                    continue  # pva: disable=spmd-divergence -- single-process only: the process_count()==1 gate above is uniform, pods raise instead of walking back
                if isinstance(first_err, (ValueError, KeyError)):
                    # a structure/shape mismatch usually means an older
                    # model layout (0.4 changed videomae_b/mvit_b trees) —
                    # say so instead of the raw orbax error
                    raise RuntimeError(
                        f"checkpoint at {self.directory} step {step} does "
                        "not match the current model's parameter tree. If "
                        "it was written by an older version (<0.4 changed "
                        "videomae_b/mvit_b layouts), re-convert the "
                        "original weights or retrain; see MIGRATING.md "
                        "'Checkpoint layout changes'."
                    ) from first_err
                raise RuntimeError(
                    f"no intact checkpoint step in {self.directory}: "
                    f"step {step} (and every older step) failed to "
                    f"restore; first error: {type(first_err).__name__}: "
                    f"{first_err}"
                ) from first_err
            else:
                step = s
                break
        # Re-materialize every restored leaf into a fresh XLA-owned buffer
        # (.copy() preserves sharding). Orbax hands back arrays backed by
        # tensorstore-owned host memory; with the persistent compilation
        # cache enabled, donating those into the deserialized train step
        # corrupted the heap on jaxlib 0.4.37 ("corrupted double-linked
        # list" / segfault a step or two after resume — reproduced by
        # pva-tpu-chaos's preempt leg, which resumes mid-epoch and trains).
        # Not re-verified on jaxlib 0.9.0, so the copy stays. One
        # whole-state copy at resume time is noise next to restore IO.
        state = jax.tree.map(
            lambda a: a.copy() if isinstance(a, jax.Array) else a,
            restored["state"],
        )
        return state, dict(restored["extra"] or {}), int(step)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self):
        return sorted(self._mgr.all_steps())

    def delete(self, step: int) -> None:
        """Drop one step from the manager (the TrainGuard's LKG ring
        replaces a revisited step index after a rollback; retention
        pruning itself stays orbax's `max_to_keep`)."""
        self._mgr.delete(int(step))

    def wait(self) -> None:
        with collective_section("ckpt_wait"):
            self._mgr.wait_until_finished()

    def close(self) -> None:
        with collective_section("ckpt_close"):
            self._mgr.wait_until_finished()
            self._mgr.close()


def resolve_resume_path(resume: str, output_dir: str) -> Optional[str]:
    """Map the reference's `--resume_from_checkpoint` forms onto a manager
    directory: "" -> None; "auto" -> output_dir if it has checkpoints; an
    explicit path -> that path (its parent manager dir if a step subdir was
    given, matching the reference's habit of pointing at `step_{i}/`)."""
    if not resume:
        return None
    if resume == "auto":
        return output_dir
    resume = resume.rstrip("/")
    base = os.path.basename(resume)
    if base.isdigit():  # orbax step dir
        return os.path.dirname(resume)
    for prefix in ("step_", "epoch_"):  # reference-style names (run.py:214-224)
        if base.startswith(prefix) and base[len(prefix):].isdigit():
            return os.path.dirname(resume)
    return resume


def resume_step_hint(resume: str) -> Optional[int]:
    """If the user pointed at a specific step/epoch dir, extract the step."""
    base = os.path.basename(resume.rstrip("/"))
    if base.isdigit():
        return int(base)
    if base.startswith("step_") and base[5:].isdigit():
        return int(base[5:])
    return None

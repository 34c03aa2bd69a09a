"""`pva-tpu-graphcheck`: jaxpr/HLO-level static analysis of the real steps.

The two prior static-analysis layers stop at Python: `pva-tpu-lint`
reads the AST, `pva-tpu-tsan` watches threads. The bugs that cost HBM
and MXU rate live one layer down, in the *compiled graph* — donation
that silently failed to alias, bf16 compute that upcast to f32, a
sharding the partitioner could only satisfy with a full regather, a
kernel the FLOP walk cannot see into. This tool traces the repo's REAL
train/eval/serve step functions (the builders the trainer and the
server use) to closed jaxprs + compiled executables and runs four
checker passes:

- **donation** (gc_donation.py): declared `donate_argnums` vs the
  compiled `input_output_alias` map — silent donation failures and
  donatable-but-undeclared state leaves, with bytes. Run on the train
  step (disarmed AND guard-armed: the in-graph skip's `jnp.where` must
  not break aliasing); skipped for eval/serve, whose state is reused
  across calls by design.
- **dtype** (gc_dtype.py): bf16→f32 taint analysis — silent upcasts
  reaching dot/conv compute, with a qualname allowlist for the designed
  f32 islands (precision.f32_island, loss math).
- **sharding** (gc_sharding.py): static re-propagation of the
  in-shardings — implicit full regathers (contracting-dim mismatches,
  block-destroying reshapes, sharded-dim concats).
- **flops** (gc_flops.py): analytical per-primitive FLOPs of the step
  as traced; a `pallas_call` with no registered FLOPs hook (or one whose
  hook fails) is a finding, as a count that is not finite. The work the
  benchmark's `step_mfu` divides by is not this count: it comes from the
  plain reference (`benchmarks/lib/flops.py`).

Exit codes (scripts/analyze.sh relies on them): 0 = clean, 1 =
findings, 2 = usage error. `--selftest` seeds one
violation per pass and exits 0 only if every one is detected AND the
matching clean construction stays clean — the detector proving it can
detect before anyone trusts its silence.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

_LAST_REPORT: Optional[dict] = None

# smoke-mode geometry (frames, crop, per-chip batch): tier-1/CLI/gate
# shapes — graph structure is shape-independent, so tiny is honest here
SMOKE_SHAPE = (4, 32, 2)


@dataclass
class CheckTarget:
    """One step function under analysis."""

    name: str
    fn: Any                      # jitted callable
    args: Tuple[Any, ...]
    policy: str = "bf16"
    donation: str = "skip"       # "require" (train) | "skip" (eval/serve)
    state_argnums: Tuple[int, ...] = (0,)
    compiled: Any = None         # filled lazily when donation/flops need it
    sharding_allowlist: frozenset = frozenset()


def arg_dim_maps(args: Sequence[Any]) -> List[dict]:
    """Flat per-leaf dim->axes maps from the args' committed shardings
    (the in-shardings the sharding pass propagates)."""
    import jax

    from pytorchvideo_accelerate_tpu.analysis.gc_sharding import (
        sharding_dim_map,
    )

    out = []
    for leaf in jax.tree_util.tree_leaves(args):
        out.append(sharding_dim_map(getattr(leaf, "sharding", None),
                                    getattr(leaf, "ndim", 0)))
    return out


def build_targets(model: str = "tiny3d", smoke: bool = True,
                  num_classes: int = 4, log=None) -> List[CheckTarget]:
    """The real step functions, built by utils/bench_setup's scaffolding:
    train (disarmed + guard-armed), eval, and the serving engine's
    forward protocol."""
    import jax

    from pytorchvideo_accelerate_tpu.trainer.steps import (
        device_normalize_batch,
        make_eval_step,
        make_pretrain_eval_step,
        make_pretrain_step,
        make_train_step,
        model_inputs,
        multiview_logits,
    )
    from pytorchvideo_accelerate_tpu.utils.bench_setup import (
        build_step_setup,
    )

    frames, crop, batch = SMOKE_SHAPE if smoke else (32, 224, 4)
    setup = build_step_setup(model, frames=frames, crop=crop,
                             batch_per_chip=batch, num_classes=num_classes)
    state = setup.state
    gb = setup.device_batch(0)
    key = jax.random.key(0)
    targets = [CheckTarget(
        name="train_step", fn=setup.step, args=(state, gb, key),
        donation="require")]

    # guard-armed variant: reliability/guard.py's in-graph skip wraps
    # every state leaf in jnp.where — donation must survive it. Pretrain
    # families (label-free batches, self-supervised loss) get their own
    # step/eval builders, matching what the Trainer would compile.
    make_armed = (make_pretrain_step if setup.pretrain else make_train_step)
    armed = make_armed(setup.model, setup.tx, setup.mesh,
                       guard_skip=True, health_metrics=True)
    targets.append(CheckTarget(
        name="train_step_guard_armed", fn=armed, args=(state, gb, key),
        donation="require"))

    eval_step = (make_pretrain_eval_step(setup.model, setup.mesh)
                 if setup.pretrain
                 else make_eval_step(setup.model, setup.mesh))
    targets.append(CheckTarget(
        name="eval_step", fn=eval_step, args=(state, gb),
        donation="skip"))

    # pipelined pretrain step (parallel/pipeline.py): donation must
    # survive the stage shard_map + microbatch scan, the dtype pass must
    # stay clean through the stage region (gc_dtype descends into the
    # open shard_map jaxpr), and the analytic counter must cost the
    # manual region (gc_flops's shard_map multiplier) so the count
    # doesn't silently deflate under the pipelined layout. Needs >= 2
    # devices on the model axis — tests/test_zpipeline.py runs it; a
    # 1-device gate skips it.
    n_dev = len(jax.devices())
    if n_dev >= 2 and n_dev % 2 == 0:
        from pytorchvideo_accelerate_tpu.config import MeshConfig

        psetup = build_step_setup(
            "videomae_t_pretrain", frames=4, crop=32,
            batch_per_chip=2, num_classes=num_classes,
            mesh_cfg=MeshConfig(data=n_dev // 2, model=2),
            pipeline_stages=2, pipeline_microbatches=2,
            overrides={"dropout_rate": 0.0})
        targets.append(CheckTarget(
            name="train_step_pipelined", fn=psetup.step,
            args=(psetup.state, psetup.device_batch(0), key),
            donation="require"))

    if setup.pretrain:
        # no serving surface for a pretraining objective: the fleet
        # serves classifiers (export_inference is supervised-only)
        return targets

    # the serving engine's forward protocol (serving/engine._make_forward
    # without the artifact plumbing): eval-mode apply through the shared
    # multiview logit-averaging helper, fp32 logits out
    model_mod, mesh = setup.model, setup.mesh
    clips = {k: v for k, v in gb.items() if k in ("video", "slow", "fast")}

    def serve_forward(params, batch_stats, clip_batch):
        from pytorchvideo_accelerate_tpu.precision import f32_island
        from pytorchvideo_accelerate_tpu.trainer.steps import (
            _constrain_batch,
        )

        b = _constrain_batch(clip_batch, mesh, leading_micro=False)
        b = device_normalize_batch(b, None)
        logits = multiview_logits(
            lambda x: model_mod.apply(
                {"params": params, "batch_stats": batch_stats},
                x, train=False),
            model_inputs(b))
        return f32_island(logits)

    targets.append(CheckTarget(
        name="serve_step", fn=jax.jit(serve_forward),
        args=(state.params, state.batch_stats, clips),
        donation="skip"))

    # fused-kernel lowering (ModelConfig.fused_kernels; docs/KERNELS.md),
    # for the conv families that wire it: (a) the SAME state/batch through
    # a fused-"auto" train step — donation and the dtype policy must
    # survive the lowering swap (the param tree is identical, so the
    # existing state drops in); (b) a forced-"pallas" serve forward, which
    # puts real `pallas_call` eqns in the jaxpr even on CPU hosts (where
    # "auto" lowers to the folded-XLA formulation) so the registered-FLOPs
    # hooks are exercised by every graphcheck run.
    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model

    fused_capable = model.startswith(
        ("tiny3d", "slow_r50", "slowfast", "x3d", "c2d", "csn",
         "r2plus1d"))
    if fused_capable:
        fused_model = create_model(ModelConfig(
            name=model, num_classes=num_classes, fused_kernels="auto"))
        fused_step = make_train_step(fused_model, setup.tx, setup.mesh)
        targets.append(CheckTarget(
            name="train_step_fused", fn=fused_step,
            args=(state, gb, key), donation="require"))

        pallas_model = create_model(ModelConfig(
            name=model, num_classes=num_classes, fused_kernels="pallas"))

        def serve_fused_pallas(params, batch_stats, clip_batch):
            from pytorchvideo_accelerate_tpu.precision import f32_island
            from pytorchvideo_accelerate_tpu.trainer.steps import (
                _constrain_batch,
            )

            b = _constrain_batch(clip_batch, mesh, leading_micro=False)
            b = device_normalize_batch(b, None)
            logits = multiview_logits(
                lambda x: pallas_model.apply(
                    {"params": params, "batch_stats": batch_stats},
                    x, train=False),
                model_inputs(b))
            return f32_island(logits)

        # interpret-mode pallas lowering: the analytic counter MUST cost
        # every pallas_call via its hook
        targets.append(CheckTarget(
            name="serve_step_fused_pallas", fn=jax.jit(serve_fused_pallas),
            args=(state.params, state.batch_stats, clips),
            donation="skip"))
    return targets


def check_target(target: CheckTarget, log=None) -> dict:
    """All four passes over one target; returns its report dict."""
    import jax

    from pytorchvideo_accelerate_tpu.analysis.gc_donation import (
        check_donation,
    )
    from pytorchvideo_accelerate_tpu.analysis.gc_dtype import check_dtype
    from pytorchvideo_accelerate_tpu.analysis.gc_flops import check_flops
    from pytorchvideo_accelerate_tpu.analysis.gc_sharding import (
        check_sharding,
    )

    out: Dict[str, Any] = {"passes": {}}
    closed = jax.make_jaxpr(target.fn)(*target.args)

    if target.donation == "require":
        lowered = target.fn.lower(*target.args)
        compiled = target.compiled or lowered.compile()
        findings, summary = check_donation(
            target.fn, target.args, state_argnums=target.state_argnums,
            lowered=lowered, compiled=compiled,
            out_avals=jax.tree_util.tree_leaves(
                jax.eval_shape(target.fn, *target.args)))
        out["passes"]["donation"] = {"findings": findings,
                                     "summary": summary}
    else:
        out["passes"]["donation"] = {
            "findings": [],
            "summary": {"skipped": True,
                        "reason": "state reused across calls by design"}}

    findings, summary = check_dtype(closed, policy=target.policy)
    out["passes"]["dtype"] = {"findings": findings, "summary": summary}

    findings, summary = check_sharding(
        closed, arg_dim_maps(target.args),
        allowlist=set(target.sharding_allowlist) or None)
    out["passes"]["sharding"] = {"findings": findings, "summary": summary}

    findings, summary = check_flops(closed)
    out["passes"]["flops"] = {"findings": findings, "summary": summary}

    if log:
        counts = {p: len(v["findings"]) for p, v in out["passes"].items()}
        log(f"[graphcheck] {target.name}: {counts}")
    return out


def run_graphcheck(model: str = "tiny3d", smoke: bool = True,
                   num_classes: int = 4, log=None) -> dict:
    """Build the real step targets and run every pass; returns the
    report dict (stash read by `graphcheck_snapshot`)."""
    global _LAST_REPORT
    t0 = time.perf_counter()
    targets = build_targets(model=model, smoke=smoke,
                            num_classes=num_classes, log=log)
    report: Dict[str, Any] = {"model": model, "smoke": smoke,
                              "targets": {}}
    for t in targets:
        report["targets"][t.name] = check_target(t, log=log)
    report["findings_total"] = finding_count(report)
    report["elapsed_s"] = round(time.perf_counter() - t0, 1)
    # the verified-donated verdict (tests/test_zgraphcheck.py
    # test_donation_round_trip_on_tiny3d, the doctor's snapshot)
    don = report["targets"]["train_step"]["passes"]["donation"]["summary"]
    report["donation_verified"] = (
        don.get("declared_unaliased") == 0
        and don.get("undeclared_donatable") == 0
        and don.get("aliased", 0) > 0)
    _LAST_REPORT = report
    publish(report)
    return report


def finding_count(report: dict) -> int:
    return sum(len(p["findings"])
               for t in report.get("targets", {}).values()
               for p in t["passes"].values())


def format_report(report: dict, max_findings: int = 20) -> str:
    lines = [f"pva-tpu-graphcheck: {report.get('findings_total', 0)} "
             f"finding(s) over model={report.get('model')} "
             f"in {report.get('elapsed_s')}s "
             f"(donation_verified={report.get('donation_verified')})"]
    shown = 0
    for tname, t in report.get("targets", {}).items():
        for pname, p in t["passes"].items():
            for f in p["findings"]:
                if shown >= max_findings:
                    lines.append("  ... (truncated)")
                    return "\n".join(lines)
                lines.append(f"  [{tname}/{pname}] {f['message']}")
                shown += 1
    return "\n".join(lines)


def publish(report: dict) -> None:
    """Verdict gauges into the process metric registry + a flight-ring
    event (the tsan_report/chaos publish discipline)."""
    try:
        from pytorchvideo_accelerate_tpu import obs

        reg = obs.get_registry()
        reg.gauge(
            "pva_graphcheck_findings",
            "total findings of the last pva-tpu-graphcheck run "
            "(donation/dtype/sharding/flops passes)",
        ).set(report.get("findings_total", 0))
        reg.gauge(
            "pva_graphcheck_donation_verified",
            "1 when the train step's declared donations all aliased and "
            "no donatable state leaf is undeclared",
        ).set(1.0 if report.get("donation_verified") else 0.0)
        obs.get_recorder().record(
            "graphcheck", "run",
            findings=report.get("findings_total", 0),
            donation_verified=bool(report.get("donation_verified")),
            elapsed_s=report.get("elapsed_s"))
    except Exception:  # telemetry stays optional
        pass


def graphcheck_snapshot() -> dict:
    """Doctor view (utils/device_doctor.diagnose): the last in-process
    run's verdict counts, or ran=False when no run happened here."""
    if _LAST_REPORT is None:
        return {"ran": False}
    rep = _LAST_REPORT
    per_pass: Dict[str, int] = {}
    for t in rep.get("targets", {}).values():
        for pname, p in t["passes"].items():
            per_pass[pname] = per_pass.get(pname, 0) + len(p["findings"])
    return {
        "ran": True,
        "model": rep.get("model"),
        "findings_total": rep.get("findings_total", 0),
        "findings_by_pass": per_pass,
        "donation_verified": rep.get("donation_verified"),
        "elapsed_s": rep.get("elapsed_s"),
        "finding_heads": [
            f["message"][:160]
            for t in rep.get("targets", {}).values()
            for p in t["passes"].values()
            for f in p["findings"]][:10],
    }


# --- selftest ---------------------------------------------------------------

def selftest(log=print) -> int:
    """Seed one violation per pass; every one MUST be detected and the
    matching clean construction MUST stay clean. Returns failure count."""
    import jax
    import jax.numpy as jnp

    from pytorchvideo_accelerate_tpu.analysis.gc_donation import (
        check_donation,
    )
    from pytorchvideo_accelerate_tpu.analysis.gc_dtype import check_dtype
    from pytorchvideo_accelerate_tpu.analysis.gc_flops import check_flops
    from pytorchvideo_accelerate_tpu.analysis.gc_sharding import (
        check_sharding,
    )
    from pytorchvideo_accelerate_tpu.precision import f32_island

    failures = 0

    def expect(cond: bool, what: str):
        nonlocal failures
        if cond:
            log(f"[selftest] PASS {what}")
        else:
            failures += 1
            log(f"[selftest] FAIL {what}")

    # donation: dtype drift -> declared-but-not-aliased; missing
    # donate_argnums -> donatable-but-undeclared; clean donation aliases
    def drift(state, x):
        return {"a": state["a"] + 1.0,
                "b": state["b"].astype(jnp.float32)}, x.sum()

    st = {"a": jnp.zeros((32, 32)), "b": jnp.zeros((16,), jnp.bfloat16)}
    x = jnp.ones((4,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's own unused-donation warning
        f, s = check_donation(jax.jit(drift, donate_argnums=0), (st, x))
    expect(s["declared_unaliased"] == 1 and s["aliased"] == 1,
           "donation: seeded dtype-drift detected as unaliased")
    f, s = check_donation(jax.jit(lambda st, x: ({"a": st["a"] * 2.0},
                                                 x.sum())),
                          ({"a": jnp.zeros((8, 8))}, x))
    expect(s["undeclared_donatable"] == 1,
           "donation: seeded undeclared donatable leaf detected")
    f, s = check_donation(
        jax.jit(lambda st, x: ({"a": st["a"] * 2.0}, x.sum()),
                donate_argnums=0),
        ({"a": jnp.zeros((8, 8))}, x))
    expect(not f, "donation: clean donated fn stays clean")

    # dtype: silent upcast feeding a dot vs the declared island
    w = jnp.ones((16, 8), jnp.float32)
    xb = jnp.ones((4, 16), jnp.bfloat16)
    f, _ = check_dtype(jax.make_jaxpr(
        lambda w, x: (x.astype(jnp.float32) @ w).sum())(w, xb))
    expect(len(f) == 1, "dtype: seeded silent bf16->f32 upcast detected")
    f, _ = check_dtype(jax.make_jaxpr(
        lambda w, x: (f32_island(x) @ w).sum())(w, xb))
    expect(not f, "dtype: declared f32_island stays clean")

    # sharding: contracting-dim mismatch + block-destroying reshape vs
    # the agreeing-contraction (DP grad psum) plan
    cj = jax.make_jaxpr(lambda x, w: x @ w)(jnp.ones((8, 512)),
                                            jnp.ones((512, 64)))
    f, _ = check_sharding(cj, [{1: ("model",)}, {}], min_bytes=1)
    expect(len(f) == 1, "sharding: seeded contracting-dim regather "
                        "detected")
    f, _ = check_sharding(
        jax.make_jaxpr(lambda x: x.reshape(48,))(jnp.ones((8, 6))),
        [{1: ("model",)}], min_bytes=1)
    expect(len(f) == 1, "sharding: seeded block-destroying reshape "
                        "detected")
    f, _ = check_sharding(
        jax.make_jaxpr(
            lambda x, g: jnp.einsum("bd,bk->dk", x, g))(
            jnp.ones((8, 32)), jnp.ones((8, 16))),
        [{0: ("data",)}, {0: ("data",)}], min_bytes=1)
    expect(not f, "sharding: agreeing contraction (grad psum plan) "
                  "stays clean")

    # flops: an UNREGISTERED pallas_call must be flagged (an opaque
    # Pallas primitive counts as zero FLOPs and silently deflates the
    # count); registering a hook makes the same graph clean
    from jax.experimental import pallas as pl

    from pytorchvideo_accelerate_tpu.analysis.gc_flops import (
        PALLAS_FLOPS_HOOKS,
        register_pallas_flops,
    )

    def _selftest_opaque_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    pj = jax.make_jaxpr(lambda x: pl.pallas_call(
        _selftest_opaque_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x))(jnp.ones((8, 128)))
    f, s = check_flops(pj)
    expect(len(f) == 1 and s["unregistered_pallas"] == [
        "_selftest_opaque_kernel"],
        "flops: seeded unregistered pallas_call detected")
    register_pallas_flops("_selftest_opaque_kernel",
                          lambda eqn: float(8 * 128))
    try:
        f, s = check_flops(pj)
        expect(not f and s["by_class"]["pallas"] == 8 * 128,
               "flops: registered pallas hook counts clean")
    finally:
        PALLAS_FLOPS_HOOKS.pop("_selftest_opaque_kernel", None)
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pva-tpu-graphcheck",
        description="jaxpr/HLO-level checks over the real train/eval/"
                    "serve steps: donation aliasing, dtype policy, "
                    "sharding propagation, analytical FLOPs "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--model", default="tiny3d",
                    help="model registry name to build the steps from "
                         "(default tiny3d — graph structure, not speed, "
                         "is under test)")
    ap.add_argument("--full-shapes", action="store_true",
                    help="trace at real clip geometry instead of the "
                         "smoke shapes (slower; same graph structure)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--selftest", action="store_true",
                    help="seed one violation per pass; exit 0 only when "
                         "every one is detected")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    if args.selftest:
        failures = selftest(log=log)
        if failures:
            log(f"pva-tpu-graphcheck --selftest: {failures} seeded "
                "violation(s) NOT detected")
            return 1
        log("pva-tpu-graphcheck --selftest: all seeded violations "
            "detected; clean constructions clean")
        return 0

    try:
        report = run_graphcheck(model=args.model,
                                smoke=not args.full_shapes, log=log)
    except Exception as e:
        log(f"pva-tpu-graphcheck: failed to build/trace targets: "
            f"{type(e).__name__}: {e}")
        return 2
    if args.format == "json":
        print(json.dumps(report, default=str))
    else:
        print(format_report(report))
    return 1 if report["findings_total"] else 0


if __name__ == "__main__":
    sys.exit(main())

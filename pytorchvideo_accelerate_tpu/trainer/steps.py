"""Compiled train/eval steps — the hot loop.

Replaces the reference's per-batch torch path (SURVEY §3.4: DDP forward ->
cross_entropy -> scaler backward -> Reducer allreduce every micro-step ->
optimizer/scheduler step) with one jitted function per effective step:

- forward+backward via `jax.value_and_grad`, bf16 compute / fp32 params;
- the gradient all-reduce is *implied* by differentiating a loss computed
  over the globally-sharded batch — XLA inserts the psum and overlaps it
  (no DDP Reducer, SURVEY §2.3-N6);
- gradient accumulation is an in-graph `lax.scan` over micro-batches that
  syncs ONCE per effective step — a deliberate fix of the reference's
  allreduce-every-micro-step behavior (run.py:257, SURVEY §2.1);
- eval metrics are accumulated in-graph as masked (loss_sum, correct, count)
  sums, fixing the reference's padded-duplicate eval bias (run.py:298 plain
  `gather` vs `gather_for_metrics`, SURVEY §2.1).

Batch convention: dict with "video" (single-pathway) or "slow"/"fast"
(SlowFast packing), "label" int32, optional "mask" float32 (1.0 = real
sample, 0.0 = padding). With gradient accumulation G>1, every leaf carries a
leading (G, B, ...) micro-step axis laid out by the data pipeline, so no
device resharding is needed to slice micro-batches.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorchvideo_accelerate_tpu.obs.registry import get_registry
from pytorchvideo_accelerate_tpu.ops import (
    attention,
    gated_delta,
    lane_fold,
    ssd,
)
from pytorchvideo_accelerate_tpu.parallel.mesh import batch_axes
from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState


def model_inputs(batch: dict):
    """Map a batch dict to the model's input convention."""
    if "slow" in batch:
        return (batch["slow"], batch["fast"])
    return batch["video"]


def device_normalize_batch(batch: dict, norm) -> dict:
    """In-graph normalize for u8-through clips (data/transforms.py
    `output_dtype="uint8"`): the host ships raw uint8 — 4x less
    host->HBM transfer than fp32 — and the graph applies the same
    `x/255` + mean/std affine the host path fuses (`normalize_u8`).
    Computed in f32 so the model's own compute-dtype cast produces
    bit-identical bf16 to the host-normalized path; XLA fuses the
    affine into the first conv's input read, so nothing extra is
    materialized in HBM. No-op when `norm` is None or a clip is
    already floating-point."""
    if norm is None:
        return batch
    mean, std = norm
    mean32 = jnp.asarray(mean, jnp.float32)
    std32 = jnp.asarray(std, jnp.float32)
    scale = 1.0 / (255.0 * std32)
    bias = -mean32 / std32

    def f(x):
        if x.dtype != jnp.uint8:
            return x
        return x.astype(jnp.float32) * scale + bias

    out = dict(batch)
    for k in ("video", "slow", "fast"):
        if k in out:
            out[k] = f(out[k])
    return out


def _constrain_batch(batch: dict, mesh, leading_micro: bool) -> dict:
    """Pin the (global) batch dim to the mesh's DP axes inside the graph
    (("data","fsdp") on the library mesh, ("data",) on the 2-D train mesh)."""
    daxes = batch_axes(mesh)
    axes = (None, daxes) if leading_micro else (daxes,)

    def cons(x):
        spec = P(*axes, *([None] * (x.ndim - len(axes))))
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree.map(cons, batch)


def assert_batch_contract(batch: dict, leading_micro: bool = False) -> None:
    """Trace-time batch-contract checks (SURVEY §5 sanitizers): ranks,
    dtypes, and matching leading dims. On when TrainConfig.debug_asserts is
    set — pure trace-time, so zero runtime cost in the compiled step."""
    import chex

    lead = 2 if leading_micro else 1
    if "tokens" in batch:  # a next-token batch: (B, T) ids and nothing else
        chex.assert_rank(batch["tokens"], lead + 1)
        chex.assert_type(batch["tokens"], jnp.int32)
        return
    clips = [batch[k] for k in ("slow", "fast", "video") if k in batch]
    assert clips, "batch has neither 'video' nor 'slow'/'fast' clips"
    for c in clips:
        # (B, T, H, W, C) + optional micro axis + optional view axis
        chex.assert_rank(c, {4 + lead, 5 + lead})
    if "label" in batch:
        chex.assert_rank(batch["label"], lead)
        chex.assert_type(batch["label"], jnp.int32)
        chex.assert_equal_shape_prefix([clips[0], batch["label"]], lead)
    if batch.get("mask") is not None:
        chex.assert_type(batch["mask"], jnp.float32)
        chex.assert_equal_shape_prefix([clips[0], batch["mask"]], lead)


def _loss_and_metrics(logits, labels, mask, label_smoothing: float):
    logits = logits.astype(jnp.float32)
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if label_smoothing > 0:
        onehot = optax.smooth_labels(onehot, label_smoothing)
    losses = optax.softmax_cross_entropy(logits, onehot)
    count = mask.sum()
    loss = (losses * mask).sum() / jnp.maximum(count, 1.0)
    correct = ((jnp.argmax(logits, -1) == labels) * mask).sum()
    return loss, correct, count


def _topk_correct(logits, labels, mask, k: int = 5):
    """Masked top-k hit count (Kinetics convention reports top-1 AND top-5;
    the reference's torchmetrics Accuracy is top-1 only)."""
    k = min(k, logits.shape[-1])
    _, top = lax.top_k(logits.astype(jnp.float32), k)
    hit = (top == labels[..., None]).any(-1)
    return (hit * mask).sum()


def _fold_micro_axis(batch: dict) -> dict:
    """Fold the leading (G, B, ...) accumulation micro axis into the batch
    dim — (G*B, ...). The pipelined step (parallel/pipeline.py) consumes
    the WHOLE effective batch in one forward and re-slices it into the
    plan's microbatches inside the stage schedule, so the outer
    accumulation scan (which would serialize a full pipeline fill+drain
    per micro-step) disappears; the loss over the folded batch equals the
    mean of per-micro losses, and its gradient equals the accumulated
    gradient over G micro-steps divided by G — the same update (bitwise
    on the rng-free supervised path; an rng objective like the VideoMAE
    tube mask draws ONE stream per effective batch here instead of one
    per micro-step — both valid samplings, not a numerics drift)."""
    return jax.tree.map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), batch)


def _make_update_step(
    grad_fn: Callable,
    tx: optax.GradientTransformation,
    mesh,
    accum_steps: int,
    lr_schedule: Optional[Callable],
    with_accuracy: bool,
    debug_asserts: bool = False,
    ema_decay: float = 0.0,
    health_metrics: bool = False,
    guard_skip: bool = False,
    pipeline=None,
) -> Callable:
    """Shared machinery of the supervised and self-supervised steps.

    `grad_fn(params, batch_stats, batch, key) -> ((loss, (new_stats, correct,
    count)), grads)` — a value_and_grad with has_aux; the self-supervised
    wrapper passes batch_stats/correct/count through untouched. The aux may
    end in a fourth entry, a dict of the task's own step outputs (scalars):
    they join the step's metrics under their own names (`make_lm_step`; not
    under accumulation, where a step is several forwards). Gradient
    accumulation is an in-graph `lax.scan` over the leading micro-batch axis
    syncing ONCE per effective step; the returned step is jitted with state
    donation (params update in place in HBM).

    `guard_skip` (reliability/guard.py TrainGuard): a step whose loss or
    grad norm is nonfinite discards its own update IN-GRAPH — every state
    leaf keeps its old value via `jnp.where`, only the step counter
    advances — so a single NaN batch can never poison params/EMA/optimizer
    state while the (one-step-delayed, pipelining-preserving) host
    detector decides whether to escalate. A data-dependent select on a
    static predicate shape: no recompile, one extra `metrics["skipped"]`
    flag. Off (the default): the branch is not traced at all —
    structurally zero overhead.

    `pipeline` (parallel/pipeline.PipelinePlan, active): the model's trunk
    runs as a P-stage SPMD pipeline, and the microbatch STREAM through the
    stages replaces the outer accumulation scan — the (G, B, ...) micro
    axis is folded into one (G*B, ...) forward whose in-graph schedule
    keeps every stage busy (`_fold_micro_axis`; the outer scan would
    serialize a pipeline fill+drain per micro-step, P-1 extra bubbles).
    Plain autodiff through the stage scan, no custom VJP; state donation
    is unchanged (graphcheck's donation pass covers the pipelined step as
    its own target)."""
    pipelined = pipeline is not None and getattr(pipeline, "active", False)

    def step(state: TrainState, batch: dict, key) -> tuple:
        if debug_asserts:
            assert_batch_contract(batch, leading_micro=accum_steps > 1)
        if accum_steps > 1 and pipelined:
            batch = _constrain_batch(batch, mesh, leading_micro=True)
            batch = _fold_micro_axis(batch)
        if accum_steps == 1 or pipelined:
            batch = _constrain_batch(batch, mesh, leading_micro=False)
            (loss, (new_stats, correct, count, *extra)), grads = grad_fn(
                state.params, state.batch_stats, batch, key
            )
        else:
            batch = _constrain_batch(batch, mesh, leading_micro=True)

            extra = ()  # a task's own outputs are of one forward, not a scan

            def micro(carry, mb):
                grads_acc, stats, i = carry
                (loss_i, (stats, corr_i, cnt_i, *_)), g = grad_fn(
                    state.params, stats, mb, jax.random.fold_in(key, i)
                )
                grads_acc = jax.tree.map(jnp.add, grads_acc, g)
                return (grads_acc, stats, i + 1), (loss_i, corr_i, cnt_i)

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (grads, new_stats, _), (losses, corrs, cnts) = lax.scan(
                micro, (zeros, state.batch_stats, 0), batch
            )
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = jnp.mean(losses)
            correct, count = corrs.sum(), cnts.sum()

        # the update and its bookkeeping under one scope, `optim/`, so a
        # device trace names the optimizer's time (metadata only: the
        # compiled arithmetic is the same)
        with jax.named_scope("optim"):
            updates, new_opt_state = tx.update(grads, state.opt_state,
                                               state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_ema = state.ema_params
            if ema_decay > 0 and state.ema_params is not None:
                # in-graph EMA: pure VPU elementwise, fused with the update
                new_ema = jax.tree.map(
                    lambda e, p: e * ema_decay + p.astype(e.dtype)
                    * (1.0 - ema_decay),
                    state.ema_params, new_params)
            grad_norm = optax.global_norm(grads)
            skipped = None
            if guard_skip:
                # in-graph skip-batch (TrainGuard): a nonfinite loss or grad
                # norm means this update is poison — keep every old leaf
                # (params, BN stats, optimizer state, EMA), advance only the
                # step counter so host/step bookkeeping stays aligned
                ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)

                def _keep(new, old):
                    return jnp.where(ok, new, old)

                new_params = jax.tree.map(_keep, new_params, state.params)
                new_stats = jax.tree.map(_keep, new_stats, state.batch_stats)
                new_opt_state = jax.tree.map(_keep, new_opt_state,
                                             state.opt_state)
                if new_ema is not None:
                    new_ema = jax.tree.map(_keep, new_ema, state.ema_params)
                skipped = 1.0 - ok.astype(jnp.float32)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            ema_params=new_ema,
        )
        metrics = {"loss": loss, "grad_norm": grad_norm}
        for outputs in extra:
            metrics.update(outputs)
        if skipped is not None:
            metrics["skipped"] = skipped
        if health_metrics:
            # training-health gauges computed IN-GRAPH (obs/: a few extra
            # reductions XLA fuses into the update's pass, on every step;
            # 1.7-1.8% of a token model's step on a v5e, docs/
            # OBSERVABILITY.md; they ride the same async metrics fetch as
            # loss/grad_norm):
            # global param norm, update/param ratio (the "is the LR sane"
            # signal — healthy runs sit around 1e-3, a spike means the
            # update is rewriting the weights), and a non-finite-loss flag
            # the host accumulates into a counter. Under `health/` in a
            # device trace.
            with jax.named_scope("health"):
                param_norm = optax.global_norm(new_params)
                metrics["param_norm"] = param_norm
                metrics["update_ratio"] = (
                    optax.global_norm(updates)
                    / jnp.maximum(param_norm, 1e-12))
                metrics["nonfinite"] = 1.0 - jnp.isfinite(loss).astype(
                    jnp.float32)
        if with_accuracy:
            metrics["accuracy"] = correct / jnp.maximum(count, 1.0)
        if lr_schedule is not None:
            metrics["lr"] = lr_schedule(state.step)
        return new_state, metrics

    # state donation, VERIFIED: the graphcheck donation pass
    # (analysis/gc_donation.py) walks the compiled input_output_alias map
    # and proves every state leaf aliases — disarmed AND guard-armed (the
    # jnp.where skip branch above must not break aliasing) — with zero
    # donatable leaves left undeclared
    # (tests/test_zgraphcheck.py::test_donation_round_trip_on_tiny3d). An
    # aval drift here (a leaf that changes dtype/shape across the step)
    # would silently double-buffer that leaf — the pass reports the bytes.
    return jax.jit(step, donate_argnums=0)


@contextlib.contextmanager
def _count_lowering_sites():
    """Around the train step's `model.apply`: which shape-and-backend rules
    engaged is a fact of the trace, set as gauges while the step is traced
    (no op added) and logged once with the first window (trainer/loop.py)."""
    with lane_fold.count_sites() as folded, \
            gated_delta.count_sites() as scans, \
            attention.count_window_sites() as bands, \
            attention.count_kernel_sites() as flashes, \
            attention.count_xla_sites() as xla_attention, \
            ssd.count_sites() as ssd_scans:
        yield
    registry = get_registry()
    registry.gauge(
        "pva_conv_lane_fold_sites",
        "ConvBNAct sites of the traced train step lowered as a "
        "lane-filling contraction (ops/lane_fold.py)").set(len(folded))
    registry.gauge(
        "pva_gdn_scan_kernel_sites",
        "gated_delta_rule calls of the traced train step lowered as the "
        "Pallas kernel pair (ops/pallas_gated_delta.py)").set(len(scans))
    registry.gauge(
        "pva_attn_window_sites",
        "causal_gqa_attention calls of the traced train step lowered under "
        "a sliding-window band (ops/attention.py)").set(len(bands))
    registry.gauge(
        "pva_attn_kernel_sites",
        "causal_gqa_attention calls of the traced train step lowered as the "
        "Pallas flash kernels (ops/pallas_attention.py)").set(len(flashes))
    registry.gauge(
        "pva_attn_kept_sites",
        "of those, the calls traced inside a remat unit that keeps the "
        "forward kernel's o and lse (models/lm_common.py)").set(
            sum(kept for _shape, _window, kept in flashes))
    registry.gauge(
        "pva_attn_xla_sites",
        "causal_gqa_attention calls of the traced train step that took the "
        "XLA form on a TPU backend: heads not a multiple of 128 wide, or one "
        "block of tokens (ops/attention.py)").set(len(xla_attention))
    registry.gauge(
        "pva_ssd_scan_sites",
        "Mamba-2 mixers of the traced train step lowered through the "
        "chunked state-space scan (ops/ssd.py)").set(len(ssd_scans))


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh,
    accum_steps: int = 1,
    label_smoothing: float = 0.0,
    lr_schedule: Optional[Callable] = None,
    debug_asserts: bool = False,
    device_normalize=None,
    mixup_alpha: float = 0.0,
    cutmix_alpha: float = 0.0,
    ema_decay: float = 0.0,
    health_metrics: bool = False,
    guard_skip: bool = False,
    pipeline=None,
) -> Callable:
    """Build the supervised `step(state, batch, dropout_key) ->
    (state, metrics)` (see `_make_update_step`). `device_normalize`:
    (mean, std) for u8-through batches (`device_normalize_batch`).
    `mixup_alpha > 0` / `cutmix_alpha > 0`: in-graph mixup / cutmix (the
    MViT/SlowFast K400 recipes' augmentations, free of host cost), both
    expressed as one per-pixel weight w against the FLIPPED batch:
    out = w*x + (1-w)*x_flip — mixup is w = lam everywhere, cutmix is a
    spatial box of zeros (shared across time, the video convention) —
    with loss lam_eff*CE(y) + (1-lam_eff)*CE(y_flip), lam_eff = mean(w).
    Both on: a coin picks one per forward — i.e. per MICRO-batch under
    gradient accumulation, each drawing its own mode/lambda/box (timm's
    switching, at micro granularity). Reported accuracy counts the
    dominant label."""

    def forward_loss(params, batch_stats, batch, key):
        batch = device_normalize_batch(batch, device_normalize)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(batch["label"].shape, jnp.float32)
        labels2 = None
        lam = 1.0
        if mixup_alpha > 0 or cutmix_alpha > 0:
            if batch.get("mask") is not None:
                raise ValueError(
                    "mixup/cutmix with an explicit batch mask is "
                    "unsupported: padded rows would mix into real clips "
                    "(the train loader is drop_last, so this can't arise "
                    "through Trainer)")
            # mixing runs AFTER the u8 normalize (floats required).
            # Pairing is the flipped batch (timm's convention): a STATIC
            # reversal, which GSPMD lowers to a one-hop collective permute
            # of the clip tensor — a random global permutation would force
            # a cross-device gather of the whole batch every step. Every
            # clip pathway flips together so slow/fast stay paired.
            key, kmix, kbox, kswitch = jax.random.split(key, 4)
            some_clip = next(batch[k] for k in ("video", "slow", "fast")
                             if k in batch)
            hh, ww = some_clip.shape[-3], some_clip.shape[-2]
            use_cutmix = cutmix_alpha > 0 and (
                mixup_alpha <= 0
                or jax.random.bernoulli(kswitch))
            if mixup_alpha > 0 and cutmix_alpha > 0:
                lam_mix = jax.random.beta(kmix, mixup_alpha, mixup_alpha)
                lam_cut = jax.random.beta(kmix, cutmix_alpha, cutmix_alpha)
            else:
                a = mixup_alpha if mixup_alpha > 0 else cutmix_alpha
                lam_mix = lam_cut = jax.random.beta(kmix, a, a)

            def _cut_weight():
                # spatial box of the flipped clip, shared across time
                # (video cutmix convention); area approx (1 - lam_cut)
                rh = jnp.sqrt(1.0 - lam_cut) * hh
                rw = jnp.sqrt(1.0 - lam_cut) * ww
                cy = jax.random.uniform(kbox, (), minval=0.0, maxval=1.0) * hh
                cx = jax.random.uniform(
                    jax.random.fold_in(kbox, 1), (), minval=0.0,
                    maxval=1.0) * ww
                y0, y1 = cy - rh / 2, cy + rh / 2
                x0, x1 = cx - rw / 2, cx + rw / 2
                ih = jax.lax.broadcasted_iota(jnp.float32, (hh, ww), 0)
                iw = jax.lax.broadcasted_iota(jnp.float32, (hh, ww), 1)
                inside = ((ih >= y0) & (ih < y1) & (iw >= x0) & (iw < x1))
                return 1.0 - inside.astype(jnp.float32)  # (H, W)

            if cutmix_alpha > 0:
                w_hw = jnp.where(use_cutmix, _cut_weight(),
                                 jnp.full((hh, ww), lam_mix))
            else:
                w_hw = jnp.full((hh, ww), lam_mix)
            # effective label weight = mean pixel weight (exact for both)
            lam = jnp.mean(w_hw)
            w = w_hw[None, None, :, :, None]  # (1,1,H,W,1) vs (B,T,H,W,C)
            batch = dict(batch)
            for k in ("video", "slow", "fast"):
                if k in batch:
                    x = batch[k]
                    mixed = (w * x.astype(jnp.float32)
                             + (1.0 - w) * x[::-1].astype(jnp.float32))
                    batch[k] = mixed.astype(x.dtype)
            labels2 = batch["label"][::-1]
        with _count_lowering_sites():
            logits, updates = model.apply(
                {"params": params, "batch_stats": batch_stats},
                model_inputs(batch),
                train=True,
                rngs={"dropout": key},
                mutable=["batch_stats"],
            )
        if labels2 is not None:
            loss_a, correct_a, count = _loss_and_metrics(
                logits, batch["label"], mask, label_smoothing)
            loss_b, correct_b, _ = _loss_and_metrics(
                logits, labels2, mask[::-1], label_smoothing)
            loss = lam * loss_a + (1.0 - lam) * loss_b
            # dominant-label accuracy (the standard mixup report)
            correct = jnp.where(lam >= 0.5, correct_a, correct_b)
        else:
            loss, correct, count = _loss_and_metrics(
                logits, batch["label"], mask, label_smoothing
            )
        return loss, (updates["batch_stats"], correct, count)

    grad_fn = jax.value_and_grad(forward_loss, has_aux=True)
    return _make_update_step(grad_fn, tx, mesh, accum_steps, lr_schedule,
                             with_accuracy=True, debug_asserts=debug_asserts,
                             ema_decay=ema_decay,
                             health_metrics=health_metrics,
                             guard_skip=guard_skip, pipeline=pipeline)


def make_pretrain_step(
    model,
    tx: optax.GradientTransformation,
    mesh,
    accum_steps: int = 1,
    lr_schedule: Optional[Callable] = None,
    debug_asserts: bool = False,
    ema_decay: float = 0.0,
    health_metrics: bool = False,
    guard_skip: bool = False,
    pipeline=None,
) -> Callable:
    """Build the VideoMAE self-supervised step: `step(state, batch, key) ->
    (state, metrics)`. No labels; batch_stats pass through unchanged (pure-LN
    ViT keeps `{}`); the model returns its own reconstruction loss. The rng
    key feeds both the tube mask and dropout streams. `pipeline`: an
    active plan folds the accumulation micro axis into the stage
    schedule's microbatch stream (see `_make_update_step`)."""

    def forward_loss(params, batch_stats, batch, key):
        kmask, kdrop = jax.random.split(key)
        out = model.apply(
            {"params": params}, batch["video"], train=True,
            rngs={"mask": kmask, "dropout": kdrop},
        )
        zero = jnp.zeros((), jnp.float32)
        return out["loss"], (batch_stats, zero, zero)

    grad_fn = jax.value_and_grad(forward_loss, has_aux=True)
    return _make_update_step(grad_fn, tx, mesh, accum_steps, lr_schedule,
                             with_accuracy=False, debug_asserts=debug_asserts,
                             ema_decay=ema_decay,
                             health_metrics=health_metrics,
                             guard_skip=guard_skip, pipeline=pipeline)


# the next-token step's own outputs -> the names the deferred logger gives
# them (docs/TOKENS.md, docs/OBSERVABILITY.md): the counts as they are, the
# two ratios among the window's `obs/` values
LM_LOG_KEYS = {
    "tokens": "tokens",
    "moe_local_pairs": "moe_local_pairs",
    "moe_expert_rows_max": "moe_expert_rows_max",
    "moe_expert_rows_mean": "moe_expert_rows_mean",
    "moe_local_pair_share": "obs/moe_local_pair_share",
    "moe_expert_load_max_over_mean": "obs/moe_expert_load_max_over_mean",
    "moe_tight_buffer_share": "obs/moe_tight_buffer_share",
}


def lm_log_values(metrics: dict) -> dict:
    """What of the next-token step's metrics the deferred logger takes: the
    `LM_LOG_KEYS` the step put out, and a looped model's `ut_*` as they are."""
    vals = {name: metrics[k] for k, name in LM_LOG_KEYS.items()
            if k in metrics}
    vals.update({k: v for k, v in metrics.items() if k.startswith("ut_")})
    return vals


def _next_token_batch(batch: dict):
    """tokens (B, T) -> (inputs, targets, weights): position t is scored on
    token t + 1, the last position of a sequence on nothing."""
    tokens = batch["tokens"]
    targets = jnp.roll(tokens, -1, axis=1)
    weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    if batch.get("mask") is not None:
        weights = weights * batch["mask"][:, None]
    return tokens, targets, weights


def make_lm_step(
    model,
    tx: optax.GradientTransformation,
    mesh,
    accum_steps: int = 1,
    lr_schedule: Optional[Callable] = None,
    debug_asserts: bool = False,
    ema_decay: float = 0.0,
    health_metrics: bool = False,
    guard_skip: bool = False,
) -> Callable:
    """Build the next-token step: `step(state, batch, key) -> (state,
    metrics)` over batches {"tokens": (B, T) int32}. The loss is the mean
    cross-entropy of positions 0..T-2 against the tokens that follow them,
    over the vocabulary slice the model holds, in float32 (the model sums it
    in sequence blocks). Besides loss and next-token accuracy the step puts
    out, with no fetch of their own (they ride the deferred logger):
    `tokens` (positions the model ran), `moe_local_pairs` ((token, held
    expert) pairs computed, all layers), `moe_expert_rows_max` /
    `moe_expert_rows_mean` (the fullest held expert's rows and the mean, the
    layer where the ratio is worst), and the two ratios a log window reports,
    `moe_local_pair_share` (pairs a token a layer) and
    `moe_expert_load_max_over_mean`; and `moe_tight_buffer_share`, the share
    of the step's mixture layers that took the tight row buffers
    (`ops/moe.py` `expert_share`: the way a layer takes unless its routing
    is skewed toward the held experts; the others went through in chunks). A model without experts returns no
    `expert_rows`: its step puts out `moe_local_pairs` 0 (no pair was computed)
    and none of the other `moe_*`. A looped model (models/ouro.py) returns
    `ut`, sums over the scored positions by pass: its step puts out
    `ut_exit_mass_<t>` (the mean share of positions' exit distribution on pass
    t), `ut_expected_steps` (the mean of sum_t t p_t) and `ut_loss_<t>` (pass
    t's mean cross-entropy). The gauge `pva_ut_steps` says, while the step is
    traced, how many times it runs the layer stack (1 without a loop)."""

    def forward_loss(params, batch_stats, batch, key):
        del key  # no dropout, no mask to draw
        tokens, targets, weights = _next_token_batch(batch)
        with _count_lowering_sites():
            out = model.apply({"params": params}, tokens, targets=targets,
                              weights=weights, train=True)
        count = out["count"]
        loss = out["loss_sum"] / jnp.maximum(count, 1.0)
        positions = jnp.float32(tokens.size)
        if "expert_rows" in out:
            rows = out["expert_rows"].astype(jnp.float32)     # (layers, held)
            ratio = rows.max(axis=1) / jnp.maximum(rows.mean(axis=1), 1e-9)
            worst = jnp.argmax(ratio)
            outputs = {
                "tokens": positions,
                "moe_local_pairs": rows.sum(),
                "moe_expert_rows_max": rows.max(axis=1)[worst],
                "moe_expert_rows_mean": rows.mean(axis=1)[worst],
                "moe_local_pair_share": rows.sum() / (positions * rows.shape[0]),
                "moe_expert_load_max_over_mean": ratio[worst],
                "moe_tight_buffer_share": jnp.mean(
                    out["expert_tight"].astype(jnp.float32)),
            }
        else:
            outputs = {"tokens": positions,
                       "moe_local_pairs": jnp.zeros((), jnp.float32)}
        ut = out.get("ut")
        passes = 1 if ut is None else ut["exit_mass"].shape[0]
        get_registry().gauge(
            "pva_ut_steps",
            "times the traced next-token step runs its layer stack "
            "(models/ouro.py total_ut_steps; 1 without a loop)").set(passes)
        if ut is not None:
            scored = jnp.maximum(count, 1.0)
            mass, ce = ut["exit_mass"] / scored, ut["loss"] / scored
            for t in range(passes):
                outputs[f"ut_exit_mass_{t + 1}"] = mass[t]
                outputs[f"ut_loss_{t + 1}"] = ce[t]
            outputs["ut_expected_steps"] = jnp.dot(
                mass, jnp.arange(1, passes + 1, dtype=jnp.float32))
        return loss, (batch_stats, out["correct"], count, outputs)

    grad_fn = jax.value_and_grad(forward_loss, has_aux=True)
    return _make_update_step(grad_fn, tx, mesh, accum_steps, lr_schedule,
                             with_accuracy=True, debug_asserts=debug_asserts,
                             ema_decay=ema_decay,
                             health_metrics=health_metrics,
                             guard_skip=guard_skip)


def make_lm_eval_step(model, mesh) -> Callable:
    """Eval for a next-token model: summed cross-entropy and hits over the
    scored positions (the `SumMetrics` contract; `count` counts positions)."""

    def eval_step(state: TrainState, batch: dict) -> dict:
        batch = _constrain_batch(batch, mesh, leading_micro=False)
        eval_params = (state.ema_params if state.ema_params is not None
                       else state.params)
        tokens, targets, weights = _next_token_batch(batch)
        out = model.apply({"params": eval_params}, tokens, targets=targets,
                          weights=weights, train=False)
        return {"loss_sum": out["loss_sum"], "correct": out["correct"],
                "count": out["count"]}

    return jax.jit(eval_step)


def make_pretrain_eval_step(model, mesh) -> Callable:
    """Eval for MAE pretraining: reconstruction loss on held-out clips with
    a deterministic mask (same SumMetrics contract; accuracy reads 0)."""

    def eval_step(state: TrainState, batch: dict) -> dict:
        batch = _constrain_batch(batch, mesh, leading_micro=False)
        eval_params = (state.ema_params if state.ema_params is not None
                       else state.params)
        out = model.apply(
            {"params": eval_params}, batch["video"], train=False,
            rngs={"mask": jax.random.key(0)},
        )
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones((batch["video"].shape[0],), jnp.float32)
        # per-sample recon loss from pred/target so zero-padded val-tail
        # clips don't bias the mean (parity with the supervised eval fix)
        per_sample = jnp.mean(
            (out["pred"].astype(jnp.float32)
             - out["target"].astype(jnp.float32)) ** 2,
            axis=tuple(range(1, out["pred"].ndim)),
        )
        count = mask.sum()
        return {"loss_sum": (per_sample * mask).sum(),
                "correct": jnp.zeros((), jnp.float32), "count": count}

    return jax.jit(eval_step)


def fold_views(inputs):
    """Fold the per-video view axis into the batch dim: clip leaves shaped
    (B, V, T, H, W, C) become (B*V, T, H, W, C); single-view (rank-5) inputs
    pass through. Returns `(inputs, num_views)`. Works on the single-pathway
    tensor and the SlowFast (slow, fast) tuple alike."""
    first = inputs[0] if isinstance(inputs, tuple) else inputs
    num_views = first.shape[1] if first.ndim == 6 else 1
    if num_views > 1:
        inputs = jax.tree.map(
            lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
            inputs,
        )
    return inputs, num_views


def multiview_logits(forward: Callable, inputs):
    """The multi-view logit-averaging protocol (reference uniform-sampler
    tiling, run.py:163), shared by `evaluate()` and the serving engine so
    their top-1 agrees by construction: fold views into the batch (one big
    MXU-friendly forward), then view-average the logits in fp32 before any
    argmax. `forward(clips) -> logits` over view-folded clips."""
    inputs, num_views = fold_views(inputs)
    logits = forward(inputs)
    if num_views > 1:
        logits = logits.astype(jnp.float32).reshape(
            -1, num_views, logits.shape[-1]
        ).mean(axis=1)
    return logits


def make_eval_step(model, mesh, label_smoothing: float = 0.0,
                   device_normalize=None) -> Callable:
    """Build `eval_step(state, batch) -> {loss_sum, correct, count}` —
    in-graph masked sums; the host just adds them across batches
    (trainer/metrics.py), nothing to gather.

    Multi-view eval (reference uniform-sampler tiling, run.py:163): when the
    clip leaves carry a view axis — (B, V, T, H, W, C) from a
    `num_clips > 1` source — `multiview_logits` folds the views into the
    batch for the forward pass and view-averages the logits in-graph before
    the argmax (the same helper the serving engine forwards through)."""

    def eval_step(state: TrainState, batch: dict) -> dict:
        batch = _constrain_batch(batch, mesh, leading_micro=False)
        batch = device_normalize_batch(batch, device_normalize)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(batch["label"].shape, jnp.float32)
        # score the EMA weights when the state carries them (the recipes'
        # eval convention); BN stats stay the live ones
        eval_params = (state.ema_params if state.ema_params is not None
                       else state.params)
        logits = multiview_logits(
            lambda x: model.apply(
                {"params": eval_params, "batch_stats": state.batch_stats},
                x,
                train=False,
            ),
            model_inputs(batch),
        )
        loss, correct, count = _loss_and_metrics(
            logits, batch["label"], mask, label_smoothing
        )
        return {"loss_sum": loss * count, "correct": correct,
                "correct5": _topk_correct(logits, batch["label"], mask),
                "count": count}

    return jax.jit(eval_step)

"""graphcheck dtype pass: catch silent bf16→f32 upcasts feeding compute.

The policy (`create_model(mixed_precision="bf16")`) is bf16 compute /
fp32 params, with *designed* f32 islands (heads, loss math, norm
statistics) routed through `precision.f32_island` and friends. The
failure mode this pass exists for: an undeclared `convert_element_type`
bf16→f32 whose result reaches a `dot_general`/`conv_general_dilated` —
the matmul then runs at the f32 MXU rate with doubled operand bytes,
and nothing in the Python source says so (the AST-level `dtype-literal`
rule catches literal casts; this pass catches what the *graph* actually
computes, including casts introduced by library promotion rules).

Mechanics: taint analysis over the closed jaxpr. A bf16→f32 convert
whose source qualnames (from the eqn's traceback) do NOT match the
island allowlist creates taint; taint propagates through f32-valued
equations (and into/out of pjit/scan/custom-grad sub-jaxprs) and dies
at any downcast (the f32 excursion ended before compute consumed it).
A dot/conv with a tainted f32 operand is a finding. The backward pass
is naturally clean: the transpose of a bf16→f32 convert is a f32→bf16
convert, so cotangents re-enter bf16 before the bwd matmuls.

The allowlist entries match frame function names, file basenames, or
"basename:function" — the PR-4 suppression philosophy (explicit,
auditable, reason-adjacent) applied to graph provenance.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Sequence, Set, Tuple

import numpy as np

# designed f32 islands by qualname: the precision seam, the loss/metric
# math of trainer/steps.py, and the view-averaging eval protocol
DEFAULT_F32_ISLANDS = frozenset({
    "f32_island",            # precision.py — THE declared-island seam
    "_loss_and_metrics",     # trainer/steps.py loss head (fp32 CE)
    "_topk_correct",         # trainer/steps.py top-k in fp32
    "multiview_logits",      # steps.py/serving: fp32 logit averaging
    "device_normalize_batch",  # u8->f32 normalize (input staging, not an
    #                            upcast of bf16 compute — defensive entry)
    # the f32-softmax attention island (ops/attention.py): its BACKWARD
    # necessarily re-enters f32 at the probs-downcast boundary (the
    # transpose of `probs.astype(q.dtype)` is a bf16->f32 convert whose
    # cotangent feeds the dV/dQ matmuls) — the autodiff image of the
    # designed island, not a silent upcast. The router entry point is
    # listed too: inlining can leave it as the innermost user frame of
    # the same converts.
    "dense_attention",
    "fused_attention",
    "dot_product_attention",
    # fused conv/norm/act kernel tier (ops/pallas_fused.py): the file IS
    # the accumulator island — every cast in it routes through
    # f32_island/end_island (the dtype-literal lint rule enforces that at
    # source level), its custom_vjp backwards re-enter f32 at the designed
    # epilogue boundaries (the autodiff image of the islands, exactly the
    # pallas_attention precedent above)
    "pallas_fused.py",
    # models/common.py fused-site helpers: BN batch statistics and the
    # train-mode affine+act tail are accumulator f32 islands by design
    # (nn.BatchNorm computes its stats in f32 too — this is the same
    # policy made explicit); end_island is the precision-seam downcast
    # whose TRANSPOSE is a designed upcast of the cotangent
    "fused_train_norm_act",
    "batch_norm_stats",
    "end_island",
    # serving weight dequantization (serving/quantize.py): int8 -> f32
    # scale multiply -> one downcast to the compute dtype; the upcast
    # starts from int8, never from bf16 compute, but inlining can
    # attribute the scale math here
    "dequantize_tree",
})


def _frames(eqn) -> List[Tuple[str, str]]:
    """[(function_name, file_basename)] user frames, innermost first
    (gc_sharding names its sites with this too). `user_frames` takes the
    Traceback and gives no frames for a missing one; anything it raises
    is an API that moved and must be seen: with no frames every site
    reads `<unknown>` and no allowlist entry can match."""
    from jax._src import source_info_util

    return [(f.function_name, os.path.basename(f.file_name))
            for f in source_info_util.user_frames(eqn.source_info.traceback)]


def _allowlisted(frames: Sequence[Tuple[str, str]],
                 allowlist: Set[str]) -> bool:
    for func, base in frames:
        if (func in allowlist or base in allowlist
                or f"{base}:{func}" in allowlist):
            return True
    return False


def _site(frames: Sequence[Tuple[str, str]]) -> str:
    if not frames:
        return "<unknown>"
    func, base = frames[0]
    return f"{base}:{func}"


def _is_dtype(aval, dtype) -> bool:
    try:
        return np.dtype(aval.dtype) == np.dtype(dtype)
    except TypeError:  # extended dtypes (PRNG keys)
        return False


def _sub_closed(value) -> List[Any]:
    from jax._src import core as jcore

    out = []
    if isinstance(value, jcore.ClosedJaxpr):
        out.append(value)
    elif isinstance(value, (tuple, list)):
        for v in value:
            out.extend(_sub_closed(v))
    return out


def check_dtype(closed_jaxpr, policy: str = "bf16",
                allowlist: Set[str] = DEFAULT_F32_ISLANDS,
                ) -> Tuple[List[dict], Dict[str, Any]]:
    """Run the taint analysis; returns (findings, summary). `policy`
    other than bf16/fp16 means there is no bf16 compute to upcast —
    the pass reports a no-op summary (fp32 parity lanes)."""
    from jax._src import core as jcore

    findings: List[dict] = []
    stats = {"converts_up": 0, "converts_allowlisted": 0,
             "tainted_dots": 0, "tainted_convs": 0}
    if policy not in ("bf16", "fp16"):
        return findings, {**stats, "policy": policy, "skipped": True}

    seen_sites: Set[str] = set()

    def walk(jaxpr, taint: Dict[Any, bool]) -> None:
        def get(v) -> bool:
            return (not isinstance(v, jcore.Literal)) and taint.get(v, False)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            op_taint = any(get(v) for v in eqn.invars)
            if name == "convert_element_type":
                src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
                if (_is_dtype(src, np.dtype("bfloat16"))
                        and _is_dtype(dst, np.float32)):
                    frames = _frames(eqn)
                    if _allowlisted(frames, allowlist):
                        stats["converts_allowlisted"] += 1
                        taint[eqn.outvars[0]] = False
                    else:
                        stats["converts_up"] += 1
                        taint[eqn.outvars[0]] = True
                elif not _is_dtype(dst, np.float32):
                    taint[eqn.outvars[0]] = False  # downcast ends the island
                else:
                    taint[eqn.outvars[0]] = op_taint
                continue
            if name in ("dot_general", "conv_general_dilated"):
                tainted_f32 = any(
                    get(v) and _is_dtype(v.aval, np.float32)
                    for v in eqn.invars)
                if tainted_f32:
                    frames = _frames(eqn)
                    if not _allowlisted(frames, allowlist):
                        site = _site(frames)
                        key = f"{name}@{site}"
                        if key not in seen_sites:
                            seen_sites.add(key)
                            kind = ("tainted_dots" if name == "dot_general"
                                    else "tainted_convs")
                            stats[kind] += 1
                            shapes = [
                                f"{v.aval.dtype}{list(v.aval.shape)}"
                                for v in eqn.invars[:2]]
                            findings.append({
                                "pass": "dtype",
                                "site": site,
                                "message": (
                                    f"f32 {name} reached from bf16 data at "
                                    f"{site} (operands {', '.join(shapes)}): "
                                    "a silent upcast is paying f32 MXU rate "
                                    "+ 2x bytes inside a bf16 policy — "
                                    "declare it via precision.f32_island "
                                    "or add the qualname to the island "
                                    "allowlist"),
                                "details": {"primitive": name,
                                            "frames": [f"{b}:{f}" for f, b
                                                       in _frames(eqn)[:4]]},
                            })
                for ov in eqn.outvars:
                    taint[ov] = op_taint and _is_dtype(ov.aval, np.float32)
                continue
            if name == "scan":
                inner = eqn.params["jaxpr"]
                sub_taint: Dict[Any, bool] = {}
                for iv, inner_v in zip(eqn.invars, inner.jaxpr.invars):
                    sub_taint[inner_v] = get(iv)
                walk(inner.jaxpr, sub_taint)
                for ov, inner_o in zip(eqn.outvars, inner.jaxpr.outvars):
                    taint[ov] = ((not isinstance(inner_o, jcore.Literal))
                                 and sub_taint.get(inner_o, False))
                continue
            if name == "shard_map":
                # SPMD-manual region (parallel/pipeline.py's stage
                # pipeline): the body rides as an OPEN Jaxpr param, which
                # the generic ClosedJaxpr recursion below misses — the
                # pipelined trunk would get zero dtype coverage. Operands
                # map 1:1 (per-shard avals, same dtypes).
                inner = eqn.params["jaxpr"]
                sub_taint = {inner_v: get(iv) for iv, inner_v
                             in zip(eqn.invars, inner.invars)}
                walk(inner, sub_taint)
                for ov, inner_o in zip(eqn.outvars, inner.outvars):
                    taint[ov] = ((not isinstance(inner_o, jcore.Literal))
                                 and sub_taint.get(inner_o, False))
                continue
            subs = []
            for v in eqn.params.values():
                subs.extend(_sub_closed(v))
            if len(subs) == 1 and len(subs[0].jaxpr.invars) == len(
                    eqn.invars):
                # pjit / remat / custom_jvp / closed_call: 1:1 operand map
                inner = subs[0]
                sub_taint = {inner_v: get(iv) for iv, inner_v
                             in zip(eqn.invars, inner.jaxpr.invars)}
                walk(inner.jaxpr, sub_taint)
                for ov, inner_o in zip(eqn.outvars, inner.jaxpr.outvars):
                    taint[ov] = ((not isinstance(inner_o, jcore.Literal))
                                 and sub_taint.get(inner_o, False))
                continue
            for ov in eqn.outvars:
                taint[ov] = op_taint and _is_dtype(ov.aval, np.float32)

    walk(closed_jaxpr.jaxpr, {})
    return findings, {**stats, "policy": policy}

"""graphcheck FLOPs pass: analytical per-primitive FLOPs from the jaxpr.

A count of the step AS TRACED, from shapes alone: a `dot_general`'s
FLOPs are arithmetic over its avals, a conv's over its output grid and
kernel. (The work the benchmark's `step_mfu` divides by is the plain
reference's, `benchmarks/lib/flops.py`: a lowering's padding is not
work.) This pass walks the closed jaxpr (recursing
through pjit/custom-grad calls, multiplying scanned bodies by their trip
count) and counts:

- `dot`: 2 * batch * M * N * K per dot_general;
- `conv`: 2 * out_elements * kernel_spatial * (C_in / feature_groups)
  per conv_general_dilated (the backward data/filter convs are plain
  conv eqns in the differentiated jaxpr, so fwd+bwd is counted
  naturally, remat recompute included);
- `elementwise`/`reduce`: 1 FLOP per output (resp. input) element for
  the plain arithmetic primitives (BN/activation traffic is a few
  percent on conv nets). Transcendentals (exp/log/...) are deliberately
  *excluded*, as XLA books them under "transcendental", not "flops".

`while` bodies can't be statically counted (trip count is dynamic);
they are counted ONCE and surfaced in `caveats` — a lying silent zero
is worse than a flagged lower bound. `cond` takes the max branch.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Tuple

# 1-FLOP-per-element arithmetic primitives (XLA cost-model "flops" class).
# Selects and comparisons ARE counted: the guard-armed train step wraps
# every state leaf in jnp.where (param-sized select_n trees), and XLA
# books those as flops — excluding them put the armed ViT-B step 35%
# under the cost model. Transcendentals (exp/log/erf/...) stay excluded:
# XLA reports them under "transcendental", not "flops".
_ELEMENTWISE_1FLOP = frozenset({
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "rem",
    "add_any", "square", "integer_pow", "pow", "rsqrt", "sqrt",
    "select_n", "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor",
    "not", "is_finite", "sign", "floor", "ceil", "round",
})
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
})
# cross-shard collectives: data movement, zero math. Registered so the
# pipelined step's stage rotation (ppermute) and the gradient syncs can
# never read as uncounted compute; tracked in eqn_counts["collective"].
_COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pshuffle", "psum", "psum2", "pmax", "pmin", "pgather",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
})


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# --- pallas_call costing ----------------------------------------------------
#
# A `pallas_call` is opaque to this walk (its body is a kernel jaxpr whose
# eqns describe ONE grid program, not the whole op), so an unregistered
# Pallas kernel would silently undercount the step — exactly the
# lying-numerator failure this pass exists to prevent. Every in-tree
# kernel therefore registers a per-kernel FLOPs hook here, keyed by the
# kernel FUNCTION name (the kernel jaxpr's debug_info.func_name, or the
# `name=` given to pallas_call), computing from the eqn's avals; a pallas_call with no hook becomes a finding in
# `check_flops` (and `--selftest` seeds one to prove the detector works).

PALLAS_FLOPS_HOOKS: Dict[str, Callable[[Any], float]] = {}


def register_pallas_flops(kernel_name: str,
                          fn: Callable[[Any], float]) -> None:
    """Register `fn(eqn) -> flops` for the Pallas kernel function named
    `kernel_name` (docs/KERNELS.md § adding a kernel)."""
    PALLAS_FLOPS_HOOKS[kernel_name] = fn


def pallas_kernel_name(eqn) -> str:
    return (eqn.params.get("name")
            or eqn.params["jaxpr"].debug_info.func_name)


def _pw_kernel_flops(eqn) -> float:
    # ops/pallas_fused._pw_bn_act_kernel: x (M, Cin) @ w (Cin, Cout)
    # + per-row bias/act epilogue
    x, w = (v.aval for v in eqn.invars[:2])
    m, cin = x.shape
    cout = w.shape[-1]
    return 2.0 * m * cin * cout + 2.0 * m * cout


def _conv_kernel_flops(eqn) -> float:
    # ops/pallas_fused._conv_bn_act_kernel: taps MXU matmuls per output
    # element (w flattened (taps, Cin, Cout)) + bias/act epilogue
    w = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    taps, cin, _ = w.shape
    out_elems = _prod(out.shape)
    return 2.0 * out_elems * taps * cin + 2.0 * out_elems


def _dw_kernel_flops(eqn) -> float:
    # ops/pallas_depthwise._dw_kernel / pallas_fused._dw_bn_act_kernel:
    # taps VPU FMAs per output element (k flattened (taps, C)); the
    # epilogue variant adds bias+act
    k = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    return 2.0 * _prod(out.shape) * k.shape[0] + 2.0 * _prod(out.shape)


def _attn_flops(products: int) -> Callable[[Any], float]:
    # ops/pallas_attention kernels: a grid step is one (block_q, block_k)
    # tile of every query head of a key head, and the grid's last axis walks
    # only the tiles the mask lets through. The kernel's refs after the three
    # prefetched tables: q (block_q, group * d), k (block_k, d). Products a
    # tile: forward q k^T + p v = 2; dq: scores, dp, ds k = 3; dk/dv: scores,
    # dv, dp, dk = 4
    def hook(eqn) -> float:
        refs = eqn.params["jaxpr"].invars
        (block_q, width), (block_k, _) = refs[3].aval.shape, refs[4].aval.shape
        steps = _prod(eqn.params["grid_mapping"].grid)
        return 2.0 * products * steps * block_q * block_k * width

    return hook


def _gdn_flops(backward: bool) -> Callable[[Any], float]:
    # ops/pallas_gated_delta kernels: q (B, T, hk*dk), v (B, T, hv*dv), gamma
    # (B, hv, chunks, C); a state operand or result (..., dk, dv) gives dk.
    # The products a chunk: a key head's q k^T and k k^T, a value head's
    # Neumann inverse (forward) and its products with the state and d
    def hook(eqn) -> float:
        q, _, v, gam = (x.aval for x in eqn.invars[:4])
        state = (eqn.invars[5] if backward else eqn.outvars[1]).aval
        b, hv, n, c = gam.shape
        dk, dv = state.shape[-2:]
        hk = q.shape[-1] // dk
        if backward:
            key_head = 6 * 2.0 * c * c * dk
            value_head = 2.0 * c * dv * (7 * dk + 5 * c)
        else:
            levels = max(c.bit_length() - 2, 0)
            key_head = 2 * 2.0 * c * c * dk
            inverse = (2.0 * c ** 3 * (2 + 2 * (levels - 1)) if levels
                       else 0.0)
            value_head = inverse + 2.0 * c * dv * (3 * dk + 2 * c)
        return b * n * (hk * key_head + hv * value_head)

    return hook


# in-tree kernels, by the stable `name=` their pallas_call gives (also the
# kernel's name in a device trace)
PALLAS_FLOPS_HOOKS.update({
    "pva_fused_pointwise_bn_act": _pw_kernel_flops,
    "pva_fused_conv3d_bn_act": _conv_kernel_flops,
    "pva_fused_depthwise_bn_act": _dw_kernel_flops,
    "pva_depthwise3d_s1": _dw_kernel_flops,
    "pva_attn_fwd": _attn_flops(2),
    "pva_attn_dq": _attn_flops(3),
    "pva_attn_dkv": _attn_flops(4),
    "pva_gdn_fwd": _gdn_flops(backward=False),
    "pva_gdn_fwd_saving": _gdn_flops(backward=False),
    "pva_gdn_bwd": _gdn_flops(backward=True),
})


def dot_general_flops(eqn) -> float:
    """2 * batch * M * N * K from the eqn's avals + dimension_numbers."""
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = _prod(lhs.shape[d] for d in lb)
    contract = _prod(lhs.shape[d] for d in lc)
    m = _prod(lhs.shape[d] for d in range(len(lhs.shape))
              if d not in set(lc) | set(lb))
    n = _prod(rhs.shape[d] for d in range(len(rhs.shape))
              if d not in set(rc) | set(rb))
    return 2.0 * batch * m * n * contract


def _conv_valid_taps(out_size: int, k: int, stride: int, pad_lo: int,
                     lhs_dil: int, rhs_dil: int, in_size: int) -> int:
    """Real multiply-adds along one spatial dim: XLA's cost model counts
    only taps that land on actual input elements — padding positions and
    the zeros interleaved by lhs_dilation (backward-data convs) cost
    nothing, so an analytic count that ignores them overshoots SAME-padded
    nets by ~15% and backward passes by more."""
    span = (in_size - 1) * lhs_dil + 1
    taps = 0
    for o in range(out_size):
        base = o * stride - pad_lo
        for d in range(k):
            p = base + d * rhs_dil
            if 0 <= p < span and p % lhs_dil == 0:
                taps += 1
    return taps


def conv_flops(eqn) -> float:
    """2 * batch * C_out * (C_in / feature_groups) * valid_taps, exactly
    the real-multiply-add count the XLA cost model reports."""
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    strides = eqn.params["window_strides"]
    padding = eqn.params["padding"]
    lhs_dil = eqn.params.get("lhs_dilation") or (1,) * len(strides)
    rhs_dil = eqn.params.get("rhs_dilation") or (1,) * len(strides)
    taps = 1
    for i, (ld, rd) in enumerate(zip(dn.lhs_spec[2:], dn.rhs_spec[2:])):
        taps *= _conv_valid_taps(
            out.shape[dn.out_spec[2 + i]], rhs.shape[rd], strides[i],
            padding[i][0], lhs_dil[i], rhs_dil[i], lhs.shape[ld])
    batch = out.shape[dn.out_spec[0]]
    c_out = out.shape[dn.out_spec[1]]
    c_in_per_group = rhs.shape[dn.rhs_spec[1]]  # already / feature_groups
    return 2.0 * batch * c_out * c_in_per_group * taps


def _sub_closed(params_value) -> List[Any]:
    """ClosedJaxpr values inside one eqn-param value (tuples recursed)."""
    from jax._src import core as jcore

    out = []
    if isinstance(params_value, jcore.ClosedJaxpr):
        out.append(params_value)
    elif isinstance(params_value, (tuple, list)):
        for v in params_value:
            out.extend(_sub_closed(v))
    return out


def jaxpr_flops(closed_jaxpr) -> Dict[str, Any]:
    """Analytical FLOPs of a closed jaxpr: total + per-class breakdown +
    caveats (unstatically-countable constructs encountered)."""
    counts = {"dot": 0.0, "conv": 0.0, "elementwise": 0.0, "reduce": 0.0,
              "pallas": 0.0}
    eqn_counts = {"dot_general": 0, "conv_general_dilated": 0,
                  "pallas_call": 0, "collective": 0}
    caveats: List[str] = []
    unregistered: List[str] = []
    hook_errors: List[str] = []

    def walk(jaxpr, mult: float) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                kname = pallas_kernel_name(eqn)
                hook = PALLAS_FLOPS_HOOKS.get(kname)
                eqn_counts["pallas_call"] += 1
                if hook is None:
                    # a silent zero here would quietly deflate
                    # the count — surface it (check_flops turns the
                    # list into findings)
                    unregistered.append(kname)
                else:
                    try:
                        counts["pallas"] += mult * float(hook(eqn))
                    except Exception as e:  # noqa: BLE001 - see below
                        # hooks key on bare kernel-function names; a name
                        # collision hands this hook an eqn whose avals it
                        # can't parse. That must surface as a finding,
                        # never crash the whole graphcheck run or book a
                        # wrong count for the colliding kernel.
                        hook_errors.append(
                            f"{kname}: {type(e).__name__}: {e}")
                continue
            if name == "dot_general":
                counts["dot"] += mult * dot_general_flops(eqn)
                eqn_counts["dot_general"] += 1
            elif name == "conv_general_dilated":
                counts["conv"] += mult * conv_flops(eqn)
                eqn_counts["conv_general_dilated"] += 1
            elif name in _ELEMENTWISE_1FLOP:
                counts["elementwise"] += mult * _prod(
                    eqn.outvars[0].aval.shape)
            elif name in _REDUCE_PRIMS:
                counts["reduce"] += mult * _prod(eqn.invars[0].aval.shape)
            elif name == "scan":
                inner = eqn.params["jaxpr"]
                walk(inner.jaxpr, mult * int(eqn.params.get("length", 1)))
            elif name == "shard_map":
                # SPMD-manual region (parallel/pipeline.py's stage
                # pipeline): the body is an OPEN Jaxpr param describing
                # ONE shard's program — the generic ClosedJaxpr recursion
                # below misses it, silently zeroing the whole pipelined
                # trunk out of the count. Every manual mesh slice runs
                # the body once, so global FLOPs = body x manual-shard
                # count. (This counts the pipeline's fill/drain garbage
                # ticks too: they execute on the MXU, so they belong in
                # an achieved-utilization numerator — the waste is
                # reported separately as pipeline_bubble_frac.)
                mesh = eqn.params.get("mesh")
                auto = eqn.params.get("auto") or frozenset()
                shards = 1
                if mesh is not None:
                    for ax, sz in dict(mesh.shape).items():
                        if ax not in auto:
                            shards *= int(sz)
                walk(eqn.params["jaxpr"], mult * shards)
            elif name in _COLLECTIVE_PRIMS:
                # cross-shard data movement, zero math: ppermute is the
                # pipeline's stage rotation, psum/all_gather the gradient
                # sync. Counted for visibility, never as FLOPs — but
                # REGISTERED here so a new collective can't fall into the
                # generic recursion and look like an uncounted op.
                eqn_counts["collective"] += 1
            elif name == "while":
                # dynamic trip count: count the body ONCE, flag it
                caveats.append("while_loop counted once (dynamic trip "
                               "count)")
                walk(eqn.params["body_jaxpr"].jaxpr, mult)
            elif name == "cond":
                branch_totals = []
                for br in eqn.params["branches"]:
                    sub = jaxpr_flops(br)
                    branch_totals.append(sub)
                    caveats.extend(sub["caveats"])
                if branch_totals:
                    best = max(branch_totals,
                               key=lambda s: s["flops_total"])
                    for k in counts:
                        counts[k] += mult * best["by_class"][k]
                    for k in eqn_counts:
                        eqn_counts[k] += best["eqn_counts"][k]
                    unregistered.extend(best["unregistered_pallas"])
                    hook_errors.extend(best["pallas_hook_errors"])
            else:
                # generic recursion: pjit / remat / custom_jvp / custom_vjp
                # / closed_call all carry their body as ClosedJaxpr params
                for v in eqn.params.values():
                    for sub in _sub_closed(v):
                        walk(sub.jaxpr, mult)

    walk(closed_jaxpr.jaxpr, 1.0)
    total = sum(counts.values())
    return {
        "flops_total": total,
        "by_class": counts,
        "eqn_counts": eqn_counts,
        "caveats": sorted(set(caveats)),
        "unregistered_pallas": sorted(set(unregistered)),
        "pallas_hook_errors": sorted(set(hook_errors)),
    }


def check_flops(closed_jaxpr) -> Tuple[List[dict], Dict[str, Any]]:
    """The pass: the analytic count, with a finding for every
    `pallas_call` it cannot cost (no registered hook, or a hook that
    failed) and for a count that is not finite."""
    analytic = jaxpr_flops(closed_jaxpr)
    summary = dict(analytic)
    findings: List[dict] = []
    for kname in analytic["unregistered_pallas"]:
        findings.append({
            "pass": "flops",
            "site": f"pallas_call:{kname}",
            "message": (
                f"pallas_call kernel {kname!r} has no registered FLOPs "
                "hook: the analytic count books it as ZERO — register "
                "one via "
                "gc_flops.register_pallas_flops (docs/KERNELS.md § "
                "adding a kernel)"),
            "details": {"kernel": kname},
        })
    for err in analytic["pallas_hook_errors"]:
        findings.append({
            "pass": "flops",
            "site": f"pallas_call:{err.split(':', 1)[0]}",
            "message": (
                f"registered FLOPs hook failed on pallas_call ({err}): "
                "likely a kernel-function NAME COLLISION handing the hook "
                "avals it can't parse — rename the kernel or register a "
                "hook that matches it (docs/KERNELS.md § adding a "
                "kernel); its FLOPs are booked as zero until fixed"),
            "details": {"error": err},
        })
    if summary["caveats"]:
        summary["lower_bound"] = True
    # guard against NaN/inf arithmetic surprises
    if not math.isfinite(summary["flops_total"]):
        findings.append({
            "pass": "flops", "site": "whole-program",
            "message": "analytic FLOPs overflowed to a non-finite value",
            "details": {},
        })
    return findings, summary

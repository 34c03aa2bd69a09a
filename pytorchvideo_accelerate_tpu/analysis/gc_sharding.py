"""graphcheck sharding pass: find implicit regathers of sharded inputs.

GSPMD propagates the in-shardings through the graph and inserts whatever
collectives make each eqn's operands compatible — silently. Most of what
it inserts is the plan (the DP gradient psum, halo exchanges); the
hazard is the *implicit full regather*: an eqn whose operand shardings
cannot be reconciled, so a batch- or model-sharded tensor is all-gathered
onto every device right in the hot path (HBM spike + ICI traffic that
no source line admits to).

This pass re-propagates the in-shardings statically over the closed
jaxpr — dim→axes maps flowing through elementwise/broadcast/transpose/
reshape/reduce/dot/conv/scan/pjit — and flags the three reconciliation
points where a regather is forced rather than chosen:

- `dot_general` whose contracting dims are sharded on one operand but
  not matching on the other (one side must be gathered before the
  matmul; the agreeing case — both sides sharded alike — is the normal
  psum-after-partial-matmul plan and is NOT flagged);
- `reshape` that destroys a sharded dim's block structure (the sharded
  dim is not the major factor of its reshape group, or the new major
  extent doesn't tile by it) — GSPMD must relayout the full tensor;
- `concatenate` along a sharded dim.

Everything it can't model (gather/while/dynamic slicing) conservatively
drops the mapping instead of guessing: a lost mapping can only cause
false NEGATIVES downstream, never a false alarm — the right polarity
for a gate that must hold `graphcheck_findings == 0` on the clean tree.
Reverses (`rev`) keep their mapping un-flagged: GSPMD lowers a reversal
of a sharded dim to a one-hop collective permute (the mixup flipped-
batch idiom), not a regather.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from pytorchvideo_accelerate_tpu.analysis.gc_dtype import (
    _frames,
    _site as _site_of,
)

DimMap = Dict[int, Tuple[str, ...]]  # dim index -> mesh axis names

_ELEMENTWISE_SAFE = True  # same-shape eqns merge operand maps


def _site(eqn) -> str:
    return _site_of(_frames(eqn))


def spec_to_dim_map(spec, ndim: int) -> DimMap:
    """PartitionSpec -> {dim: axes}; None/missing entries dropped."""
    out: DimMap = {}
    if spec is None:
        return out
    for d, entry in enumerate(tuple(spec)[:ndim]):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if axes:
            out[d] = tuple(str(a) for a in axes)
    return out


def sharding_dim_map(sharding, ndim: int) -> DimMap:
    """NamedSharding -> dim map; anything else (SingleDevice, GSPMD
    without a spec) -> empty (conservative)."""
    spec = getattr(sharding, "spec", None)
    return spec_to_dim_map(spec, ndim)


def _bytes_of(aval) -> int:
    itemsize = int(getattr(getattr(aval, "dtype", None), "itemsize", 4))
    return int(np.prod(aval.shape, dtype=np.int64)) * itemsize


def _reshape_groups(in_shape, out_shape):
    """Greedy factor groups [(in_dims, out_dims)] with equal products."""
    groups = []
    i = j = 0
    ni, nj = len(in_shape), len(out_shape)
    while i < ni or j < nj:
        gi, gj = [i], [j] if j < nj else []
        pi = in_shape[i] if i < ni else 1
        pj = out_shape[j] if j < nj else 1
        i += 1
        j += 1
        while pi != pj:
            if pi < pj:
                if i >= ni:
                    break
                pi *= in_shape[i]
                gi.append(i)
                i += 1
            else:
                if j >= nj:
                    break
                pj *= out_shape[j]
                gj.append(j)
                j += 1
        groups.append((gi, gj))
    return groups


def check_sharding(closed_jaxpr, in_dim_maps: Sequence[DimMap],
                   allowlist: Optional[Set[str]] = None,
                   min_bytes: int = 1 << 16,
                   ) -> Tuple[List[dict], Dict[str, Any]]:
    """Propagate `in_dim_maps` (one per flat jaxpr input, in order) and
    flag forced regathers. `min_bytes`: ignore regathers of small
    tensors (a gathered scalar/bias is noise; the hazard is clip-sized
    and params-sized tensors)."""
    from jax._src import core as jcore

    allowlist = allowlist or set()
    findings: List[dict] = []
    seen: Set[str] = set()
    stats = {"tracked_inputs": sum(1 for m in in_dim_maps if m),
             "dot_regathers": 0, "reshape_losses": 0, "concat_regathers": 0}

    def sub_closed(value):
        out = []
        if isinstance(value, jcore.ClosedJaxpr):
            out.append(value)
        elif isinstance(value, (tuple, list)):
            for v in value:
                out.extend(sub_closed(v))
        return out

    def emit(kind: str, stat: str, eqn, message: str, nbytes: int):
        site = _site(eqn)
        fr = _frames(eqn)
        if any(f in allowlist or b in allowlist or f"{b}:{f}" in allowlist
               for f, b in fr):
            return
        key = f"{kind}@{site}"
        if key in seen:
            return
        seen.add(key)
        stats[stat] += 1
        findings.append({
            "pass": "sharding", "site": site,
            "message": message,
            "details": {"kind": kind, "bytes": nbytes,
                        "frames": [f"{b}:{f}" for f, b in fr[:4]]},
        })

    def walk(jaxpr, env: Dict[Any, DimMap]) -> None:
        def get(v) -> DimMap:
            if isinstance(v, jcore.Literal):
                return {}
            return env.get(v, {})

        def put(v, m: DimMap) -> None:
            if m:
                env[v] = m

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = eqn.invars
            outs = eqn.outvars
            if name == "dot_general":
                lm, rm = get(ins[0]), get(ins[1])
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                for ld, rd in zip(lc, rc):
                    la, ra = lm.get(ld), rm.get(rd)
                    if la == ra:
                        continue  # agreeing (incl. both-None): psum plan
                    shard_side, aval = ((("lhs", ins[0].aval) if la else
                                         ("rhs", ins[1].aval)))
                    nbytes = _bytes_of(aval)
                    if nbytes < min_bytes:
                        continue
                    axes = la or ra
                    emit(
                        "dot-contract", "dot_regathers", eqn,
                        f"dot_general at {_site(eqn)} contracts a dim "
                        f"sharded over {axes} on its {shard_side} "
                        f"({aval.dtype}{list(aval.shape)}, {nbytes} B) "
                        "while the other operand is not sharded to match "
                        "— GSPMD must all-gather one side before the "
                        "matmul (implicit full regather in the hot path)",
                        nbytes)
                # out: batch dims then lhs free then rhs free
                om: DimMap = {}
                pos = 0
                for ld in lb:
                    if ld in lm:
                        om[pos] = lm[ld]
                    pos += 1
                for d in range(len(ins[0].aval.shape)):
                    if d in set(lc) | set(lb):
                        continue
                    if d in lm:
                        om[pos] = lm[d]
                    pos += 1
                for d in range(len(ins[1].aval.shape)):
                    if d in set(rc) | set(rb):
                        continue
                    if d in rm:
                        om[pos] = rm[d]
                    pos += 1
                put(outs[0], om)
                continue
            if name == "conv_general_dilated":
                dn = eqn.params["dimension_numbers"]
                lm = get(ins[0])
                om = {}
                if dn.lhs_spec[0] in lm:
                    om[dn.out_spec[0]] = lm[dn.lhs_spec[0]]
                put(outs[0], om)
                continue
            if name == "reshape":
                m = get(ins[0])
                if not m:
                    continue
                in_shape = tuple(ins[0].aval.shape)
                out_shape = tuple(outs[0].aval.shape)
                om = {}
                groups = _reshape_groups(in_shape, out_shape)
                for d, axes in m.items():
                    grp = next((g for g in groups if d in g[0]), None)
                    if grp is None or not grp[1]:
                        continue
                    major_in = grp[0][0]
                    major_out = grp[1][0]
                    in_d = in_shape[d]
                    out_first = out_shape[major_out]
                    if d == major_in and in_d > 0 and (
                            out_first % in_d == 0 or in_d % out_first == 0):
                        om[major_out] = axes
                        continue
                    nbytes = _bytes_of(ins[0].aval)
                    if nbytes < min_bytes:
                        continue
                    emit(
                        "reshape-loss", "reshape_losses", eqn,
                        f"reshape at {_site(eqn)} "
                        f"{list(in_shape)}->{list(out_shape)} destroys the "
                        f"block structure of dim {d} sharded over {axes} "
                        f"({nbytes} B): GSPMD must relayout the full "
                        "tensor (implicit regather)",
                        nbytes)
                put(outs[0], om)
                continue
            if name == "concatenate":
                dim = eqn.params["dimension"]
                maps = [get(v) for v in ins]
                for v, m in zip(ins, maps):
                    if dim in m:
                        nbytes = _bytes_of(v.aval)
                        if nbytes >= min_bytes:
                            emit(
                                "concat-sharded-dim", "concat_regathers",
                                eqn,
                                f"concatenate at {_site(eqn)} joins along "
                                f"dim {dim} sharded over {m[dim]} "
                                f"({nbytes} B): the shards must be "
                                "gathered to lay out the result",
                                nbytes)
                om = {}
                for m in maps:
                    for d, axes in m.items():
                        if d != dim:
                            om.setdefault(d, axes)
                put(outs[0], om)
                continue
            if name == "transpose":
                m = get(ins[0])
                perm = eqn.params["permutation"]
                put(outs[0], {j: m[perm[j]] for j in range(len(perm))
                              if perm[j] in m})
                continue
            if name == "broadcast_in_dim":
                m = get(ins[0])
                bd = eqn.params["broadcast_dimensions"]
                put(outs[0], {bd[d]: axes for d, axes in m.items()
                              if d < len(bd)})
                continue
            if name in ("reduce_sum", "reduce_max", "reduce_min",
                        "reduce_prod", "reduce_and", "reduce_or",
                        "argmax", "argmin"):
                m = get(ins[0])
                axes_p = eqn.params.get("axes", ())
                dropped = set(axes_p)
                om = {}
                for d, ax in m.items():
                    if d in dropped:
                        continue  # reduction over a shard = psum, fine
                    om[d - sum(1 for a in dropped if a < d)] = ax
                put(outs[0], om)
                continue
            if name in ("reduce_window_max", "reduce_window_sum",
                        "select_and_scatter_add"):
                src = ins[-1] if name == "select_and_scatter_add" else ins[0]
                m = get(src)
                wd = eqn.params.get("window_dimensions")
                if wd is not None:
                    put(outs[0], {d: ax for d, ax in m.items()
                                  if d < len(wd) and wd[d] == 1})
                continue
            if name in ("rev", "convert_element_type", "copy",
                        "stop_gradient", "select_n", "pad"):
                src = ins[1] if name == "select_n" and len(ins) > 1 else ins[0]
                put(outs[0], dict(get(src)))
                continue
            if name == "sharding_constraint":
                sh = eqn.params.get("sharding")
                m = sharding_dim_map(sh, len(outs[0].aval.shape))
                put(outs[0], m or dict(get(ins[0])))
                continue
            if name == "scan":
                inner = eqn.params["jaxpr"]
                n_c = eqn.params["num_consts"]
                n_k = eqn.params["num_carry"]
                sub_env: Dict[Any, DimMap] = {}
                for k, (iv, sv) in enumerate(
                        zip(ins, inner.jaxpr.invars)):
                    m = get(iv)
                    if k >= n_c + n_k:  # xs: leading scan axis sliced off
                        m = {d - 1: ax for d, ax in m.items() if d > 0}
                    sub_env[sv] = m
                walk(inner.jaxpr, sub_env)
                for k, (ov, so) in enumerate(
                        zip(outs, inner.jaxpr.outvars)):
                    if isinstance(so, jcore.Literal):
                        continue
                    m = sub_env.get(so, {})
                    if k >= n_k:  # ys: stacked along a new leading axis
                        m = {d + 1: ax for d, ax in m.items()}
                    put(ov, m)
                continue
            subs = []
            for v in eqn.params.values():
                subs.extend(sub_closed(v))
            if len(subs) == 1 and len(subs[0].jaxpr.invars) == len(ins):
                inner = subs[0]
                sub_env = {sv: get(iv)
                           for iv, sv in zip(ins, inner.jaxpr.invars)}
                walk(inner.jaxpr, sub_env)
                for ov, so in zip(outs, inner.jaxpr.outvars):
                    if not isinstance(so, jcore.Literal):
                        put(ov, sub_env.get(so, {}))
                continue
            # same-shape elementwise: merge operand maps (first wins)
            out_shape = tuple(getattr(outs[0].aval, "shape", ()))
            if _ELEMENTWISE_SAFE and all(
                    tuple(getattr(v.aval, "shape", ())) == out_shape
                    for v in ins if not isinstance(v, jcore.Literal)):
                om = {}
                for v in ins:
                    for d, ax in get(v).items():
                        om.setdefault(d, ax)
                for ov in outs:
                    if tuple(getattr(ov.aval, "shape", ())) == out_shape:
                        put(ov, dict(om))
                continue
            # unknown structure: drop the mapping (conservative — can
            # only suppress findings, never invent one)

    env: Dict[Any, DimMap] = {}
    for v, m in zip(closed_jaxpr.jaxpr.invars, in_dim_maps):
        if m:
            env[v] = dict(m)
    walk(closed_jaxpr.jaxpr, env)
    return findings, stats

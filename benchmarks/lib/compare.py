"""The comparison that decides `correct` for a training cell.

The program's first optimizer steps (through the window's own call and feed)
against the plain float32 reference following the same batches from the same
seeded weights. Compared, each against a limit of its own (PERF.md gives the
readings each limit was set from):

* `loss_rel_step<i>`: each step's loss, |program - reference| / |reference|;
* `grad_gap_median` / `grad_gap_worst`: the first gradient as the optimizer
  got it, worked out from the program's momentum buffer after one step
  (buffer - wd * p0); by leaf | ||g_prog|| - ||g_ref|| | / max(||g_ref||,
  median leaf's), then the median leaf and the worst leaf;
* `delta_gap_median` / `delta_gap_worst`: the parameters' change over the
  steps, the same measure, over the leaves whose reference gradient is at
  least a thousandth of the median leaf's (the others move by round-off);
* `grad_gap_worst_wide` / `delta_gap_worst_wide`: the worst leaf among those
  of at least `WIDE` elements. A leaf's norm averages the element-wise noise
  of the policy's compute type over its elements, so the gap falls with the
  leaf's size (PERF.md, Findings, gives the readings by size); over all
  leaves the worst is an 8-element norm scale and swings between 0.4 and 1.5;
* the batch as the pipeline placed it: shapes and type (`input_shape_gap`),
  values outside the normalised pixel range and labels outside the classes
  (`input_range_out`), the channels' mean against the one the cell's file
  states, in normalised units (`input_mean_gap`), and what the reference
  cuts from the batch itself against what the pipeline cut
  (`input_derived_gap`: SlowFast's slow pathway);
* exact: no two rows of the first batches alike, `fit()`'s step count equal
  to the harness's, no recompile after the first step.

Which of these have a limit (and so decide `correct`) is the cell's choice,
in `workloads/<cell>.json`; the others are shown beside them.
"""

from __future__ import annotations

import math

import numpy as np


def _flat(tree):
    import jax

    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norm(x):
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def duplicate_rows(batches):
    """Rows of the checked batches that equal another row (by the bytes of
    the row's first clip tensor); the contract wants rows that all differ."""
    seen, dup = set(), 0
    for batch in batches:
        key = next(k for k in ("fast", "video", "slow") if k in batch)
        for row in np.asarray(batch[key]):
            h = hash(row.tobytes())
            dup += h in seen
            seen.add(h)
    return dup


WIDE = 4096  # elements: the leaves `*_gap_worst_wide` is taken over
SIZE_CLASSES = ((1, 64), (64, 512), (512, WIDE), (WIDE, 65536), (65536, 2 ** 62))


def gaps_by_size(program, ref, sizes, keep=None):
    """[(low, high, leaves, worst, median)] of the leaf gaps by the leaves'
    number of elements: the look behind `*_gap_worst_wide`, shown on
    standard error in every run."""
    gaps = leaf_gaps(program, ref, keep)
    rows = []
    for low, high in SIZE_CLASSES:
        g = [v for n, v in gaps.items() if low <= sizes[n] < high]
        if g:
            rows.append((low, high, len(g), max(g), float(np.median(g))))
    return rows


def input_numbers(batches, expect):
    """The placed batches against what the cell's and the configuration's
    files state: `expect` = {"shapes": {key: shape}, "dtype", "num_classes",
    "low", "high" (the normalised pixel range), "mean" (per channel),
    "derive": fn(batch) -> {key: array}}."""
    shape_gap = range_out = 0
    mean_gap = derived_gap = 0.0
    tol = 2.0 ** -7  # half a bfloat16 step at the range's far end
    for batch in batches:
        for key, shape in expect["shapes"].items():
            x = batch.get(key)
            if x is None or tuple(x.shape) != tuple(shape) \
                    or str(x.dtype) != expect["dtype"]:
                shape_gap += 1
                continue
            x = np.asarray(x, np.float32)
            range_out += int(np.sum((x < expect["low"] - tol)
                                    | (x > expect["high"] + tol)))
            mean = x.mean(axis=tuple(range(x.ndim - 1)), dtype=np.float64)
            mean_gap = max(mean_gap, float(np.max(np.abs(mean - expect["mean"]))))
        label = np.asarray(batch["label"])
        shape_gap += label.shape != (next(iter(expect["shapes"].values()))[0],)
        range_out += int(np.sum((label < 0) | (label >= expect["num_classes"])))
        for key, ours in expect["derive"](batch).items():
            theirs = np.asarray(batch[key], np.float32)
            ours = np.asarray(ours, np.float32)
            derived_gap = max(derived_gap, float("inf") if ours.shape != theirs.shape
                              else float(np.max(np.abs(ours - theirs))))
    return {"input_shape_gap": shape_gap, "input_range_out": range_out,
            "input_derived_gap": derived_gap, "input_mean_gap": mean_gap}


def leaf_gaps(program, ref, keep=None):
    """By leaf: | ||program|| - ||ref|| | / max(||ref||, median ||ref||), the
    gap between the two norms (not the norm of their difference)."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return {n: abs(program[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names}


def norm_gap(program, ref, keep=None, among=None):
    """The worst leaf's gap and its name; `among` narrows the leaves the
    worst is looked for in, not the leaves the median leaf is taken over."""
    gaps = leaf_gaps(program, ref, keep)
    if among is not None:
        gaps = {n: g for n, g in gaps.items() if n in among}
    if not all(math.isfinite(g) for g in gaps.values()):
        return float("inf"), next(n for n, g in gaps.items() if not math.isfinite(g))
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def median_gap(program, ref, keep=None):
    gaps = list(leaf_gaps(program, ref, keep).values())
    if not all(math.isfinite(g) for g in gaps):
        return float("inf")
    return float(np.median(gaps))


def program_norms(params0, momentum_after_1, params_after, weight_decay):
    """Host arithmetic on the program's snapshots: per-leaf norms of its
    first gradient and of its parameters' change."""
    p0, m1, pn = _flat(params0), _flat(momentum_after_1), _flat(params_after)
    grad = {n: _norm(np.asarray(m1[n], np.float64)
                     - weight_decay * np.asarray(p0[n], np.float64))
            for n in p0}
    delta = {n: _norm(np.asarray(pn[n], np.float64)
                      - np.asarray(p0[n], np.float64)) for n in p0}
    return grad, delta


def judge(program_losses, prog_grad, prog_delta, ref, limits, structure):
    """Every reading, each {"name","value","limit","ok","note"}. A reading
    whose name has a limit in `limits` is compared; the others (limit None)
    are shown beside them and decide nothing (PERF.md says why each is not)."""
    numbers = []

    def add(name, value, limit=None, note=""):
        value = float("inf") if value is None else float(value)
        limit = limits.get(name, limit)
        numbers.append({"name": name, "value": value, "limit": limit,
                        "ok": bool(limit is None or value <= limit),
                        "note": note})

    for i, ref_loss in enumerate(ref["losses"]):
        got = program_losses[i] if i < len(program_losses) else None
        rel = None if got is None else abs(got - ref_loss) / abs(ref_loss)
        add(f"loss_rel_step{i + 1}", rel,
            note=f"program {got} reference {ref_loss}")
    med = float(np.median(list(ref["grad_norms"].values())))
    moving = {n for n, g in ref["grad_norms"].items() if g >= 1e-3 * med}
    left_out = len(ref["grad_norms"]) - len(moving)
    add("grad_gap_median", median_gap(prog_grad, ref["grad_norms"]))
    add("delta_gap_median", median_gap(prog_delta, ref["delta_norms"], moving),
        note=f"{left_out} leaves left out")
    sizes = ref["sizes"]
    wide = {n for n in sizes if sizes[n] >= WIDE}
    gap, leaf = norm_gap(prog_grad, ref["grad_norms"])
    add("grad_gap_worst", gap, note=f"{leaf} ({sizes[leaf]} elements)")
    gap, leaf = norm_gap(prog_delta, ref["delta_norms"], keep=moving)
    add("delta_gap_worst", gap, note=f"{leaf} ({sizes[leaf]} elements)")
    gap, leaf = norm_gap(prog_grad, ref["grad_norms"], among=wide)
    add("grad_gap_worst_wide", gap,
        note=f"{leaf} ({sizes[leaf]} elements; {len(wide)} leaves of {WIDE} or more)")
    gap, leaf = norm_gap(prog_delta, ref["delta_norms"], keep=moving, among=wide)
    add("delta_gap_worst_wide", gap, note=f"{leaf} ({sizes[leaf]} elements)")
    for name, value in structure.items():
        add(name, value, limit=0)
    return numbers


def size_table(prog_grad, prog_delta, ref):
    """The gaps by leaf size as lines of text (standard error)."""
    lines = []
    for what, prog, norms in (("grad", prog_grad, ref["grad_norms"]),
                              ("delta", prog_delta, ref["delta_norms"])):
        for low, high, n, worst, med in gaps_by_size(prog, norms, ref["sizes"]):
            span = f"{low}..{high - 1}" if high < 2 ** 62 else f"{low} or more"
            lines.append(f"{what}_gap leaves of {span} elements: "
                         f"{n} leaves, worst {worst:.4f}, median {med:.4f}")
    return lines


def follow_reference(family, arch, optim, seed, batches, device, q=None,
                     fault=None, note=None):
    """The reference's readings over `batches` (host copies of what the
    pipeline placed), from the seeded weights, on `device`."""
    import jax

    from benchmarks import reference

    with jax.default_device(device):
        params0 = jax.device_put(
            reference.init_variables(family, arch, seed)["params"], device)
        return reference.follow(
            family, arch, optim, params0,
            (jax.device_put(b, device) for b in batches), q=q, fault=fault,
            note=note)


def training_numbers(family, arch, optim, seed, batches, program, limits,
                     structure, device, note=None):
    """Judge the program's snapshots against the reference. Returns the
    compared numbers and the reference's readings."""
    import jax

    from benchmarks import reference

    with jax.default_device(device):
        host_params0 = jax.device_get(
            reference.init_variables(family, arch, seed)["params"])
    prog_grad, prog_delta = program_norms(
        host_params0, program["momentum_after_1"], program["params_after"],
        optim["weight_decay"])
    del host_params0
    ref = follow_reference(family, arch, optim, seed, batches, device,
                           note=note)
    if note:
        for line in size_table(prog_grad, prog_delta, ref):
            note(line)
    return judge(program["losses"], prog_grad, prog_delta, ref, limits,
                 structure), ref


def stand_in_numbers(family, arch, optim, seed, batches, ref, limits, device,
                     q=None, fault=None, note=None):
    """The control: the reference put in the program's place, computed in the
    precision `q` (or with `fault` planted), judged as the program is."""
    other = follow_reference(family, arch, optim, seed, batches, device, q=q,
                             fault=fault)
    if note:
        for line in size_table(other["grad_norms"], other["delta_norms"], ref):
            note(line)
    return judge(other["losses"], other["grad_norms"], other["delta_norms"],
                 ref, limits, {})

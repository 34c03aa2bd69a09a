"""SPMD pipeline parallelism over the mesh's model axis.

ROADMAP item 3's last unlanded leg: long-clip VideoMAE pretraining blows
past one chip's activation memory, and the repo already has every
prerequisite — `make_pretrain_step`, in-graph `lax.scan` gradient
accumulation, the named (data, model) train mesh, ring/ulysses context
parallelism — except a pipeline lane over the model axis. This module is
that lane, in the SPMD-friendly formulation ("Scaling Deep Learning
Training with MPMD Pipeline Parallelism", PAPERS.md, lowered onto the
pjit/GSPMD mesh idiom of the TPUv4 pjit paper):

- the transformer trunk's K structurally-identical blocks are stacked
  IN-GRAPH into per-stage sub-stacks: stage s (one model-axis slice)
  computes blocks [s·K/P, (s+1)·K/P) and nothing else, so each stage's
  working set is 1/P of the trunk's layer compute and the per-microbatch
  activation footprint — the memory lever that fits long-clip pretrain.
  (Trunk params stay replicated per device, the repo's status quo for
  every other lane; the model-axis-sharded stacked-params form
  miscompiles on the pinned jaxlib — see the in-function comment — and
  params are not the scarce resource here, activations are.)
- inside a `shard_map` over the mesh, a `lax.scan` runs the microbatch
  schedule: at tick t, stage 0 ingests microbatch t, every stage runs its
  local sub-stack (an inner `lax.scan` over its K/P blocks), and a
  `ppermute` rotates activations one stage forward. After M + P - 1 ticks
  every microbatch has drained through the last stage — steady-state
  keeps every stage busy, exactly the 1F1B occupancy picture, and plain
  reverse-mode autodiff through the scan replays the same schedule
  backwards (no custom VJP anywhere);
- the fill/drain ticks where a stage chews on garbage ARE the pipeline
  bubble: `analytic_bubble_frac(P, M) = (P-1)/(M+P-1)` per direction.
  More microbatches amortize it; the realized fraction needs a
  two-point (M, 2M) timing fit on the chip (not measured).

The param-tree contract that makes checkpoints interchange: the stacking
happens IN-GRAPH, per step, from the model's ordinary `block{i}` param
tree — TrainState, optimizer state, checkpoints, converted weights, and
the donation story are byte-identical to the unpipelined model, and a run
saved under a (data, P) pipelined mesh restores under (N, 1) or a single
chip through the existing mesh-portable restore path (trainer/
checkpoint.py, the PR 7 contract). Under GSPMD the stack lowers to a
local dynamic-slice per stage (params are replicated over the model
axis), and the stacked gradient's unstack transposes to the model-axis
all-gather that is this scheme's gradient-sync cost.

Composition rules (docs/PARALLELISM.md § pipeline):
- on the 2-D (data, model) train mesh the pipeline SPENDS the model
  axis, so it excludes Megatron TP and ring/ulysses CP (both want the
  same axis); on the 4-axis library mesh the stages ride `tensor` while
  CP keeps its own `context` axis — inside the pipelined region the
  blocks call the CP kernels in their already-inside-a-shard_map form
  (`axis_name=`, ops/attention.py convention), so pipeline x CP composes;
- block functions must be rng-free and shape-preserving (pre-LN ViT
  blocks are; MViT's multiscale schedule is validated per cut — see
  models/mvit.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorchvideo_accelerate_tpu.parallel.mesh import batch_axes, model_axis


@dataclass(frozen=True)
class PipelinePlan:
    """Static description of one pipelined trunk execution.

    Frozen + hashable on purpose: models carry it as a flax module
    attribute (like `context_mesh`), and step builders close over it.

    stages        — P, the stage count; must equal the mesh's model-axis
                    size (each stage is one model-axis slice).
    microbatches  — M, microbatches streamed through the stages per step.
                    The trainer reuses the gradient-accumulation
                    micro-batch axis for this by default (config.py
                    `parallel.pipeline_microbatches`).
    mesh          — the device mesh the shard_map runs over.
    axis          — the mesh axis carrying stages ("model" on the 2-D
                    train mesh, "tensor" on the library mesh).
    cp_axis       — when ring/ulysses context parallelism composes with
                    the pipeline (library mesh only), the axis the token
                    dim is sharded over INSIDE the pipelined region; the
                    blocks then run their attention in `axis_name=` form.
    """

    stages: int
    microbatches: int
    mesh: Mesh
    axis: str
    cp_axis: Optional[str] = None

    @property
    def active(self) -> bool:
        return self.stages > 1

    def covers(self, n_blocks: int) -> bool:
        """Can this plan partition an `n_blocks` stack into equal stages?
        (The VideoMAE decoder opts out of pipelining when its 4 narrow
        blocks don't divide by P, rather than failing the whole model.)"""
        return n_blocks % self.stages == 0


def analytic_bubble_frac(stages: int, microbatches: int) -> float:
    """Idle fraction of the fill/drain schedule, per direction:
    (P-1)/(M+P-1). The forward scan runs M+P-1 ticks of which M are
    useful per stage; plain autodiff replays the same shape backwards, so
    the whole-step fraction is the same number. 0.0 for P=1."""
    p, m = int(stages), int(microbatches)
    if p <= 1:
        return 0.0
    return (p - 1) / (m + p - 1)


def stage_cuts(n_blocks: int, stages: int) -> List[Tuple[int, int]]:
    """[start, end) block ranges per stage — equal contiguous chunks, the
    only partition the stacked-leading-dim sharding can express."""
    if stages < 1:
        raise ValueError(f"pipeline stages must be >= 1, got {stages}")
    if n_blocks % stages:
        raise ValueError(
            f"cannot cut {n_blocks} blocks into {stages} equal pipeline "
            f"stages: {n_blocks} % {stages} != 0 (pick a stage count that "
            "divides the trunk depth)")
    size = n_blocks // stages
    return [(s * size, (s + 1) * size) for s in range(stages)]


def make_plan(mesh: Mesh, stages: int, microbatches: int = 0,
              accum_steps: int = 1,
              cp_axis_name: Optional[str] = None) -> PipelinePlan:
    """Resolve config knobs into a PipelinePlan against a concrete mesh.

    `microbatches=0` means auto: reuse the gradient-accumulation
    micro-batch count when accumulation is on (the data pipeline already
    lays the batch out micro-first), else 2·P — enough that the analytic
    bubble stays under (P-1)/(3P-1) ≈ 1/3 by default; raise it for less.
    """
    axis = model_axis(mesh)
    if axis is None:
        raise ValueError(
            f"pipeline_stages={stages} needs a model-parallel mesh axis "
            f"('model' or 'tensor'); mesh has {tuple(mesh.axis_names)}")
    if mesh.shape[axis] != stages:
        raise ValueError(
            f"pipeline_stages={stages} must equal the mesh's {axis!r} axis "
            f"size ({mesh.shape[axis]}): stages are placed one per "
            f"{axis}-slice — set --mesh.model {stages} (train mesh) or "
            f"--mesh.tensor {stages} (library mesh)")
    if cp_axis_name is not None and cp_axis_name == axis:
        raise ValueError(
            "pipeline stages and context parallelism both want the "
            f"{axis!r} axis: on the 2-D train mesh they are mutually "
            "exclusive — use the 4-axis library mesh (tensor=P for "
            "stages, context=C for CP) to compose them")
    m = int(microbatches) or (int(accum_steps) if accum_steps > 1
                              else 2 * int(stages))
    if m < 1:
        raise ValueError(f"pipeline_microbatches must be >= 1, got {m}")
    return PipelinePlan(stages=int(stages), microbatches=m, mesh=mesh,
                        axis=axis, cp_axis=cp_axis_name)


def validate_homogeneous_blocks(block_params: Sequence[Any]) -> None:
    """Homogeneity is a hard requirement of the stage pipeline — one
    block function runs every slice of a uniformly-shaped sub-stack — so
    a mismatching tree (MViT's dim-doubling stage starts, a stray pool
    conv) fails here with the offending block named instead of as a
    shape error deep inside shard_map. Pure metadata checks, no ops."""
    blocks = list(block_params)
    if not blocks:
        raise ValueError("no blocks to pipeline")
    ref = jax.tree_util.tree_structure(blocks[0])
    ref_avals = [(np.shape(leaf), jnp.result_type(leaf))
                 for leaf in jax.tree_util.tree_leaves(blocks[0])]
    for i, b in enumerate(blocks[1:], start=1):
        if jax.tree_util.tree_structure(b) != ref:
            raise ValueError(
                f"pipeline stages need a homogeneous block stack: block {i}"
                f"'s param tree structure differs from block 0's "
                "(heterogeneous trunks — MViT stage starts, pooled blocks "
                "— cannot stack; see docs/PARALLELISM.md § pipeline)")
        for j, leaf in enumerate(jax.tree_util.tree_leaves(b)):
            if (np.shape(leaf), jnp.result_type(leaf)) != ref_avals[j]:
                raise ValueError(
                    f"pipeline stages need a homogeneous block stack: "
                    f"block {i} leaf #{j} has shape/dtype "
                    f"{np.shape(leaf)}/{jnp.result_type(leaf)} vs block "
                    f"0's {ref_avals[j][0]}/{ref_avals[j][1]}")


def stack_block_params(block_params: Sequence[Any]) -> Any:
    """Stack per-block param trees along a new leading (block) axis
    (validated homogeneous first — `validate_homogeneous_blocks`)."""
    blocks = list(block_params)
    validate_homogeneous_blocks(blocks)
    return jax.tree.map(lambda *ls: jnp.stack(ls), *blocks)


def unstack_block_params(stacked: Any, n_blocks: int) -> List[Any]:
    """Inverse of `stack_block_params` (host-side checkpoint tooling and
    tests; the train path never materializes the unstacked form — the
    stack's AD transpose does it implicitly)."""
    return [jax.tree.map(lambda a, i=i: a[i], stacked)
            for i in range(n_blocks)]


def _data_shards(mesh: Mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def pipeline_blocks(block_fn: Callable[[Any, Any], Any],
                    block_params: Sequence[Any], x, plan: PipelinePlan):
    """Run `x` through a stack of homogeneous blocks as a P-stage SPMD
    pipeline (module docstring has the schedule picture).

    block_fn(one_block_params, h) -> h  — pure, rng-free, shape- and
    dtype-preserving (validated at trace time). `x`: (B, ...) activations,
    batch-sharded over the mesh's data axes; with `plan.cp_axis` set, dim
    1 (the token dim) is sharded over that axis inside the region and the
    blocks must use the `axis_name=` attention convention.

    Returns activations identical in shape/sharding contract to `x`
    (replicated over the stage axis, like every other activation the
    surrounding jit computes redundantly per model slice).
    """
    mesh, axis = plan.mesh, plan.axis
    stages = int(plan.stages)
    micro = int(plan.microbatches)
    blocks = list(block_params)
    stage_cuts(len(blocks), stages)  # divisibility, with the clear error
    if mesh.shape[axis] != stages:
        raise ValueError(
            f"plan has {stages} stages but mesh axis {axis!r} is "
            f"{mesh.shape[axis]}-wide")
    dshards = _data_shards(mesh)
    batch = int(x.shape[0])
    if batch % (dshards * micro):
        raise ValueError(
            f"global batch {batch} must divide data_shards x microbatches "
            f"= {dshards} x {micro}: each data slice re-slices its local "
            "batch into the pipeline's microbatches")

    # Validate homogeneity up front (the clear error): metadata only —
    # an actual stack here would put a dead full-trunk copy in every
    # traced (and a real one in every eager) pipelined apply.
    validate_homogeneous_blocks(blocks)
    n_stage = len(blocks) // stages

    # EVERY in/out spec mentions EVERY mesh axis its value touches, via
    # explicit leading tile dims for the axes a value is replicated over
    # (block params over data/context and the stage axis, x over the stage
    # axis). Why not lean on shard_map's unmentioned-axis replication
    # accounting: reverse-mode AD of replicated-in operands through the
    # pinned jax's check_rep=False rewrite machinery over-psums their
    # cotangents once per nesting level of this body's scans (measured:
    # block grads x dshards^3 on a (data, model) mesh — found while
    # building the P=2 parity test). And why each block rides in as its
    # own tiled input instead of one model-axis-sharded (K, ...) stack:
    # the pinned jaxlib's SPMD partitioner miscompiles an IN-GRAPH
    # `jnp.stack` (concatenate) feeding the manual-computation boundary
    # whenever a tile dim shards over the data axis — output values come
    # back multiplied by mesh.size (fingerprinted: exactly
    # `mesh.size * correct`); a pre-stacked jit *argument* compiles fine,
    # but the in-graph stack is non-negotiable (it is what keeps the
    # param tree identical to the unpipelined model). Per-block tiles
    # sidestep the bug at the cost of replicating trunk params over the
    # stage axis (the status quo for every other lane in this repo — the
    # pipeline's memory win is the per-microbatch ACTIVATION footprint
    # and the schedule, not param bytes; revisit the stacked form on a
    # fixed jaxlib). The `broadcast_to` tiles cost nothing under GSPMD
    # (each shard holds the one copy it already had) and their transposes
    # are plain reduce_sums over the sharded tile dims — lowered to the
    # cross-shard all-reduce that IS this scheme's gradient sync.
    daxes = batch_axes(mesh)
    cp = plan.cp_axis if (plan.cp_axis is not None and x.ndim >= 3) else None
    cp_size = mesh.shape[cp] if cp is not None else 1
    lead = (dshards,) + ((cp_size,) if cp is not None else ()) + (stages,)
    lead_spec = (daxes,) + ((cp,) if cp is not None else ()) + (axis,)
    n_lead = len(lead)

    def tile_leaf(a):
        t = jnp.broadcast_to(a[(None,) * n_lead], lead + a.shape)
        spec = P(*lead_spec, *([None] * a.ndim))
        return lax.with_sharding_constraint(t, NamedSharding(mesh, spec))

    tiled = tuple(jax.tree.map(tile_leaf, b) for b in blocks)
    tiled_specs = tuple(
        jax.tree.map(lambda a: P(*lead_spec,
                                 *([None] * (a.ndim - n_lead))), b)
        for b in tiled)

    x_dims = [daxes] + [None] * (x.ndim - 1)
    if cp is not None:
        x_dims[1] = cp  # token dim sharded inside the region
    x_tiled = lax.with_sharding_constraint(
        jnp.broadcast_to(x[None], (stages,) + x.shape),
        NamedSharding(mesh, P(axis, *x_dims)))
    x_spec = P(axis, *x_dims)
    out_spec = P(axis, *x_dims)
    last = stages - 1

    def body(bps, xt):
        # strip the tile dims (each device holds one replica of every
        # block), stack locally — plain XLA inside the manual region, no
        # partitioner involvement — and slice out THIS stage's contiguous
        # sub-stack by stage id
        sid = lax.axis_index(axis)
        locals_ = [jax.tree.map(lambda a: a.reshape(a.shape[n_lead:]), b)
                   for b in bps]
        full = jax.tree.map(lambda *ls: jnp.stack(ls), *locals_)
        bp = jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(a, sid * n_stage, n_stage,
                                               axis=0), full)
        xl = xt.reshape(xt.shape[1:])
        b_loc = xl.shape[0]
        xm = xl.reshape((micro, b_loc // micro) + xl.shape[1:])

        def run_stage(h):
            def blk(c, p):
                y = block_fn(p, c)
                if y.shape != c.shape or y.dtype != c.dtype:
                    raise ValueError(
                        f"pipelined block_fn must preserve shape/dtype: "
                        f"{c.shape}/{c.dtype} -> {y.shape}/{y.dtype}")
                return y, None

            h, _ = lax.scan(blk, h, bp)
            return h

        def tick(carry, t):
            h, out = carry
            # stage 0 ingests microbatch t (clipped past the drain — the
            # reprocessed garbage never reaches `out`); later stages use
            # the activation the ppermute rotated in last tick
            inp = lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, micro - 1), 0, keepdims=False)
            h = jnp.where(sid == 0, inp, h)
            y = run_stage(h)
            # the last stage drains microbatch t-(P-1) once the fill is
            # done; other stages' writes are masked out
            oidx = jnp.clip(t - last, 0, micro - 1)
            write = jnp.logical_and(sid == last, t >= last)
            out = jnp.where(write,
                            lax.dynamic_update_index_in_dim(out, y, oidx, 0),
                            out)
            h = lax.ppermute(y, axis,
                             [(i, (i + 1) % stages) for i in range(stages)])
            return (h, out), None

        out0 = jnp.zeros_like(xm)
        (_, out), _ = lax.scan(tick, (jnp.zeros_like(xm[0]), out0),
                               jnp.arange(micro + last))
        # the drained activations live on the last stage; the out spec
        # carries the stage dim explicitly (zeros elsewhere)
        return out.reshape((1,) + xl.shape)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(tiled_specs, x_spec),
                       out_specs=out_spec, check_vma=False)
    # reduce the stage dim instead of slicing it: non-last stages are
    # zeros, so the sum IS stage P-1's value, and a reduce over a sharded
    # dim lowers to the local reduce + all-reduce that hands every model
    # slice the full tensor (the replicated-over-stage-axis contract the
    # decoder/head/loss consumers need) — with the trivially correct
    # transpose (broadcast; the masked writes zero the non-last
    # cotangents in the backward scan).
    return fn(tiled, x_tiled).sum(axis=0)


def apply_pipelined_blocks(mod, tokens, *, prefix: str, depth: int,
                           template, plan: PipelinePlan,
                           apply_args: Tuple = ()):
    """Drive a bound flax module's named block stack through the stage
    pipeline — the ONE dispatch both transformer families share (videomae
    `run_vit_blocks`, the MViT block loop), so remat wrapping / param
    addressing / boundary constraints can't drift apart between them.

    Reads the `{prefix}{i}` param subtrees straight off `mod.variables` —
    the SAME trees the plain loop trains (param-tree identity is the
    checkpoint-interchange contract) — and applies `template` (an
    UNNAMED block module instance) to each as a pure function.
    `apply_args` are static extras after the activations (MViT's `train`
    flag). Honors `mod.remat` and re-anchors the output on
    `mod.shard_mesh` like the plain loops do."""
    from pytorchvideo_accelerate_tpu.parallel.sharding import constrain_block

    bp = [mod.variables["params"][f"{prefix}{i}"] for i in range(depth)]

    def block_fn(p, h):
        return template.apply({"params": p}, h, *apply_args)

    if mod.remat:
        block_fn = jax.checkpoint(block_fn)
    tokens = pipeline_blocks(block_fn, bp, tokens, plan)
    return constrain_block(tokens, mod.shard_mesh)


def stage_tag(mesh: Mesh, axis: Optional[str] = None) -> str:
    """Which pipeline stage(s) this PROCESS runs: "2/4" when its local
    devices sit on one model-axis slice (a real multi-host pipeline — the
    attribution the hang detector wants: a wedged dispatch on this host
    IS that stage wedging), "0-3/4" when several stages are local
    (single-process / forced-host runs). The one formatting every watched
    collective shares, mirroring hangcheck.host_tag()."""
    axis = axis or model_axis(mesh)
    if axis is None:
        return ""
    pos = list(mesh.axis_names).index(axis)
    local = {d for d in jax.local_devices() if d in mesh.devices.flat}
    coords = sorted({int(idx[pos])
                     for idx in np.ndindex(mesh.devices.shape)
                     if mesh.devices[idx] in local})
    total = mesh.shape[axis]
    if not coords:
        return f"?/{total}"
    if len(coords) == 1:
        return f"{coords[0]}/{total}"
    return f"{coords[0]}-{coords[-1]}/{total}"

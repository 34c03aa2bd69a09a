"""SPMD pipeline parallelism (parallel/pipeline.py; ISSUE 14).

Late-alphabet on purpose (the tier-1 suite is timeout-bound; the compiled
multi-device cases here must never starve the early cheap tests). Covers
the stage-cut contract, P=1 == unpipelined, the microbatch schedule's
parity with plain gradient accumulation, checkpoint interchange across
pipelined/unpipelined layouts, CP x pipeline composition on the library
mesh, guard skip-batch under the pipelined step, and the watchdog's
per-stage stall attribution.

Parity baselines are SAME-MESH runs throughout: the random tube mask's
rng -> argsort -> gather graph is not layout-invariant between an eager
host run and a sharded mesh run (pre-existing at seed, nothing to do with
the pipeline), so eager-vs-pipelined comparisons of rng-masked models
would measure the mask, not the schedule.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.config import (
    MeshConfig,
    ModelConfig,
    OptimConfig,
)
from pytorchvideo_accelerate_tpu.models import create_model
from pytorchvideo_accelerate_tpu.parallel import pipeline as pl
from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh, make_train_mesh
from pytorchvideo_accelerate_tpu.parallel.sharding import (
    shard_batch,
    shard_state,
)
from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer
from pytorchvideo_accelerate_tpu.trainer.steps import (
    make_pretrain_step,
    make_train_step,
)
from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState


def _mesh22():
    return make_train_mesh(MeshConfig(data=2, model=2),
                           devices=jax.devices()[:4])


def _leaves_max_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# --- schedule arithmetic (no compile) ---------------------------------------

def test_stage_cuts_and_bubble_frac():
    assert pl.stage_cuts(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert pl.stage_cuts(4, 1) == [(0, 4)]
    with pytest.raises(ValueError, match="equal pipeline"):
        pl.stage_cuts(6, 4)
    # non-vacuous bubble bound: 0 only at P=1, exactly (P-1)/(M+P-1)
    # otherwise, strictly shrinking as microbatches amortize the fill
    assert pl.analytic_bubble_frac(1, 4) == 0.0
    assert pl.analytic_bubble_frac(4, 4) == pytest.approx(3 / 7)
    prev = 1.0
    for m in (1, 2, 4, 8, 64):
        b = pl.analytic_bubble_frac(4, m)
        assert 0.0 < b < 1.0
        assert b < prev
        prev = b


def test_make_plan_validation():
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=3)
    assert plan.active and plan.stages == 2 and plan.microbatches == 3
    # auto microbatches: reuse accumulation when on, else 2P
    assert pl.make_plan(mesh, 2, accum_steps=4).microbatches == 4
    assert pl.make_plan(mesh, 2).microbatches == 4
    with pytest.raises(ValueError, match="must equal the mesh"):
        pl.make_plan(mesh, 4)
    # the 2-D train mesh's model axis can't carry stages AND CP tokens
    with pytest.raises(ValueError, match="mutually exclusive"):
        pl.make_plan(mesh, 2, cp_axis_name="model")


def test_create_model_refuses_conv_families():
    plan = pl.make_plan(_mesh22(), 2)
    with pytest.raises(ValueError, match="no pipeline stage-cut seam"):
        create_model(ModelConfig(name="tiny3d", num_classes=4), "fp32",
                     pipeline=plan)


def test_mvit_cut_check_names_the_obstruction():
    from pytorchvideo_accelerate_tpu.models.mvit import MViT

    plan = pl.make_plan(_mesh22(), 2)
    base = dict(num_classes=4, embed_dim=16, depth=4, num_heads=2,
                pipeline=plan)
    with pytest.raises(ValueError, match="stage_starts"):
        MViT(stage_starts=(1, 3), drop_path_rate=0.0,
             **base).pipeline_cut_check(2)
    with pytest.raises(ValueError, match="drop_path"):
        MViT(stage_starts=(), drop_path_rate=0.1,
             **base).pipeline_cut_check(2)
    with pytest.raises(ValueError, match="context-parallel"):
        MViT(stage_starts=(), drop_path_rate=0.0, attention_backend="ring",
             **base).pipeline_cut_check(2)
    # a uniform trunk cuts cleanly
    MViT(stage_starts=(), drop_path_rate=0.0, **base).pipeline_cut_check(2)


# --- stage-cut param-tree identity ------------------------------------------

def test_param_tree_identical_across_the_knob():
    """The checkpoint-interchange contract: pipelined and plain models
    share one param tree, leaf for leaf."""
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=2)
    cfg = ModelConfig(name="videomae_t_pretrain", num_classes=4)
    x = jnp.zeros((4, 4, 16, 16, 3), jnp.float32)
    k = jax.random.key(0)
    v_plain = create_model(cfg, "fp32").init({"params": k, "mask": k}, x)
    v_pipe = create_model(cfg, "fp32", pipeline=plan).init(
        {"params": k, "mask": k}, x)
    assert (jax.tree_util.tree_structure(v_plain)
            == jax.tree_util.tree_structure(v_pipe))
    assert ([np.shape(l) for l in jax.tree_util.tree_leaves(v_plain)]
            == [np.shape(l) for l in jax.tree_util.tree_leaves(v_pipe)])
    # stack/unstack round-trips the per-block subtrees
    bp = [v_plain["params"]["encoder"][f"block{i}"] for i in range(4)]
    stacked = pl.stack_block_params(bp)
    back = pl.unstack_block_params(stacked, 4)
    assert _leaves_max_diff(bp, back) == 0.0


def test_p1_plan_is_bitwise_the_unpipelined_model():
    mesh = make_train_mesh(MeshConfig(data=4, model=1),
                           devices=jax.devices()[:4])
    plan = pl.make_plan(mesh, 1)
    assert not plan.active
    cfg = ModelConfig(name="videomae_t", num_classes=4, dropout_rate=0.0)
    m1 = create_model(cfg, "fp32")
    m2 = create_model(cfg, "fp32", pipeline=plan)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 4, 16, 16, 3), dtype=np.float32))
    v = m1.init(jax.random.key(0), x)
    o1 = m1.apply(v, x)
    o2 = m2.apply(v, x)
    assert float(jnp.max(jnp.abs(o1 - o2))) == 0.0


# --- the schedule itself ----------------------------------------------------

def test_pipeline_blocks_matches_sequential_fwd_and_grad():
    """Core contract on the (data, model) mesh: the P-stage microbatch
    schedule computes the SAME function as the sequential block stack —
    forward bitwise, gradients at fp32 roundoff (plain autodiff through
    the scan, no custom VJP)."""
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=2)
    rng = np.random.default_rng(0)
    D = 8

    def block_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    x = jnp.asarray(rng.standard_normal((8, 4, D), dtype=np.float32))
    bl = [{"w": jnp.asarray(rng.standard_normal((D, D),
                                                dtype=np.float32) * 0.3),
           "b": jnp.asarray(rng.standard_normal((D,),
                                                dtype=np.float32) * 0.1)}
          for _ in range(4)]
    fref = functools.reduce(lambda h, p: block_fn(p, h), bl, x)

    def loss_seq(bs, xx):
        return jnp.mean(
            functools.reduce(lambda h, p: block_fn(p, h), bs, xx) ** 2)

    def loss_pipe(bs, xx):
        return jnp.mean(pl.pipeline_blocks(block_fn, bs, xx, plan) ** 2)

    fwd = jax.jit(lambda bs, xx: pl.pipeline_blocks(
        block_fn, bs, xx, plan))(bl, x)
    assert float(jnp.max(jnp.abs(fwd - fref))) == 0.0
    gref = jax.grad(loss_seq, argnums=(0, 1))(bl, x)
    gpipe = jax.jit(jax.grad(loss_pipe, argnums=(0, 1)))(bl, x)
    assert _leaves_max_diff(gref[0], gpipe[0]) < 1e-6
    assert float(jnp.max(jnp.abs(gref[1] - gpipe[1]))) < 1e-6


def test_pipeline_blocks_validates_batch_and_shapes():
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=4)
    bl = [{"w": jnp.eye(4)} for _ in range(2)]

    def block_fn(p, h):
        return h @ p["w"]

    # batch 6 can't slice into 2 data shards x 4 microbatches
    with pytest.raises(ValueError, match="data_shards x microbatches"):
        jax.eval_shape(lambda: pl.pipeline_blocks(
            block_fn, bl, jnp.zeros((6, 3, 4)), plan))
    # a shape-changing block fn dies at trace time, not inside the scan
    with pytest.raises(ValueError, match="preserve shape"):
        jax.eval_shape(lambda: pl.pipeline_blocks(
            lambda p, h: (h @ p["w"])[:, :2], bl, jnp.zeros((8, 3, 4)),
            plan))


def test_mvit_uniform_pipelined_matches_plain():
    """A uniform MViT (no multiscale schedule) pipelines through the
    shared apply_pipelined_blocks dispatch and matches the plain loop."""
    from pytorchvideo_accelerate_tpu.models.mvit import MViT

    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=2)
    kw = dict(num_classes=4, embed_dim=16, depth=4, num_heads=2,
              stage_starts=(), drop_path_rate=0.0, dtype=jnp.float32)
    m_plain = MViT(**kw)
    m_pipe = MViT(pipeline=plan, **kw)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (8, 4, 16, 16, 3), dtype=np.float32))
    v = m_plain.init(jax.random.key(0), x)
    o1 = m_plain.apply(v, x)
    o2 = jax.jit(lambda v, x: m_pipe.apply(v, x))(v, x)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-5


def test_model_forward_parity_same_mesh():
    """videomae_t_pretrain pipelined vs the SAME-MESH unpipelined model:
    identical loss/pred (the valid baseline — see module docstring)."""
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=2)
    cfg = ModelConfig(name="videomae_t_pretrain", num_classes=4)
    m_pipe = create_model(cfg, "fp32", mesh=mesh, pipeline=plan)
    m_mesh = create_model(cfg, "fp32", mesh=mesh)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (8, 4, 16, 16, 3), dtype=np.float32))
    k = jax.random.key(0)
    v = m_mesh.init({"params": k, "mask": k}, x)
    o1 = jax.jit(lambda v, x: m_mesh.apply(
        v, x, rngs={"mask": jax.random.key(1)}))(v, x)
    o2 = jax.jit(lambda v, x: m_pipe.apply(
        v, x, rngs={"mask": jax.random.key(1)}))(v, x)
    assert abs(float(o1["loss"]) - float(o2["loss"])) < 1e-5
    assert float(jnp.max(jnp.abs(o1["pred"] - o2["pred"]))) < 1e-4


# --- the trainer step -------------------------------------------------------

def _fresh_state(mesh, params, tx):
    p = jax.tree.map(lambda a: jnp.array(np.asarray(a)), params)
    return shard_state(mesh, TrainState.create(p, {}, tx), tp=False)


def test_microbatch_fold_matches_plain_accumulation():
    """The pipelined step folds the (G, B, ...) accumulation axis into
    the stage schedule's microbatch stream; on the rng-free supervised
    path the loss is BITWISE the plain accumulation scan's and the
    updated params agree to fp32 roundoff."""
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=0, accum_steps=2)
    assert plan.microbatches == 2  # auto: reuse the accumulation axis
    cfg = ModelConfig(name="videomae_t", num_classes=4, dropout_rate=0.0)
    m_pipe = create_model(cfg, "fp32", pipeline=plan)
    m_plain = create_model(cfg, "fp32")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 8, 4, 16, 16, 3)).astype(np.float32)
    lab = rng.integers(0, 4, (2, 8)).astype(np.int32)
    v = m_plain.init(jax.random.key(0), jnp.asarray(x[0]))
    tx = build_optimizer(OptimConfig(), total_steps=8)
    step_plain = make_train_step(m_plain, tx, mesh, accum_steps=2)
    step_pipe = make_train_step(m_pipe, tx, mesh, accum_steps=2,
                                pipeline=plan)
    key = jax.random.key(7)
    s1, m1 = step_plain(_fresh_state(mesh, v["params"], tx),
                        shard_batch(mesh, {"video": x, "label": lab},
                                    micro_dim=True), key)
    s2, m2 = step_pipe(_fresh_state(mesh, v["params"], tx),
                       shard_batch(mesh, {"video": x, "label": lab},
                                   micro_dim=True), key)
    assert float(m1["loss"]) == float(m2["loss"])
    assert _leaves_max_diff(s1.params, s2.params) < 1e-6
    # and the pipelined layout holds the zero-recompile contract: a second
    # step, from the first one's state, adds no executable
    from pytorchvideo_accelerate_tpu.analysis.recompile_guard import (
        cache_size,
    )

    before = cache_size(step_pipe)
    step_pipe(s2, shard_batch(mesh, {"video": x[::-1].copy(), "label": lab},
                              micro_dim=True), jax.random.key(8))
    assert before == cache_size(step_pipe) == 1


def test_guard_skip_batch_under_pipelined_step():
    """TrainGuard's in-graph skip composes with the pipelined step: a NaN
    batch discards its own update (every leaf kept, step advances)."""
    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=2)
    cfg = ModelConfig(name="videomae_t_pretrain", num_classes=4,
                      dropout_rate=0.0)
    m_pipe = create_model(cfg, "fp32", pipeline=plan)
    x = np.random.default_rng(0).standard_normal(
        (8, 4, 16, 16, 3)).astype(np.float32)
    v = create_model(cfg, "fp32").init(
        {"params": jax.random.key(0), "mask": jax.random.key(0)},
        jnp.asarray(x))
    tx = build_optimizer(OptimConfig(), total_steps=8)
    step = make_pretrain_step(m_pipe, tx, mesh, pipeline=plan,
                              guard_skip=True)
    bad = x.copy()
    bad[0, 0, 0, 0, :] = np.nan
    s0 = _fresh_state(mesh, v["params"], tx)
    s1, metrics = step(s0, shard_batch(mesh, {"video": bad}),
                       jax.random.key(3))
    assert float(metrics["skipped"]) == 1.0
    assert int(s1.step) == 1  # counter advances, nothing else does
    ref = _fresh_state(mesh, v["params"], tx)
    assert _leaves_max_diff(ref.params, s1.params) == 0.0


# --- checkpoint interchange across layouts ----------------------------------

def test_ckpt_pipelined_to_reshaped_to_single_roundtrip(tmp_path):
    """A checkpoint written under the pipelined (2, P=2) layout restores
    under (4, 1) unpipelined AND under a single-device mesh at the
    identical step with bit-identical params — the PR 7 mesh-portability
    contract extended to the pipeline knob (the param tree is the same
    tree, so no conversion exists to get wrong)."""
    from pytorchvideo_accelerate_tpu.trainer.checkpoint import Checkpointer

    mesh = _mesh22()
    plan = pl.make_plan(mesh, 2, microbatches=2)
    cfg = ModelConfig(name="videomae_t_pretrain", num_classes=4,
                      dropout_rate=0.0)
    m_pipe = create_model(cfg, "fp32", pipeline=plan)
    x = np.random.default_rng(0).standard_normal(
        (8, 4, 16, 16, 3)).astype(np.float32)
    v = create_model(cfg, "fp32").init(
        {"params": jax.random.key(0), "mask": jax.random.key(0)},
        jnp.asarray(x))
    tx = build_optimizer(OptimConfig(), total_steps=8)
    step = make_pretrain_step(m_pipe, tx, mesh, pipeline=plan)
    s, _ = step(_fresh_state(mesh, v["params"], tx),
                shard_batch(mesh, {"video": x}), jax.random.key(1))
    saved = jax.device_get(s.params)
    ckpt = Checkpointer(str(tmp_path / "ck"), use_async=False)
    ckpt.save(1, s)
    ckpt.wait()
    for devs, mcfg in ((jax.devices()[:4], MeshConfig(data=4, model=1)),
                       (jax.devices()[:1], MeshConfig(data=1, model=1))):
        mesh_b = make_train_mesh(mcfg, devices=devs)
        template = _fresh_state(mesh_b, v["params"], tx)
        restored, _extra, step_b = ckpt.restore(template, step=1,
                                                mesh=mesh_b, tp=False)
        assert step_b == 1
        assert int(restored.step) == 1
        assert _leaves_max_diff(saved, jax.device_get(
            restored.params)) == 0.0
    ckpt.close()


# --- composition ------------------------------------------------------------

def test_cp_pipeline_composition_on_library_mesh():
    """Pipeline over `tensor` + ring-attention CP over `context` on the
    4-axis library mesh: the blocks run their attention in the
    already-inside-a-shard_map `axis_name=` form, and the result matches
    the dense unpipelined reference."""
    lib = make_mesh(MeshConfig(data=2, fsdp=1, tensor=2, context=2),
                    devices=jax.devices()[:8])
    plan = pl.make_plan(lib, 2, microbatches=2, cp_axis_name="context")
    assert plan.axis == "tensor" and plan.cp_axis == "context"
    cfg_ring = ModelConfig(name="videomae_t", num_classes=4,
                           dropout_rate=0.0, attention="ring")
    cfg_dense = ModelConfig(name="videomae_t", num_classes=4,
                            dropout_rate=0.0)
    m_cp = create_model(cfg_ring, "fp32", mesh=lib, pipeline=plan)
    m_ref = create_model(cfg_dense, "fp32")
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 4, 16, 16, 3), dtype=np.float32))
    v = m_ref.init(jax.random.key(0), x)
    o_ref = m_ref.apply(v, x)
    o_cp = jax.jit(lambda v, x: m_cp.apply(v, x))(v, x)
    assert float(jnp.max(jnp.abs(o_ref - o_cp))) < 1e-5


# --- observability ----------------------------------------------------------

def test_stage_tag_formats_local_slice():
    mesh = _mesh22()
    # single-process run: every model-axis coordinate is local
    assert pl.stage_tag(mesh) == "0-1/2"
    mesh1 = make_train_mesh(MeshConfig(data=4, model=1),
                            devices=jax.devices()[:4])
    assert pl.stage_tag(mesh1) in ("", "0/1")


def test_watchdog_attributes_pipelined_stage_stall():
    """The satellite's hang story: a wedged pipelined dispatch attributes
    to 'stage i/P' through the collective section BEFORE any external
    kill (the loop.py step-dispatch detail carries stage_tag)."""
    import time

    from pytorchvideo_accelerate_tpu.obs.watchdog import Watchdog
    from pytorchvideo_accelerate_tpu.parallel import hangcheck

    mesh = _mesh22()
    wd = Watchdog(0.05, poll_s=10.0)  # driven manually via check()
    hangcheck.install_collective_watch(wd)
    try:
        tag = f"{hangcheck.host_tag()} stage={pl.stage_tag(mesh)}"
        with hangcheck.collective_section(f"step_dispatch {tag}",
                                          gstep=12):
            time.sleep(0.12)
            assert wd.check() == ["collective"]
        detail, age = wd.last_attribution["collective"]
        assert "stage=0-1/2" in detail and "gstep=12" in detail
        assert age >= 0.05
    finally:
        hangcheck.uninstall_collective_watch()


def test_graphcheck_builds_the_pipelined_target():
    """graphcheck's target list includes train_step_pipelined on a
    multi-device host (donation/dtype/flops coverage for the stage
    region; the passes themselves run in tests/test_zgraphcheck.py's
    `report`)."""
    from pytorchvideo_accelerate_tpu.analysis.graphcheck import (
        build_targets,
    )

    targets = build_targets(model="videomae_t_pretrain", smoke=True)
    names = [t.name for t in targets]
    assert "train_step_pipelined" in names
    t = next(t for t in targets if t.name == "train_step_pipelined")
    assert t.donation == "require"

"""Each plain reference (benchmarks/reference/) against the program's model at
a tiny geometry with the benchmark's seeded weights: parameter tree, forward,
loss and gradients; and the reference's SGD against the program's optimizer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference
from benchmarks.lib import compare
from benchmarks.reference import plain

SLOWFAST_ARCH = {"depths": [1, 1, 1, 1], "alpha": 4, "beta_inv": 8,
                 "fusion_ratio": 2, "stem_features": 16,
                 "slow_temporal_kernels": [1, 1, 3, 3], "num_classes": 5}
X3D_ARCH = {"depths": [1, 2, 1, 1], "stem_features": 24,
            "stage_features": [24, 48, 96, 192], "expansion": 2.25,
            "head_features": 64, "se_ratio": 0.0625, "num_classes": 5}


def _slowfast():
    from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast

    r = np.random.default_rng(0)
    fast = jnp.asarray(r.standard_normal((4, 8, 32, 32, 3)), jnp.float32)
    # the reference cuts the slow pathway from `fast` itself
    batch = {"slow": reference.family("slowfast").slow_frames(fast, 4),
             "fast": fast,
             "label": jnp.asarray(r.integers(0, 5, 4), jnp.int32)}
    model = SlowFast(num_classes=5, depths=(1, 1, 1, 1), stem_features=16,
                     dropout_rate=0.0)
    return "slowfast", SLOWFAST_ARCH, model, batch


def _x3d():
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    r = np.random.default_rng(1)
    batch = {"video": jnp.asarray(r.standard_normal((4, 5, 32, 32, 3)), jnp.float32),
             "label": jnp.asarray(r.integers(0, 5, 4), jnp.int32)}
    model = X3D(num_classes=5, depths=(1, 2, 1, 1), head_features=64,
                dropout_rate=0.0)
    return "x3d", X3D_ARCH, model, batch


@pytest.mark.parametrize("case", [_slowfast, _x3d], ids=["slowfast", "x3d"])
def test_reference_agrees_with_the_programs_model(case):
    family, arch, model, batch = case()
    fam = reference.family(family)
    variables = reference.init_variables(family, arch, 7)
    # the benchmark's tree is the tree the program's model declares
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), fam.inputs(batch)))
    for part in ("params", "batch_stats"):
        ours = {jax.tree_util.keystr(p): v.shape for p, v in
                jax.tree_util.tree_flatten_with_path(variables[part])[0]}
        theirs = {jax.tree_util.keystr(p): v.shape for p, v in
                  jax.tree_util.tree_flatten_with_path(shapes[part])[0]}
        assert ours == theirs

    def program(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            fam.inputs(batch), train=True, mutable=["batch_stats"])
        return plain.cross_entropy(logits, batch["label"]), logits

    def ref(params):
        logits = fam.forward(plain.Net({"params": params}), batch, arch)
        return plain.cross_entropy(logits, batch["label"]), logits

    (lp, logits_p), gp = jax.jit(jax.value_and_grad(program, has_aux=True))(variables["params"])
    (lr, logits_r), gr = jax.jit(jax.value_and_grad(ref, has_aux=True))(variables["params"])
    scale = float(jnp.max(jnp.abs(logits_r)))
    assert float(jnp.max(jnp.abs(logits_p - logits_r))) < 1e-4 * scale
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    pn = {k: float(v) for k, v in plain.leaf_norms(gp).items()}
    rn = {k: float(v) for k, v in plain.leaf_norms(gr).items()}
    gap, leaf = compare.norm_gap(pn, rn)
    # float32 both sides; the program's batch norm takes E[x^2]-E[x]^2, whose
    # backward pass loses up to ~1e-2 at these tiny batch statistics (its own
    # float64 run sides with the reference to 5e-6: PERF.md, Findings)
    assert gap < 5e-2, (gap, leaf)


def test_seed_gives_the_same_weights_and_other_seeds_others():
    a = reference.init_variables("x3d", X3D_ARCH, 5)["params"]
    b = reference.init_variables("x3d", X3D_ARCH, 5)["params"]
    c = reference.init_variables("x3d", X3D_ARCH, 2 ** 31 + 11)["params"]
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["proj"]["kernel"], c["proj"]["kernel"]))


def test_sgd_matches_the_programs_optimizer():
    from pytorchvideo_accelerate_tpu.config import OptimConfig
    from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer

    total = 50
    cfg = OptimConfig(lr=0.05, momentum=0.9, weight_decay=1e-3)
    tx = build_optimizer(cfg, total)
    optim = {"lr": 0.05, "momentum": 0.9, "weight_decay": 1e-3, "total_steps": total}
    r = np.random.default_rng(0)
    params = {"a": jnp.asarray(r.standard_normal((3, 4)), jnp.float32),
              "b": {"c": jnp.asarray(r.standard_normal((5,)), jnp.float32)}}
    theirs, state = params, tx.init(params)
    ours, buf = params, jax.tree.map(jnp.zeros_like, params)
    import optax

    for step in range(4):
        grads = jax.tree.map(lambda p: jnp.sin(p + step), theirs)
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        ours, buf = plain.sgd_update(ours, buf, jax.tree.map(lambda p: jnp.sin(p + step), ours),
                                     jnp.int32(step), optim)
    for x, y in zip(jax.tree.leaves(theirs), jax.tree.leaves(ours)):
        np.testing.assert_allclose(x, y, rtol=2e-6, atol=1e-7)
    # and the harness finds the momentum buffer in the program's state
    from benchmarks.jobs.train_fit import find_momentum

    for x, y in zip(jax.tree.leaves(find_momentum(state)), jax.tree.leaves(buf)):
        np.testing.assert_allclose(x, y, rtol=2e-6, atol=1e-7)

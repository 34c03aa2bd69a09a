"""Fused conv/norm/act kernel tier (ops/pallas_fused.py + model wiring).

Named `test_zkernels` ON PURPOSE: the tier-1 suite is timeout-bound and
runs alphabetically, so the kernel additions sort late — a slow run
kills these, never the pre-existing suite (the test_zserving
convention). Everything here is tiny-shape CPU work; time at real
shapes is the benchmark's (`conv_roofline`, `depthwise_roofline`).

Contracts locked here:
- every fused op matches its unfused XLA reference — both lowerings
  (folded-XLA and interpret-mode Pallas), forward AND gradients;
- `model.fused_kernels` is a pure lowering knob: identical param trees,
  eval/train parity (batch_stats updates included) on the same
  variables;
- the fused train step holds `train_recompiles == 0` after warmup,
  guard-disarmed AND guard-armed (the RecompileGuard contract);
- `pallas_call` eqns are costed by the registered-FLOPs hooks and an
  unregistered kernel is a graphcheck finding (gc_flops satellite).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from pytorchvideo_accelerate_tpu.ops.kbench_refs import (
    ref_conv_bn_act,
    ref_dw_bn_act,
    ref_pw_bn_act,
)
from pytorchvideo_accelerate_tpu.ops.pallas_fused import (
    fused_conv3d_bn_act,
    fused_depthwise_bn_act,
    fused_pointwise_bn_act,
)


def _affine(rng, c):
    gamma = rng.standard_normal(c).astype(np.float32) * 0.1 + 1.0
    beta = rng.standard_normal(c).astype(np.float32) * 0.1
    mean = rng.standard_normal(c).astype(np.float32) * 0.1
    var = np.abs(rng.standard_normal(c)).astype(np.float32) + 1.0
    scale = gamma / np.sqrt(var + 1e-5)
    return jnp.asarray(scale), jnp.asarray(beta - mean * scale)


def _x(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("act", ["identity", "relu", "silu"])
def test_fused_ops_match_references_xla_lowering(act):
    """The folded-XLA lowering (what mode='auto' runs off-TPU) must equal
    the unfused conv->affine->act chain for all three op families."""
    rng = np.random.default_rng(0)
    x = _x(rng, (2, 5, 9, 11, 12))
    s, b = _affine(rng, 16)
    w = _x(rng, (1, 3, 3, 12, 16)) * 0.2
    np.testing.assert_allclose(
        np.asarray(fused_conv3d_bn_act(x, w, s, b, act=act, mode="xla")),
        np.asarray(ref_conv_bn_act(x, w, s, b, act=act)),
        rtol=2e-5, atol=2e-5)
    wp = _x(rng, (1, 1, 1, 12, 16)) * 0.2
    np.testing.assert_allclose(
        np.asarray(fused_pointwise_bn_act(x, wp, s, b, act=act,
                                          mode="xla")),
        np.asarray(ref_pw_bn_act(x, wp, s, b, act=act)),
        rtol=2e-5, atol=2e-5)
    k = _x(rng, (3, 3, 3, 1, 12)) * 0.2
    sd, bd = _affine(rng, 12)
    np.testing.assert_allclose(
        np.asarray(fused_depthwise_bn_act(x, k, sd, bd, act=act,
                                          mode="xla")),
        np.asarray(ref_dw_bn_act(x, k, sd, bd, act=act)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["pw", "conv", "dw"])
def test_fused_ops_match_references_pallas_interpret(case):
    """Interpret-mode Pallas (the identical kernel code the TPU compiles)
    must match the XLA reference on the CPU harness."""
    rng = np.random.default_rng(1)
    x = _x(rng, (2, 4, 7, 9, 8))
    if case == "pw":
        w = _x(rng, (1, 1, 1, 8, 12)) * 0.2
        s, b = _affine(rng, 12)
        got = fused_pointwise_bn_act(x, w, s, b, act="relu", mode="pallas")
        want = ref_pw_bn_act(x, w, s, b, act="relu")
    elif case == "conv":
        w = _x(rng, (3, 1, 1, 8, 12)) * 0.2
        s, b = _affine(rng, 12)
        got = fused_conv3d_bn_act(x, w, s, b, act="relu", mode="pallas")
        want = ref_conv_bn_act(x, w, s, b, act="relu")
    else:
        k = _x(rng, (3, 3, 3, 1, 8)) * 0.2
        s, b = _affine(rng, 8)
        got = fused_depthwise_bn_act(x, k, s, b, act="silu", mode="pallas")
        want = ref_dw_bn_act(x, k, s, b, act="silu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_fused_conv_gradients_match_reference(mode):
    """custom_vjp backward (pallas) and plain autodiff (xla) must equal
    jax.grad of the unfused reference — all four operands."""
    rng = np.random.default_rng(2)
    x = _x(rng, (1, 4, 6, 6, 8))
    w = _x(rng, (1, 3, 3, 8, 10)) * 0.2
    s, b = _affine(rng, 10)

    def loss(fn):
        return lambda x, w, s, b: jnp.sum(fn(x, w, s, b) ** 2)

    gp = jax.grad(loss(lambda x, w, s, b: fused_conv3d_bn_act(
        x, w, s, b, act="silu", mode=mode)), (0, 1, 2, 3))(x, w, s, b)
    gr = jax.grad(loss(lambda x, w, s, b: ref_conv_bn_act(
        x, w, s, b, act="silu")), (0, 1, 2, 3))(x, w, s, b)
    for a, r in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_fused_depthwise_and_pointwise_gradients_match():
    rng = np.random.default_rng(3)
    x = _x(rng, (1, 4, 6, 6, 8))
    k = _x(rng, (3, 3, 3, 1, 8)) * 0.2
    s, b = _affine(rng, 8)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) ** 2)

    gp = jax.grad(loss(lambda x, k, s, b: fused_depthwise_bn_act(
        x, k, s, b, act="relu", mode="pallas")), (0, 1, 2, 3))(x, k, s, b)
    gr = jax.grad(loss(lambda x, k, s, b: ref_dw_bn_act(
        x, k, s, b, act="relu")), (0, 1, 2, 3))(x, k, s, b)
    for a, r in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)
    w = _x(rng, (1, 1, 1, 8, 10)) * 0.2
    s, b = _affine(rng, 10)
    gp = jax.grad(loss(lambda x, w, s, b: fused_pointwise_bn_act(
        x, w, s, b, act="silu", mode="pallas")), (0, 1, 2, 3))(x, w, s, b)
    gr = jax.grad(loss(lambda x, w, s, b: ref_pw_bn_act(
        x, w, s, b, act="silu")), (0, 1, 2, 3))(x, w, s, b)
    for a, r in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_x3d_fused_knob_is_pure_lowering():
    """fused on/off: identical param trees, same-variables eval/train
    parity (running-stat updates included), matching grads."""
    from pytorchvideo_accelerate_tpu.models.x3d import X3D

    rng = np.random.default_rng(4)
    x = _x(rng, (2, 4, 16, 16, 3))
    kw = dict(num_classes=5, depths=(1, 1), stem_features=8,
              stage_features=(8, 16), head_features=32, dropout_rate=0.0)
    m_off = X3D(fused="off", **kw)
    m_xla = X3D(fused="xla", **kw)
    m_pal = X3D(fused="pallas", **kw)
    v = m_off.init(jax.random.key(0), x)
    assert (jax.tree.structure(v)
            == jax.tree.structure(m_xla.init(jax.random.key(0), x)))

    a = np.asarray(m_off.apply(v, x))
    np.testing.assert_allclose(a, np.asarray(m_xla.apply(v, x)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(a, np.asarray(m_pal.apply(v, x)),
                               rtol=1e-4, atol=1e-4)

    out0, mut0 = m_off.apply(v, x, train=True, mutable=["batch_stats"],
                             rngs={"dropout": jax.random.key(1)})
    out1, mut1 = m_xla.apply(v, x, train=True, mutable=["batch_stats"],
                             rngs={"dropout": jax.random.key(1)})
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               rtol=1e-3, atol=1e-3)
    for l0, l1 in zip(jax.tree.leaves(mut0), jax.tree.leaves(mut1)):
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   rtol=1e-4, atol=1e-4)

    def loss(vv, m):
        out = m.apply(vv, x, train=True, mutable=["batch_stats"],
                      rngs={"dropout": jax.random.key(1)})[0]
        return jnp.sum(out ** 2)

    for l0, l1 in zip(jax.tree.leaves(jax.grad(loss)(v, m_off)),
                      jax.tree.leaves(jax.grad(loss)(v, m_xla))):
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   rtol=5e-3, atol=5e-3)


def test_csn_and_r2plus1d_fused_knob_is_pure_lowering():
    """Every conv family that wires ConvBNAct honors the knob — a family
    that silently ignored `fused_kernels` would let users believe the
    kernel tier is active (the registry passes it to csn/r2plus1d too)."""
    from pytorchvideo_accelerate_tpu.models.csn import CSN
    from pytorchvideo_accelerate_tpu.models.r2plus1d import R2Plus1D

    rng = np.random.default_rng(10)
    x = _x(rng, (1, 4, 16, 16, 3))
    for cls, kw in ((CSN, dict(num_classes=4, depths=(1, 1),
                               stem_features=8, dropout_rate=0.0)),
                    (R2Plus1D, dict(num_classes=4, depths=(1, 1),
                                    stem_features=8, dropout_rate=0.0))):
        m_off = cls(fused="off", **kw)
        m_on = cls(fused="xla", **kw)
        v = m_off.init(jax.random.key(0), x)
        assert (jax.tree.structure(v)
                == jax.tree.structure(m_on.init(jax.random.key(0), x)))
        np.testing.assert_allclose(np.asarray(m_off.apply(v, x)),
                                   np.asarray(m_on.apply(v, x)),
                                   rtol=1e-4, atol=1e-4)


def test_fused_matches_unfused_under_bf16_policy():
    """bf16 compute (the production policy): the fused path's f32
    accumulation + folded affine must track the unfused conv+BN+act
    chain — both round once to bf16 at the end, so worst case is an ulp
    apart (the test_depthwise bf16 convention)."""
    from pytorchvideo_accelerate_tpu.models.common import ConvBNAct

    rng = np.random.default_rng(9)
    x = _x(rng, (2, 4, 8, 8, 16))
    m_off = ConvBNAct(16, kernel=(1, 3, 3), fused="off",
                      dtype=jnp.bfloat16)
    m_on = ConvBNAct(16, kernel=(1, 3, 3), fused="xla",
                     dtype=jnp.bfloat16)
    v = m_off.init(jax.random.key(0), x)
    a = np.asarray(m_off.apply(v, x), np.float32)
    b = np.asarray(m_on.apply(v, x), np.float32)
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
    assert np.mean(a == b) > 0.9  # overwhelmingly identical after rounding


def test_fused_falls_back_on_strided_and_foreign_act_sites():
    """Strided ConvBNAct sites and unrecognized activations must keep the
    unfused path (same function) rather than silently change geometry."""
    from pytorchvideo_accelerate_tpu.models.common import ConvBNAct

    rng = np.random.default_rng(5)
    x = _x(rng, (1, 4, 8, 8, 6))
    for kwargs in (dict(stride=(1, 2, 2)),        # strided -> fallback
                   dict(act=jnp.tanh)):           # foreign act -> fallback
        m_off = ConvBNAct(8, kernel=(1, 3, 3), fused="off", **kwargs)
        m_on = ConvBNAct(8, kernel=(1, 3, 3), fused="auto", **kwargs)
        v = m_off.init(jax.random.key(0), x)
        assert (jax.tree.structure(v)
                == jax.tree.structure(m_on.init(jax.random.key(0), x)))
        np.testing.assert_array_equal(np.asarray(m_off.apply(v, x)),
                                      np.asarray(m_on.apply(v, x)))


def test_fused_train_step_zero_recompiles_guarded_and_not():
    """RecompileGuard contract for the fused-kernel train step: after the
    first (legitimate) compile the jit cache must not grow across steps
    with distinct batches — guard-disarmed AND guard-armed variants."""
    from pytorchvideo_accelerate_tpu.analysis.recompile_guard import (
        RecompileGuard,
    )
    from pytorchvideo_accelerate_tpu.trainer.steps import make_train_step
    from pytorchvideo_accelerate_tpu.utils.bench_setup import (
        build_step_setup,
    )

    setup = build_step_setup(
        "tiny3d", frames=4, crop=16, batch_per_chip=1, num_classes=4,
        overrides={"fused_kernels": "auto"})
    for step_fn in (
            setup.step,
            make_train_step(setup.model, setup.tx, setup.mesh,
                            guard_skip=True, health_metrics=True)):
        # the step donates its state arg — each variant gets a fresh copy
        state = jax.tree.map(
            lambda a: a.copy() if isinstance(a, jax.Array) else a,
            setup.state)
        state, _ = step_fn(state, setup.device_batch(0), jax.random.key(0))
        guard = RecompileGuard(step_fn)
        guard.arm()
        for i in range(1, 3):
            state, metrics = step_fn(state, setup.device_batch(i),
                                     jax.random.key(i))
        assert np.isfinite(float(np.asarray(metrics["loss"])))
        if guard.supported:
            assert guard.sample() == 0


def test_pallas_flops_hooks_cost_fused_kernels():
    """gc_flops satellite: fused pallas_call eqns are costed (fwd and the
    custom_vjp bwd kernels) and an unregistered kernel is a finding."""
    from jax.experimental import pallas as pl

    from pytorchvideo_accelerate_tpu.analysis.gc_flops import (
        check_flops,
        jaxpr_flops,
    )

    x = jnp.ones((1, 4, 8, 8, 8))
    k = jnp.ones((3, 3, 3, 1, 8))
    s, b = jnp.ones((8,)), jnp.zeros((8,))
    cj = jax.make_jaxpr(lambda x, k, s, b: fused_depthwise_bn_act(
        x, k, s, b, act="silu", mode="pallas"))(x, k, s, b)
    res = jaxpr_flops(cj)
    assert res["eqn_counts"]["pallas_call"] == 1
    # exact tap arithmetic: 2 * out_elems * taps + epilogue
    out_elems = 1 * 4 * 8 * 8 * 8
    assert res["by_class"]["pallas"] == 2.0 * out_elems * 27 + 2.0 * out_elems
    assert res["unregistered_pallas"] == []
    findings, _ = check_flops(cj)
    assert not findings

    # backward kernels are registered too — a grad graph stays clean
    g = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(fused_depthwise_bn_act(
        x, k, s, b, act="silu", mode="pallas"))))(x)
    gres = jaxpr_flops(g)
    assert gres["unregistered_pallas"] == []
    assert gres["by_class"]["pallas"] > res["by_class"]["pallas"]

    # an unregistered kernel must become a finding, not a silent zero
    def _zkernels_opaque(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    oj = jax.make_jaxpr(lambda x: pl.pallas_call(
        _zkernels_opaque,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x))(jnp.ones((8, 128)))
    findings, summary = check_flops(oj)
    assert summary["unregistered_pallas"] == ["_zkernels_opaque"]
    assert len(findings) == 1 and "registered FLOPs hook" in \
        findings[0]["message"]


def test_even_kernel_and_mode_validation():
    """Even-tap dense kernels fall back to the XLA lowering under
    mode='pallas' (the halo kernel hard-codes odd SAME geometry), and an
    unknown mode fails loudly."""
    rng = np.random.default_rng(6)
    x = _x(rng, (1, 4, 8, 8, 4))
    w = _x(rng, (2, 3, 3, 4, 6)) * 0.2
    s, b = _affine(rng, 6)
    got = fused_conv3d_bn_act(x, w, s, b, act="relu", mode="pallas")
    want = lax.conv_general_dilated(
        x, w * s, (1, 1, 1), [(k // 2, k // 2) for k in w.shape[:3]],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC")) + b
    np.testing.assert_allclose(np.asarray(got),
                               np.maximum(np.asarray(want), 0.0),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="auto|pallas|xla"):
        fused_conv3d_bn_act(x, w, s, b, act="relu", mode="bogus")
    from pytorchvideo_accelerate_tpu.config import ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model

    with pytest.raises(ValueError, match="fused_kernels"):
        create_model(ModelConfig(name="tiny3d", num_classes=2,
                                 fused_kernels="bogus"))


# --- lane fold: the RGB stems as a lane-filling contraction -----------------
# (ops/lane_fold.py; docs/KERNELS.md). The rule's backend half is off on the
# CPU, so every test here forces it and leaves the shape half to decide.

@pytest.fixture
def fold_forced(monkeypatch):
    from pytorchvideo_accelerate_tpu.ops import lane_fold

    monkeypatch.setattr(lane_fold, "takes_fold", lambda: True)
    return lane_fold


# name: (kernel, stride, Cin, Cout, input (B,T,H,W), the G the rule gives)
LANE_FOLD_SITES = {
    "fast_stem": ((5, 7, 7), (1, 2, 2), 3, 8, (2, 4, 12, 64), 16),
    "slow_stem": ((1, 7, 7), (1, 2, 2), 3, 64, (2, 2, 12, 16), 2),
    "stride1_24ch": ((3, 3, 3), (1, 1, 1), 3, 24, (1, 4, 8, 20), 5),
    # W = 48 is no multiple of G x stride_w = 32: keeps nn.Conv
    "width_not_divisible": ((5, 7, 7), (1, 2, 2), 3, 8, (2, 4, 12, 48), 0),
    # a 64-channel input is no stem: the rule does not fire
    "cin64": ((1, 3, 3), (1, 1, 1), 64, 64, (1, 2, 8, 16), 0),
    "cout128": ((1, 3, 3), (1, 2, 2), 3, 128, (1, 2, 8, 16), 0),
}


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("site", sorted(LANE_FOLD_SITES))
def test_lane_fold_matches_conv_general_dilated(fold_forced, site, policy):
    """The folded lowering against `lax.conv_general_dilated`: the op's
    output and weight gradient, then the ConvBNAct site around it (train
    mode: batch statistics, running averages, gradients of all three
    parameters) against the same site with the fold off."""
    from pytorchvideo_accelerate_tpu.models.common import ConvBNAct

    kernel, stride, cin, cout, shape, want_group = LANE_FOLD_SITES[site]
    dtype = jnp.dtype(policy)
    # f32: the two contractions differ in summation order only; bf16: both
    # round an f32 accumulation once, the gradient sums G rounded parts
    tol = 2e-5 if policy == "float32" else 2e-2
    rng = np.random.default_rng(11)
    x = _x(rng, shape + (cin,))
    group = fold_forced.fold_group(cin, cout, kernel, stride, shape[-1])
    assert group == want_group

    if group:
        w = _x(rng, kernel + (cin, cout)) * 0.1
        pads = [(k // 2, k // 2) for k in kernel]

        def plain(w):
            return lax.conv_general_dilated(
                x.astype(dtype), w.astype(dtype), stride, pads,
                dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))

        def folded(w):
            return fold_forced.unfold(fold_forced.lane_fold_conv3d(
                x.astype(dtype), w.astype(dtype), stride, group), group)

        ref = np.asarray(plain(w), np.float32)
        got = np.asarray(folded(w), np.float32)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, rtol=tol,
                                   atol=tol * np.abs(ref).max())
        ct = _x(rng, ref.shape)
        g_ref, g_got = (np.asarray(jax.grad(
            lambda w: jnp.sum(f(w).astype(jnp.float32) * ct))(w))
            for f in (plain, folded))
        assert (np.linalg.norm(g_got - g_ref)
                <= tol * np.linalg.norm(g_ref))

    site_mod = ConvBNAct(cout, kernel=kernel, stride=stride, dtype=dtype)
    v = site_mod.init(jax.random.key(0), x)

    def run(params, fold):
        fold_forced.takes_fold = lambda: fold
        with fold_forced.count_sites() as sites:
            y, mut = site_mod.apply(
                {"params": params, "batch_stats": v["batch_stats"]}, x,
                train=True, mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, mut, sites)

    outs = {}
    for fold in (False, True):
        (_, (y, mut, sites)), grads = jax.value_and_grad(
            run, has_aux=True)(v["params"], fold)
        outs[fold] = (y, mut, grads)
        assert len(sites) == (1 if fold and group else 0)
    for a, b in zip(jax.tree.leaves(outs[False]), jax.tree.leaves(outs[True])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if not group:
            np.testing.assert_array_equal(a, b)  # the same nn.Conv graph
        else:
            assert np.linalg.norm(a - b) <= 10 * tol * np.linalg.norm(a)
    # eval mode (serving): running statistics, the same lowering
    fold_forced.takes_fold = lambda: True
    e1 = np.asarray(site_mod.apply(v, x), np.float32)
    fold_forced.takes_fold = lambda: False
    e0 = np.asarray(site_mod.apply(v, x), np.float32)
    np.testing.assert_allclose(e1, e0, rtol=10 * tol,
                               atol=10 * tol * np.abs(e0).max())


def _toy_slowfast():
    """Stems at the published widths (3 -> 64 and 3 -> 8), so that no other
    site has few enough input channels for the rule."""
    from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast

    rng = np.random.default_rng(12)
    fast = _x(rng, (2, 8, 16, 64, 3))
    model = SlowFast(num_classes=5, depths=(1, 1), stem_features=64,
                     slow_temporal_kernels=(1, 3), dropout_rate=0.0)
    return model, (fast[:, ::4], fast)


def test_slowfast_lane_fold_is_pure_lowering(fold_forced):
    """Fold forced or not: the same parameter tree (shapes and values at
    the same key), eval/train parity on the same variables, matching
    running-stat updates and gradients; both stems take it, nothing else."""
    model, pathways = _toy_slowfast()
    v = model.init(jax.random.key(0), pathways)
    fold_forced.takes_fold = lambda: False
    v_off = model.init(jax.random.key(0), pathways)
    assert jax.tree.structure(v) == jax.tree.structure(v_off)
    for a, b in zip(jax.tree.leaves(v), jax.tree.leaves(v_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def loss(params, fold):
        fold_forced.takes_fold = lambda: fold
        with fold_forced.count_sites() as sites:
            out, mut = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                pathways, train=True, mutable=["batch_stats"])
        return jnp.sum(out ** 2), (out, mut, sites)

    (_, (out0, mut0, sites0)), g0 = jax.value_and_grad(
        loss, has_aux=True)(v["params"], False)
    (_, (out1, mut1, sites1)), g1 = jax.value_and_grad(
        loss, has_aux=True)(v["params"], True)
    assert sites0 == set()
    assert sites1 == {("slow_stem",), ("fast_stem",)}
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               rtol=1e-3, atol=1e-3)
    for a, b in zip(jax.tree.leaves((mut0, g0)), jax.tree.leaves((mut1, g1))):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-3 * max(np.linalg.norm(a), 1e-6)
    fold_forced.takes_fold = lambda: True
    e1 = np.asarray(model.apply(v, pathways))
    fold_forced.takes_fold = lambda: False
    np.testing.assert_allclose(e1, np.asarray(model.apply(v, pathways)),
                               rtol=1e-4, atol=1e-4)


def _lowered_toy_step(model, batch):
    """(the lowered train step of a toy model on one device, the value the
    `pva_conv_lane_fold_sites` gauge holds after its trace)."""
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig
    from pytorchvideo_accelerate_tpu.obs import get_registry
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import (
        make_train_step,
        model_inputs,
    )
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tx = optax.sgd(0.01)
    v = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model_inputs(batch)))
    state = jax.eval_shape(
        lambda p, s: TrainState.create(p, s, tx), v["params"],
        v["batch_stats"])
    lowered = make_train_step(model, tx, mesh).lower(
        state, batch, jax.random.key(0))
    return lowered, get_registry().get("pva_conv_lane_fold_sites").value()


@pytest.mark.parametrize("family", ["slowfast", "slowfast_unforced", "x3d"])
def test_lane_fold_sites_gauge(fold_forced, family):
    """The gauge counts the ConvBNAct sites of the traced train step that
    took the fold: both SlowFast stems when forced, none on the CPU's own
    rule, none for X3D (its RGB stem `stem_xy` is an nn.Conv)."""
    if family == "x3d":
        from pytorchvideo_accelerate_tpu.models.x3d import X3D

        model = X3D(num_classes=5, depths=(1, 1), stem_features=8,
                    stage_features=(8, 16), head_features=32,
                    dropout_rate=0.0)
        batch = {"video": jnp.zeros((2, 4, 16, 64, 3))}
    else:
        model, (slow, fast) = _toy_slowfast()
        batch = {"slow": slow, "fast": fast}
    batch["label"] = jnp.zeros((2,), jnp.int32)
    if family == "slowfast_unforced":
        fold_forced.takes_fold = lambda: jax.default_backend() == "tpu"
    _, sites = _lowered_toy_step(model, batch)
    assert sites == (2 if family == "slowfast" else 0)


def test_lane_fold_ops_sit_under_the_conv_scope(fold_forced):
    """Every op the fold adds to a SlowFast train step (the input's fold,
    the weight's expansion, the conv, forward and backward) carries the
    scope the unfused nn.Conv would open, `<site>/conv`: the benchmark's
    `conv_roofline` and the ledger's breakdown select device time by it."""
    import re

    model, (slow, fast) = _toy_slowfast()
    batch = {"slow": slow, "fast": fast, "label": jnp.zeros((2,), jnp.int32)}
    lowered, sites = _lowered_toy_step(model, batch)
    assert sites == 2
    # the text names its locations by alias: an op's is
    # loc("<name stack>"(#call stack)), a call stack callsite(#inner at
    # #outer), a frame loc("<function>"(#file)), a file loc("<path>":line)
    defs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$",
                           lowered.as_text(debug_info=True), re.M))

    def innermost_file(ref):
        d = defs[ref]
        m = (re.match(r"callsite\((#loc\d+) at", d)
             or re.match(r'"[^"]*"\((#loc\d+)\)$', d))
        if m:
            return innermost_file(m.group(1))
        m = re.match(r'"([^"]+)":\d+', d)
        return m.group(1) if m else ""

    stacks = [m.group(1) for m in (
        re.match(r'"(jit\([^"]*)"\((#loc\d+)\)$', d) for d in defs.values())
        if m and innermost_file(m.group(2)).endswith("ops/lane_fold.py")]
    assert len(stacks) >= 8  # 2 sites x (reshape, einsum, conv) x fwd/bwd
    stray = [s for s in stacks
             if not re.search(r"(fast|slow)_stem\)?/conv/", s)]
    assert not stray, stray[:5]

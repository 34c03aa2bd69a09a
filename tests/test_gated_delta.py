"""ops/gated_delta.py: the chunked gated delta rule against the per-token
recurrence that defines it, forward and every gradient, in both lowerings:
the XLA form (`xla`: toy widths, the rule's own choice on the CPU) and the
Pallas kernel pair of ops/pallas_gated_delta.py, interpreted (`kernel`: 128-
wide heads and the backend half of the rule forced, one key head under two
value heads)."""

import jax
import jax.numpy as jnp
import pytest

from pytorchvideo_accelerate_tpu.ops import gated_delta, pallas_gated_delta
from pytorchvideo_accelerate_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)

# (b, key heads, value heads, dk, dv) of each lowering's cases
SIZES = {"xla": (2, 3, 3, 16, 24), "kernel": (1, 1, 2, 128, 128)}


@pytest.fixture(params=["xla", "kernel"])
def lowering(request, monkeypatch):
    if request.param == "kernel":
        monkeypatch.setattr(gated_delta, "takes_kernel", lambda: True)
    return request.param


def _inputs(t, decay, beta, seed=0, b=2, h=3, dk=16, dv=24, hk=None):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, t, hk or h, dk))
    k = jax.random.normal(ks[1], (b, t, hk or h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, t, h), minval=0.1)
    if beta is None:
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    else:
        beta = jnp.full((b, t, h), beta, jnp.float32)
    return q, k, v, g, beta


def _sized(lowering, t, decay, beta, seed=0):
    b, hk, h, dk, dv = SIZES[lowering]
    return _inputs(t, decay, beta, seed, b=b, h=h, dk=dk, dv=dv, hk=hk)


def _took_kernel(fn, *args):
    """(fn's result, the number of `gated_delta_rule` calls that took the
    kernel while it ran)."""
    with gated_delta.count_sites() as sites:
        out = fn(*args)
    return out, len(sites)


# float32 on the CPU: the two forms differ by summation order only. 2e-5 is
# ten float32 ulps of the O(1) outputs times the 64-token products; strong
# decay (exp(-20 a token)) multiplies rounding of tiny factors and reads 3e-5
# relative on the gradient of g, so gradients get 2e-4
CASES = [
    pytest.param(128, 1.0, None, id="two_whole_chunks"),
    pytest.param(150, 1.0, None, id="tail_of_22_tokens"),
    pytest.param(37, 1.0, None, id="shorter_than_a_chunk"),
    pytest.param(150, 20.0, None, id="strong_decay"),
    pytest.param(150, 0.01, None, id="weak_decay"),
    pytest.param(150, 1.0, 0.0, id="beta_0_writes_nothing"),
    pytest.param(150, 1.0, 1.0, id="beta_1_full_delta"),
]


@pytest.mark.parametrize("t,decay,beta", CASES)
def test_chunked_forward_equals_recurrence(lowering, t, decay, beta):
    args = _sized(lowering, t, decay, beta)
    o_ref, s_ref = gated_delta_recurrence(*args)
    (o, s), took = _took_kernel(gated_delta_rule, *args)
    assert took == (lowering == "kernel")
    assert o.shape == o_ref.shape == args[2].shape
    assert float(jnp.abs(o - o_ref).max()) < 2e-5
    assert float(jnp.abs(s - s_ref).max()) < 2e-5
    assert bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())
    if beta == 0.0:
        assert float(jnp.abs(o).max()) == 0.0  # nothing was ever written


def _loss(fn):
    """Reads the outputs and the last state, so both cotangents are live."""
    def loss(*a):
        o, state = fn(*a)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(
            state * jnp.cos(jnp.arange(state.size).reshape(state.shape)))
    return loss


@pytest.mark.parametrize("t,decay,beta", CASES)
def test_chunked_gradients_equal_recurrence(lowering, t, decay, beta):
    args = _sized(lowering, t, decay, beta, seed=1)
    want = jax.grad(_loss(gated_delta_recurrence), argnums=range(5))(*args)
    got, took = _took_kernel(
        jax.grad(_loss(gated_delta_rule), argnums=range(5)), *args)
    assert took == (lowering == "kernel")
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape, name
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max()) + 1e-30
        assert float(jnp.abs(a - b).max()) / scale < 2e-4, name


def test_bfloat16_inputs_keep_a_float32_state(lowering):
    """The policy's path: bfloat16 q, k, v, float32 decay and state. Against
    the float32 recurrence on the same rounded inputs the outputs differ by
    bfloat16 rounding of the products' operands (2^-8 relative, a few of
    them): 3e-2 of the largest output."""
    q, k, v, g, beta = _sized(lowering, 200, 1.0, None, seed=2)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    o, state = gated_delta_rule(qb, kb, vb, g, beta)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    o_ref, s_ref = gated_delta_recurrence(qb, kb, vb, g, beta)
    assert float(jnp.abs(o.astype(jnp.float32) - o_ref).max()) \
        < 3e-2 * float(jnp.abs(o_ref).max())
    assert float(jnp.abs(state - s_ref).max()) \
        < 3e-2 * float(jnp.abs(s_ref).max())


def test_bfloat16_gradients_keep_their_operands_dtype(lowering):
    """Under the bfloat16 policy each gradient comes back in its operand's
    dtype (q, k, v bfloat16; g, beta float32) and within bfloat16 rounding
    of the float32 recurrence's on the same rounded inputs."""
    q, k, v, g, beta = _sized(lowering, 200, 1.0, None, seed=3)
    args = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    want = jax.grad(_loss(gated_delta_recurrence), argnums=range(5))(*args)
    got = jax.grad(_loss(gated_delta_rule), argnums=range(5))(*args)
    for name, a, b, x in zip("q k v g beta".split(), got, want, args):
        assert a.dtype == x.dtype, name
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        assert float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max()) < 5e-2 * scale, name


@pytest.mark.parametrize("hk,hv", [(1, 1), (2, 2), (2, 4), (3, 6)],
                         ids=["1_under_1", "2_under_2", "2_under_4",
                              "3_under_6"])
def test_kernel_and_xla_form_agree(monkeypatch, hk, hv):
    """One algorithm, two lowerings: on the same 128-wide inputs the
    interpreted kernel and the XLA chunked form give the same outputs,
    state and gradients to float32 rounding, whatever the number of value
    heads a key head serves (an even number of key heads goes two a grid
    step, an odd one one)."""
    args = _inputs(130, 1.0, None, seed=4, b=1, h=hv, hk=hk, dk=128, dv=128)
    assert pallas_gated_delta.key_heads_a_step(hk) == (2 if hk % 2 == 0 else 1)

    def outputs_and_grads():
        def f(*a):
            out = gated_delta_rule(*a)
            return _loss(lambda: out)(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            f, argnums=range(5), has_aux=True))(*args)
        return out, grads

    xla, xla_grads = outputs_and_grads()
    monkeypatch.setattr(gated_delta, "takes_kernel", lambda: True)
    ((o, state), grads), took = _took_kernel(outputs_and_grads)
    assert took == 1
    assert float(jnp.abs(o - xla[0]).max()) < 2e-5
    assert float(jnp.abs(state - xla[1]).max()) < 2e-5
    for name, a, b in zip("q k v g beta".split(), grads, xla_grads):
        scale = float(jnp.abs(b).max()) + 1e-30
        assert float(jnp.abs(a - b).max()) / scale < 2e-4, name


@pytest.mark.parametrize("backend,dk,dv,takes", [
    ("tpu", 128, 128, True), ("tpu", 256, 128, True), ("tpu", 16, 16, False),
    ("tpu", 128, 64, False), ("cpu", 128, 128, False), ("cpu", 16, 16, False),
])
def test_who_takes_the_kernel(monkeypatch, backend, dk, dv, takes):
    """The rule, whole: the TPU backend and head widths that are multiples
    of 128 lanes; the CPU and the toy `qwen3_next_t` (dk = dv = 16) keep the
    XLA form. Nothing else selects the path."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert (gated_delta.takes_kernel()
            and gated_delta.kernel_shapes(dk, dv)) == takes


@pytest.mark.parametrize("forced,width,sites", [
    (True, 128, 3), (False, 128, 0), (True, 16, 0)],
    ids=["tpu_rule_128", "cpu_rule_128", "tpu_rule_toy_16"])
def test_kernel_sites_gauge(monkeypatch, forced, width, sites):
    """`pva_gdn_scan_kernel_sites`, set while the next-token step is traced:
    the three DeltaNet layers of a period of four where the rule holds, none
    by the CPU's own rule, none at the toy model's 16-wide heads."""
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig
    from pytorchvideo_accelerate_tpu.models.qwen3_next import (
        Qwen3Next,
        Qwen3NextArch,
    )
    from pytorchvideo_accelerate_tpu.obs import get_registry
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    if forced:
        monkeypatch.setattr(gated_delta, "takes_kernel", lambda: True)
    arch = Qwen3NextArch(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=width,
        linear_value_head_dim=width, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        vocab_size=256)
    model = Qwen3Next(arch, dtype=jnp.float32, remat=True)
    batch = {"tokens": jnp.zeros((1, 128), jnp.int32)}
    mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tx = optax.sgd(0.01)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), batch["tokens"]))["params"]
    state = jax.eval_shape(lambda p: TrainState.create(p, {}, tx), params)
    make_lm_step(model, tx, mesh).lower(state, batch, jax.random.key(0))
    registry = get_registry()
    assert registry.get("pva_gdn_scan_kernel_sites").value() == sites
    assert registry.get("pva_conv_lane_fold_sites").value() == 0


def test_chunk_must_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_rule(*_inputs(16, 1.0, None), chunk=48)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_graphcheck_costs_the_kernels(monkeypatch, grad):
    """graphcheck's flops pass has a hook for each `pva_gdn_*` call (no
    finding), and the hooks count the products the kernels do: checked
    against the same count taken from the kernel bodies' own `dot_general`s,
    a grid step's times the grid."""
    from pytorchvideo_accelerate_tpu.analysis import gc_flops

    monkeypatch.setattr(gated_delta, "takes_kernel", lambda: True)
    args = _inputs(300, 1.0, None, b=2, h=4, hk=2, dk=128, dv=256)
    fn = (jax.grad(_loss(gated_delta_rule), argnums=range(5)) if grad
          else gated_delta_rule)
    closed = jax.make_jaxpr(fn)(*args)
    findings, summary = gc_flops.check_flops(closed)
    assert findings == [], findings
    assert summary["eqn_counts"]["pallas_call"] == (2 if grad else 1)

    def kernel_products(jaxpr):
        total = 0.0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grid = eqn.params["grid_mapping"].grid
                body = gc_flops.jaxpr_flops(
                    jax.extend.core.ClosedJaxpr(eqn.params["jaxpr"], ()))
                total += body["by_class"]["dot"] * gc_flops._prod(grid)
            for v in eqn.params.values():
                for sub in gc_flops._sub_closed(v):
                    total += kernel_products(sub.jaxpr)
        return total

    assert summary["by_class"]["pallas"] == pytest.approx(
        kernel_products(closed.jaxpr), rel=1e-6)

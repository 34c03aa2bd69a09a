"""models/granite_h.py through the normal path, against the plain reference
(benchmarks/reference/granite_h.py): logits, loss and every leaf's gradient
on seeded weights, the tied table's gradient as the sum of its two uses, the
held slice of the vocabulary, the reference's planted faults told apart from
the sound program, the shared Mamba-2 mixer and `Attention(scale=None)`
lowering as they did before, and the layers counted while a step is
traced."""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from benchmarks.reference import granite_h as ref
from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
from pytorchvideo_accelerate_tpu.models import create_model, model_task
from pytorchvideo_accelerate_tpu.models import granite_h, nemotron_h
from pytorchvideo_accelerate_tpu.models.lm_common import (
    Attention,
    _dense,
    causal_conv,
    rms,
)
from pytorchvideo_accelerate_tpu.models.mamba2 import Mamba
from pytorchvideo_accelerate_tpu.ops import attention
from pytorchvideo_accelerate_tpu.ops.ssd import ssd_scan
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

# the toy of models/__init__.py `granite_h_t`, under the reference's keys:
# the published layer_types' first ten layers (attention at layer 5)
ARCH = dict(hidden_size=64, num_hidden_layers=10, num_attention_heads=4,
            num_key_value_heads=2, shared_intermediate_size=96,
            mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=1,
            mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=32,
            embedding_multiplier=12, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8,
            rms_norm_eps=1e-5, vocab_size=256)
T = 150  # no multiple of the chunk (32) or the loss block

# float32 policy: program and reference differ by summation order (the
# chunked scan against the per-token recurrence, blocked attention against
# dense): 1e-5 of the largest entry (read: 0 on the loss, under 1e-6 on the
# logits and every gradient leaf). bfloat16 policy: every projection operand
# and activation rounded to 8 bits of mantissa through 20 residual units:
# logits within 3e-2 of their range, loss within 2e-3, a wide leaf's
# gradient NORM within 10%.
POLICIES = [pytest.param("fp32", 1e-5, 1e-6, id="float32_tight"),
            pytest.param("bf16", 3e-2, 2e-3, id="bfloat16_loose")]


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda: ref.init_params(ARCH, 3))()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, T), 0, ARCH["vocab_size"])


def _model(policy="fp32", **kw):
    return create_model(ModelConfig(name="granite_h_t", **kw), policy)


def _scored(tokens):
    targets = jnp.roll(tokens, -1, axis=1)
    weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    return targets, weights


def _program_loss(model, params, tokens):
    targets, weights = _scored(tokens)
    out = model.apply({"params": params}, tokens, targets=targets,
                      weights=weights, train=True)
    return out["loss_sum"] / out["count"], out


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's loss, gradient and logits (float32)."""
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_and_count(p, tokens, ARCH)[0]))(params)
    return loss, grads, jax.jit(lambda p: ref.logits(p, tokens, ARCH))(params)


def test_registry_declares_the_task_and_the_tree_is_the_references(params):
    assert model_task("granite_h_t") == model_task(
        "granite_4_0_h_micro") == "next_token"
    made = jax.jit(_model().init)(jax.random.key(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    got = {n: x.shape for n, x in _leaves(made).items()}
    assert got == {n: x.shape for n, x in _leaves(params).items()}
    kinds = ["mamba" if "mamba" in made[f"layer_{i}"]["mixer"] else "attn"
             for i in range(10)]
    assert kinds == ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    # the tied table is the one vocabulary-sized leaf: no head of its own
    assert [n for n, x in _leaves(made).items() if 256 in x.shape] == ["['embed']"]
    # the program's own initialiser: the table N(0, 0.1), matrices N(0,
    # 0.02), A_log = log(1..H), D = 1, dt_bias the softplus inverse of dt in
    # [1e-3, 0.1]
    assert 0.09 < float(jnp.std(made["embed"])) < 0.11
    mlp = made["layer_0"]["ffn"]["mlp"]
    assert 0.018 < float(jnp.std(mlp["input_linear"])) < 0.022
    mamba = made["layer_0"]["mixer"]["mamba"]
    assert bool(jnp.all(mamba["A_log"] == jnp.log(jnp.arange(1.0, 5.0))))
    assert bool(jnp.all(mamba["D"] == 1.0))
    dt = jax.nn.softplus(mamba["dt_bias"])
    assert bool(jnp.all((dt >= 1e-3 - 1e-9) & (dt <= 0.1 + 1e-9)))


@pytest.mark.parametrize("policy,logit_tol,loss_tol", POLICIES)
def test_logits_and_loss_against_the_reference(params, tokens, want, policy,
                                               logit_tol, loss_tol):
    model = _model(policy)
    want_loss, _, want_logits = want
    got = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    assert got.dtype == jnp.float32 and got.shape == (2, T, 256)
    assert float(jnp.abs(got - want_logits).max()) < \
        logit_tol * float(jnp.abs(want_logits).max())
    loss, out = jax.jit(lambda p: _program_loss(model, p, tokens))(params)
    assert abs(float(loss) - float(want_loss)) < loss_tol * float(want_loss)
    assert "expert_rows" not in out   # a dense model: no mixture layer


@pytest.fixture(scope="module")
def program_grads(params, tokens):
    """{policy: the program's gradient}, one compile a policy."""
    return {policy: jax.jit(jax.grad(lambda p: _program_loss(
        _model(policy), p, tokens)[0]))(params) for policy in ("fp32", "bf16")}


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
def test_every_leafs_gradient_against_the_reference(want, program_grads,
                                                    policy):
    got = _leaves(program_grads[policy])
    expected = _leaves(want[1])
    assert got.keys() == expected.keys()
    for name, w in expected.items():
        g = got[name]
        if policy == "fp32":
            assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max()), name
        elif w.size >= 512:
            norm = float(jnp.linalg.norm(w))
            assert abs(float(jnp.linalg.norm(g)) - norm) <= 0.1 * norm, name


def test_tied_tables_gradient_is_the_sum_of_its_two_uses(params, tokens,
                                                         program_grads):
    """The reference with the head reading a copy of the table hands the
    embedding's part and the head's part back as two leaves; their sum is
    the program's gradient of its one table, and neither part alone is."""
    untied = {**params, "lm_head": params["embed"]}
    parts = jax.jit(jax.grad(lambda p: ref.loss_and_count(
        p, tokens, ARCH, fault="head_untied")[0]))(untied)
    got = program_grads["fp32"]["embed"]
    both = parts["embed"] + parts["lm_head"]
    scale = float(jnp.abs(both).max())
    assert float(jnp.abs(got - both).max()) <= 1e-5 * scale
    for part in (parts["embed"], parts["lm_head"]):
        assert float(jnp.abs(got - part).max()) > 0.01 * scale


def test_logits_are_over_the_held_slice_only(params):
    """A share of 128 of the toy's 256 rows holds the table's first 128 rows:
    on ids inside the slice its logits are the whole model's first 128
    columns, and nothing else."""
    ids = jax.random.randint(jax.random.key(7), (2, 40), 0, 128)
    whole = jax.jit(lambda p: _model().apply({"params": p}, ids))(params)
    half = _model(vocab_size=128)
    cut = {**params, "embed": params["embed"][:128]}
    got = jax.jit(lambda p: half.apply({"params": p}, ids))(cut)
    assert got.shape == (2, 40, 128)
    assert float(jnp.abs(got - whole[..., :128]).max()) <= \
        1e-5 * float(jnp.abs(whole).max())


@pytest.fixture(scope="module")
def faulty(tokens):
    """The reference's loss and gradient with a fault switched on by a
    traced vector over `ref.SWITCHED`: one compile serves every fault."""
    return jax.jit(jax.value_and_grad(
        lambda p, flags: ref.loss_and_count(p, tokens, ARCH, flags=flags)[0]))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_planted_fault_is_told_apart_from_the_sound_program(params, want,
                                                            faulty, fault):
    """Each of the family's faults moves the toy's loss or a gradient leaf by
    far more than the float32 tolerance above (1e-5)."""
    want_loss, want_grads, _ = want
    loss, grads = faulty(params, jnp.array([name == fault
                                            for name in ref.SWITCHED]))
    expected = _leaves(want_grads)
    moved = max(float(jnp.abs(g - expected[n]).max())
                / max(float(jnp.abs(expected[n]).max()), 1e-30)
                for n, g in _leaves(grads).items())
    moved = max(moved, abs(float(loss) - float(want_loss)) / float(want_loss))
    assert moved > 100 * 1e-5, (fault, moved)


NEMOTRON_TOY = nemotron_h.NemotronHArch(
    hidden_size=64, num_hidden_layers=6, num_attention_heads=4,
    num_key_value_heads=1, head_dim=16, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=16, chunk_size=32, n_routed_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, vocab_size=256)


class _MambaBefore(nn.Module):
    """Nemotron-H's mixer as models/nemotron_h.py held it before it moved to
    models/mamba2.py, word for word but its initialisers (the parameters
    are the shared mixer's)."""

    arch: nemotron_h.NemotronHArch
    dtype: Any

    @nn.compact
    def __call__(self, x):
        a, dt = self.arch, self.dtype
        b, t, _ = x.shape
        h, p = a.mamba_num_heads, a.mamba_head_dim
        g, n = a.n_groups, a.ssm_state_size
        inner = h * p
        conv_dim = inner + 2 * g * n
        with jax.named_scope("in_proj"):
            zxbcdt = _dense(self, "in_proj", x, inner + conv_dim + h, dt)
            z, xbc, step = (zxbcdt[..., :inner],
                            zxbcdt[..., inner:inner + conv_dim],
                            zxbcdt[..., inner + conv_dim:])
        with jax.named_scope("conv"):
            taps = a.conv_kernel
            w = self.param("conv", _unused, (taps, conv_dim)).astype(dt)
            bias = self.param("conv_bias", _unused, (conv_dim,))
            xbc = jax.nn.silu(causal_conv(xbc, w) + bias.astype(dt))
        with jax.named_scope("scan"):
            a_log = self.param("A_log", _unused, (h,))
            dt_bias = self.param("dt_bias", _unused, (h,))
            skip = self.param("D", _unused, (h,))
            delta = jax.nn.softplus(f32_island(step) + dt_bias)
            y, _ = ssd_scan(xbc[..., :inner].reshape(b, t, h, p), delta,
                            -jnp.exp(a_log),
                            xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                            xbc[..., inner + g * n:].reshape(b, t, g, n),
                            skip, a.chunk_size)
        with jax.named_scope("out"):
            scale = self.param("norm", _unused, (inner,))
            y = f32_island(y).reshape(b, t, inner) * jax.nn.silu(f32_island(z))
            y = rms(y.reshape(b, t, g, inner // g), a.layer_norm_epsilon)
            y = end_island(y.reshape(b, t, inner) * scale, dt)
            return _dense(self, "out_proj", y, a.hidden_size, dt)


_unused = nn.initializers.zeros  # the parameters are given


def _shared_mamba(a, dtype):
    return Mamba(a.hidden_size, a.mamba_num_heads, a.mamba_head_dim,
                 a.n_groups, a.ssm_state_size, a.conv_kernel, a.chunk_size,
                 a.layer_norm_epsilon,
                 (a.time_step_min, a.time_step_max, a.time_step_floor), dtype)


def _value_and_grad(layer, variables, x):
    loss = lambda p, x: jnp.sum(layer.apply(p, x) ** 2)  # noqa: E731
    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    return fn.lower(variables, x).as_text(), fn(variables, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_shared_mamba_is_nemotrons_mixer_bit_for_bit(dtype):
    """Nemotron-H's mixer through the shared `Mamba` against the mixer as it
    was before the move, on the same weights: the same lowered program, and
    the same value and gradient bit for bit; and the model's unit is the
    shared mixer."""
    x = jax.random.normal(jax.random.key(5), (2, 80, 64))
    shared = _shared_mamba(NEMOTRON_TOY, dtype)
    variables = jax.jit(shared.init)(jax.random.key(6), x)
    text, got = _value_and_grad(shared, variables, x)
    before_text, want = _value_and_grad(_MambaBefore(NEMOTRON_TOY, dtype),
                                        variables, x)
    assert text == before_text
    assert all(bool(jnp.all(g == w)) for g, w in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    unit = nemotron_h._Layer(NEMOTRON_TOY, dtype, "M")
    made = jax.eval_shape(unit.init, jax.random.key(0), x)["params"]
    assert jax.tree.map(lambda v: v.shape, made["mamba"]) == \
        jax.tree.map(lambda v: v.shape, variables["params"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_attention_without_a_scale_lowers_as_before(dtype):
    """`Attention(scale=None)` lowers to the program a scale of
    head_dim^-1/2 gives, which is what the layer computed before it took a
    `scale`; Granite's multiplier lowers to another."""
    x = jax.random.normal(jax.random.key(8), (2, 80, 64))
    layer = Attention(NEMOTRON_TOY, dtype, rotary=False, window=None)
    variables = jax.jit(layer.init)(jax.random.key(9), x)
    text, _ = _value_and_grad(layer, variables, x)
    given = Attention(NEMOTRON_TOY, dtype, rotary=False, window=None,
                      scale=16 ** -0.5)
    assert _value_and_grad(given, variables, x)[0] == text
    granite = Attention(NEMOTRON_TOY, dtype, rotary=False, window=None,
                        scale=0.015625)
    assert _value_and_grad(granite, variables, x)[0] != text


def test_model_input_spec_and_share_validation():
    cut = create_model(ModelConfig(name="granite_4_0_h_micro", num_layers=10,
                                   vocab_size=12544), "bf16")
    arch = cut.arch
    assert arch.layer_types[:10] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (arch.hidden_size, arch.mamba_n_heads, arch.mamba_d_head,
            arch.mamba_n_groups, arch.mamba_d_state, arch.mamba_chunk_size,
            arch.shared_intermediate_size, arch.num_attention_heads,
            arch.num_key_value_heads, arch.head_dim) == \
        (2048, 64, 64, 1, 128, 256, 8192, 32, 8, 64)
    assert (arch.embedding_multiplier, arch.residual_multiplier,
            arch.attention_multiplier, arch.logits_scaling) == \
        (12, 0.22, 0.015625, 8)
    shapes = jax.eval_shape(
        lambda: cut.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # the configuration's count: 9 mamba layers, 1 attention layer, the table
    assert sum(x.size for x in jax.tree.leaves(shapes["params"])) == 772_160_448
    with pytest.raises(ValueError, match="more than the 40 layers"):
        create_model(ModelConfig(name="granite_4_0_h_micro", num_layers=41),
                     "bf16")
    with pytest.raises(ValueError, match="has no experts"):
        create_model(ModelConfig(name="granite_4_0_h_micro", experts_held=8),
                     "bf16")


def test_layers_are_counted_while_the_step_is_traced(monkeypatch):
    """`pva_ssd_scan_sites`: one a Mamba-2 mixer of the traced step (9 in the
    toy's and the cell's ten layers); `pva_attn_xla_sites`: the attention
    layer, 64-wide heads, took the XLA form where the backend would have
    taken the kernels for heads of 128, for its head width; a model without a
    mixture puts out `moe_local_pairs` 0 and no other `moe_*`."""
    from pytorchvideo_accelerate_tpu.obs import get_registry
    from pytorchvideo_accelerate_tpu.ops import ssd
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    def gauges(**kw):
        model = _model(**kw)
        tx = optax.adamw(1e-3)
        mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        state = jax.eval_shape(lambda p: TrainState.create(p, {}, tx),
                               shapes["params"])
        batch = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
        _state, out = jax.eval_shape(make_lm_step(model, tx, mesh), state,
                                     batch, jax.random.key(0))
        registry = get_registry()
        return set(out), [registry.get(g).value() for g in (
            "pva_ssd_scan_sites", "pva_attn_kernel_sites", "pva_attn_xla_sites")]

    keys, sites = gauges()
    assert sites == [9, 0, 0]   # the CPU: no site is a TPU's XLA form
    assert "moe_local_pairs" in keys
    assert not {k for k in keys if k.startswith("moe_") and k != "moe_local_pairs"}
    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    assert gauges(num_layers=5)[1] == [5, 0, 0]   # no attention layer yet
    cell = create_model(ModelConfig(name="granite_4_0_h_micro", num_layers=10,
                                    vocab_size=12544), "bf16")
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    params = jax.eval_shape(lambda t: cell.init(jax.random.key(0), t[:, :8]),
                            tokens)["params"]
    with ssd.count_sites() as scans, attention.count_kernel_sites() as kernels, \
            attention.count_xla_sites() as xla:
        jax.eval_shape(lambda p, t: cell.apply({"params": p}, t), params, tokens)
    assert scans == [((1, 8192, 64, 64), (1, 8192, 1, 128))] * 9
    assert kernels == [] and xla == [((1, 8192, 32, 64), "head_width")]


def test_xla_form_sites_give_their_cause(monkeypatch):
    """`count_xla_sites`: on a TPU backend, heads not a multiple of 128 wide
    take the XLA form for their width, a sequence of one block for its
    length; off the TPU nothing is counted."""
    q = jnp.zeros((1, 256, 4, 64))
    kv = jnp.zeros((1, 256, 2, 64))
    wide = jnp.zeros((1, 256, 2, 128))
    with attention.count_xla_sites() as found:
        attention.causal_gqa_attention(q, kv, kv)
    assert found == []
    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    with attention.count_xla_sites() as found:
        jax.eval_shape(attention.causal_gqa_attention, q, kv, kv)
        jax.eval_shape(attention.causal_gqa_attention, wide, wide, wide)
    assert found == [((1, 256, 4, 64), "head_width"),
                     ((1, 256, 2, 128), "length")]


def test_each_residual_unit_is_rematerialised(params, tokens):
    """A layer is two remat units, the mixer's and the MLP's: over the first
    five layers (all Mamba-2) the gradient's jaxpr holds ten checkpoints
    more than the model without them, two a layer."""
    model = _model(num_layers=5)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]

    def checkpoints(model):
        return str(jax.make_jaxpr(jax.grad(
            lambda p: _program_loss(model, p, tokens)[0]))(shapes)).count(
                "remat2[")

    assert isinstance(model, granite_h.GraniteH) and model.remat
    flat = granite_h.GraniteH(model.arch, dtype=jnp.float32, remat=False)
    assert checkpoints(model) - checkpoints(flat) == 2 * 5

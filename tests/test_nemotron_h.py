"""models/nemotron_h.py through the normal path, against the plain reference
(benchmarks/reference/nemotron_h.py): logits, loss and every leaf's gradient
on seeded weights, the reference's planted faults told apart from the sound
program, the sigmoid router with its selection bias and scale, relu^2
experts of two products, the expert-parallel share tied to the uncut layer,
and the Mamba-2 mixers counted while a step is traced."""

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmarks.reference import nemotron_h as ref
from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
from pytorchvideo_accelerate_tpu.models import create_model, model_task
from pytorchvideo_accelerate_tpu.models import nemotron_h
from pytorchvideo_accelerate_tpu.ops import moe

# the toy of models/__init__.py `nemotron_h_t`, under the reference's keys:
# the published pattern's first six layers, MEMEM*
ARCH = dict(hidden_size=64, num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=1, head_dim=16, mamba_num_heads=4,
            mamba_head_dim=16, n_groups=2, ssm_state_size=16, conv_kernel=4,
            chunk_size=32, time_step_min=0.001, time_step_max=0.1,
            time_step_floor=1e-4, n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
            norm_topk_prob=True, routed_scaling_factor=2.5, vocab_size=256,
            layer_norm_epsilon=1e-5, experts_held=2, expert_offset=0)
T = 150  # no multiple of the chunk (32) or the loss block

# float32 policy: program and reference differ by summation order (the
# chunked scan against the per-token recurrence, grouped products against a
# loop, blocked attention against dense): 1e-5 of the largest entry (read:
# 0 on the loss, 2.6e-6 the worst gradient leaf). bfloat16 policy: every
# projection operand and activation rounded to 8 bits of mantissa through 6
# layers: logits within 6e-2 of their range, loss within 2e-3, a wide leaf's
# gradient NORM within 10%.
POLICIES = [pytest.param("fp32", 1e-5, 1e-6, id="float32_tight"),
            pytest.param("bf16", 6e-2, 2e-3, id="bfloat16_loose")]


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda: ref.init_params(ARCH, 3))()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, T), 0, ARCH["vocab_size"])


def _model(policy="fp32"):
    return create_model(ModelConfig(name="nemotron_h_t", experts_held=2), policy)


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
    """The reference's loss, rows, gradient and logits (float32)."""
    (loss, rows), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_and_rows(p, tokens, ARCH), has_aux=True))(params)
    return loss, rows, grads, jax.jit(lambda p: ref.logits(p, tokens, ARCH))(params)


def test_registry_declares_the_task_and_the_tree_is_the_references(params):
    assert model_task("nemotron_h_t") == model_task(
        "nemotron_3_nano_30b_a3b") == "next_token"
    model = _model()
    made = jax.jit(model.init)(jax.random.key(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    got = {n: x.shape for n, x in _leaves(made).items()}
    assert got == {n: x.shape for n, x in _leaves(params).items()}
    assert [k for k in made if k.startswith("layer_")] == [
        f"layer_{i}" for i in range(6)]
    kinds = ["mamba" if "mamba" in made[f"layer_{i}"] else
             "moe" if "moe" in made[f"layer_{i}"] else "attn" for i in range(6)]
    assert kinds == ["mamba", "moe", "mamba", "moe", "mamba", "attn"]
    # the program's own initialiser: matrices and the embedding N(0, 0.02),
    # the conv PyTorch's U(-1/2, 1/2), A_log = log(1..H), D = 1, dt_bias the
    # softplus inverse of dt in [1e-3, 0.1], the correction bias 0
    for name in ("embed", "lm_head"):
        assert 0.018 < float(jnp.std(made[name])) < 0.022, name
    mamba = made["layer_0"]["mamba"]
    assert float(jnp.abs(mamba["conv"]).max()) <= 0.5
    assert bool(jnp.all(mamba["A_log"] == jnp.log(jnp.arange(1.0, 5.0))))
    assert bool(jnp.all(mamba["D"] == 1.0))
    dt = jax.nn.softplus(mamba["dt_bias"])
    assert bool(jnp.all((dt >= 1e-3 - 1e-9) & (dt <= 0.1 + 1e-9)))
    assert float(jnp.abs(made["layer_1"]["moe"]["e_score_correction_bias"]).max()) == 0


@pytest.mark.parametrize("policy,logit_tol,loss_tol", POLICIES)
def test_logits_and_loss_against_the_reference(params, tokens, want, policy,
                                               logit_tol, loss_tol):
    model = _model(policy)
    want_loss, want_rows, _, want_logits = want
    got = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    assert got.dtype == jnp.float32 and got.shape == (2, T, 256)
    assert float(jnp.abs(got - want_logits).max()) < \
        logit_tol * float(jnp.abs(want_logits).max())
    loss, out = jax.jit(lambda p: _program_loss(model, p, tokens))(params)
    assert abs(float(loss) - float(want_loss)) < loss_tol * float(want_loss)
    if policy == "fp32":   # the same routing, token for token
        assert bool(jnp.all(out["expert_rows"] == want_rows))
    assert out["expert_rows"].shape == (2, 2)   # 2 mixture layers, 2 held


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
def test_every_leafs_gradient_against_the_reference(params, tokens, want,
                                                    policy):
    model = _model(policy)
    got = _leaves(jax.jit(jax.grad(
        lambda p: _program_loss(model, p, tokens)[0]))(params))
    expected = _leaves(want[2])
    assert got.keys() == expected.keys()
    for name, w in expected.items():
        g = got[name]
        if "e_score_correction_bias" in name:   # it chooses, it is not trained
            assert float(jnp.abs(g).max()) == 0.0 == float(jnp.abs(w).max())
        elif policy == "fp32":
            assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max()), name
        elif w.size >= 512:
            norm = float(jnp.linalg.norm(w))
            assert abs(float(jnp.linalg.norm(g)) - norm) <= 0.1 * norm, name


@pytest.fixture(scope="module")
def faulty(tokens):
    """The reference's loss and gradient with a fault switched on by a
    traced vector over `ref.SWITCHED`: one compile serves every fault."""
    return jax.jit(jax.value_and_grad(
        lambda p, flags: ref.loss_and_rows(p, tokens, ARCH, flags=flags)[0]))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_planted_fault_is_told_apart_from_the_sound_program(params, want,
                                                            faulty, fault):
    """Each of the family's faults moves the toy's loss or a gradient leaf by
    far more than the float32 tolerance above (1e-5)."""
    want_loss, _, want_grads, _ = want
    loss, grads = faulty(params, jnp.array([name == fault
                                            for name in ref.SWITCHED]))
    expected = _leaves(want_grads)
    moved = max(float(jnp.abs(g - expected[n]).max())
                / max(float(jnp.abs(expected[n]).max()), 1e-30)
                for n, g in _leaves(grads).items())
    moved = max(moved, abs(float(loss) - float(want_loss)) / float(want_loss))
    assert moved > 100 * 1e-5, (fault, moved)


def test_sigmoid_route_is_the_written_formula():
    """s = sigmoid(x W); the top-k of s + b choose; w = scale * s / (sum s +
    1e-20). A bias moves the choice and never the weights of what is chosen."""
    k = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(k[0], (64, 16))
    router = jax.random.normal(k[1], (16, 8))
    bias = 0.3 * jax.random.normal(k[2], (8,))
    w, e = moe.route(x, router, 3, True, score="sigmoid", selection_bias=bias,
                     scale=2.5)
    s = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    want_e = jnp.argsort(-(s + bias), axis=-1)[:, :3]
    assert bool(jnp.all(jnp.sort(e, axis=-1) == jnp.sort(want_e, axis=-1)))
    picked = jnp.take_along_axis(s, e, axis=-1)
    assert float(jnp.abs(w - 2.5 * picked / (picked.sum(-1, keepdims=True)
                                             + 1e-20)).max()) < 1e-6
    assert float(jnp.abs(w.sum(-1) - 2.5).max()) < 1e-5
    # the bias moves the choice of some tokens
    _, unbiased = moe.route(x, router, 3, True, score="sigmoid", scale=2.5)
    assert bool(jnp.any(jnp.sort(unbiased, -1) != jnp.sort(e, -1)))
    # where the choice is the same, so are the weights: the bias weighs nothing
    same = jnp.all(jnp.sort(unbiased, -1) == jnp.sort(e, -1), axis=-1)
    w0, _ = moe.route(x, router, 3, True, score="sigmoid",
                      selection_bias=jnp.zeros((8,)), scale=2.5)
    w1, _ = moe.route(x, router, 3, True, score="sigmoid", scale=2.5)
    assert float(jnp.abs(w0 - w1).max()) == 0.0
    assert bool(jnp.any(same))
    # the reference's routing is the same
    w_ref, e_ref = ref.routing({"router": router, "e_score_correction_bias": bias},
                               x, {**ARCH, "n_routed_experts": 8,
                                   "num_experts_per_tok": 3})
    assert bool(jnp.all(e == e_ref)) and float(jnp.abs(w - w_ref).max()) < 1e-6
    with pytest.raises(ValueError, match="score"):
        moe.route(x, router, 3, score="tanh")


def test_two_product_relu2_experts_against_a_dense_loop():
    """`expert_share` with no gate: act(x U_e) V_e, act relu^2, each token's
    chosen held experts weighted, against a plain loop over the experts."""
    k = jax.random.split(jax.random.key(3), 4)
    n, d, f, held = 96, 16, 8, 3
    x = jax.random.normal(k[0], (n, d))
    w_up = 0.3 * jax.random.normal(k[1], (held, d, f))
    w_down = 0.3 * jax.random.normal(k[2], (held, f, d))
    weights, experts = moe.route(x, jax.random.normal(k[3], (d, 8)), 2,
                                 score="sigmoid", scale=2.5)
    y, rows, _ = jax.jit(lambda *a: moe.expert_share(
        *a, None, w_up, w_down, 2, 8, activation=nemotron_h.relu_squared))(
            x, weights, experts)
    want = jnp.zeros_like(x)
    for j in range(held):
        w_e = jnp.sum(jnp.where(experts == 2 + j, weights, 0.0), axis=-1)
        out = jnp.square(jax.nn.relu(x @ w_up[j])) @ w_down[j]
        want = want + out * w_e[:, None]
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert rows.tolist() == [int(jnp.sum(experts == 2 + j)) for j in range(held)]


@pytest.mark.parametrize("shares", [4, 2], ids=lambda s: f"{s}_shares")
def test_shares_add_up_to_the_uncut_reference_layer(shares):
    """The model-configs guide's share test: the routed parts of `shares`
    shares of 8 / `shares` experts each, plus the shared expert once, equal
    the uncut reference layer's output."""
    arch = nemotron_h.NemotronHArch(**{
        k: v for k, v in ARCH.items() if k not in ("conv_kernel",)})
    x = jax.random.normal(jax.random.key(4), (2, 40, 64))
    whole = ref.init_params({**ARCH, "experts_held": 8,
                             "hybrid_override_pattern": "E",
                             "num_hidden_layers": 1}, 5)["layer_0"]["moe"]
    whole = {**whole, "e_score_correction_bias": 0.05 * jnp.arange(8.0)}
    per = 8 // shares
    total, shared = 0.0, None
    for s in range(shares):
        cut = {**whole, "w_up": whole["w_up"][s * per:(s + 1) * per],
               "w_down": whole["w_down"][s * per:(s + 1) * per]}
        layer = nemotron_h.Mixture(
            nemotron_h.NemotronHArch(**{**arch.__dict__, "experts_held": per,
                                        "expert_offset": s * per}),
            jnp.float32)
        y, _ = jax.jit(layer.apply)({"params": cut}, x)
        total = total + y
        flat = x.reshape(-1, 64)
        shared = (jnp.square(jax.nn.relu(flat @ whole["shared_up_proj"]))
                  @ whole["shared_down_proj"]).reshape(x.shape)
    routed = total - shares * shared + shared          # the shared part once
    want, _ = jax.jit(lambda w, x: ref.mixture(
        w, x, {**ARCH, "experts_held": 8}, None, False, False,
        ref.switches()))(whole, x.reshape(-1, 64))
    assert float(jnp.abs(routed - want.reshape(x.shape)).max()) < \
        1e-5 * float(jnp.abs(want).max())


def test_model_input_spec_and_share_validation():
    cut = create_model(ModelConfig(name="nemotron_3_nano_30b_a3b", num_layers=9,
                                   vocab_size=16384, experts_held=8), "bf16")
    arch = cut.arch
    assert arch.hybrid_override_pattern[:arch.num_hidden_layers] == "MEMEM*EME"
    assert (arch.hidden_size, arch.mamba_num_heads, arch.mamba_head_dim,
            arch.n_groups, arch.ssm_state_size, arch.n_routed_experts,
            arch.num_experts_per_tok, arch.moe_intermediate_size,
            arch.moe_shared_expert_intermediate_size, arch.num_attention_heads,
            arch.num_key_value_heads, arch.head_dim) == \
        (2688, 64, 64, 8, 128, 128, 6, 1856, 3712, 32, 2, 128)
    shapes = jax.eval_shape(
        lambda: cut.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # the configuration's count: 4 M, 4 E of 8 held experts, 1 *, embedding and head
    assert sum(x.size for x in jax.tree.leaves(shapes["params"])) == 666_963_456
    with pytest.raises(ValueError, match="more than the 52 layers"):
        create_model(ModelConfig(name="nemotron_3_nano_30b_a3b", num_layers=53),
                     "bf16")
    with pytest.raises(ValueError, match="not among the model's 128"):
        create_model(ModelConfig(name="nemotron_3_nano_30b_a3b", experts_held=8,
                                 expert_offset=121), "bf16")


def test_mixers_are_counted_while_the_step_is_traced():
    """`pva_ssd_scan_sites`: one a Mamba-2 mixer of the traced step (3 in the
    toy, 4 in the cell's nine layers); a model without a mixture puts out
    `moe_local_pairs` 0 and no other `moe_*`."""
    from pytorchvideo_accelerate_tpu.obs import get_registry
    from pytorchvideo_accelerate_tpu.ops import ssd
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    def metrics(name, **kw):
        model = create_model(ModelConfig(name=name, **kw), "fp32")
        tx = optax.adamw(1e-3)
        mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        state = jax.eval_shape(lambda p: TrainState.create(p, {}, tx),
                               shapes["params"])
        batch = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
        _state, out = jax.eval_shape(make_lm_step(model, tx, mesh), state,
                                     batch, jax.random.key(0))
        return set(out), get_registry().get("pva_ssd_scan_sites").value()

    keys, sites = metrics("nemotron_h_t", experts_held=2)
    assert sites == 3 and "moe_expert_load_max_over_mean" in keys
    keys, sites = metrics("nemotron_h_t", num_layers=1)      # "M" alone
    assert sites == 1 and "moe_local_pairs" in keys
    assert not {k for k in keys if k.startswith("moe_") and k != "moe_local_pairs"}
    keys, sites = metrics("qwen3_next_t", experts_held=2)
    assert sites == 0
    cell = create_model(ModelConfig(name="nemotron_3_nano_30b_a3b", num_layers=9,
                                    vocab_size=16384, experts_held=8), "bf16")
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    params = jax.eval_shape(lambda t: cell.init(jax.random.key(0), t[:, :8]),
                            tokens)["params"]
    with ssd.count_sites() as found:
        jax.eval_shape(lambda p, t: cell.apply({"params": p}, t), params, tokens)
    assert found == [((2, 8192, 64, 64), (2, 8192, 8, 128))] * 4


@pytest.mark.parametrize("forced", [True, False], ids=["kernel", "xla_form"])
def test_attention_layer_keeps_the_flash_forwards_results(monkeypatch, forced):
    """Heads 128 wide over 1024 tokens, the kernels taken as on a TPU: the
    one attention layer of MEMEM* runs the forward kernel once and keeps its
    `o` and `lse` for the backward pass; on the CPU's own rule the step is a
    plain `nn.remat`'s."""
    from kept_attention import check_units_keep_the_forward

    def model():
        return nemotron_h.NemotronH(nemotron_h.NemotronHArch(
            hidden_size=64, num_hidden_layers=6, num_attention_heads=16,
            num_key_value_heads=1, head_dim=128, mamba_num_heads=4,
            mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=128,
            n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=48, vocab_size=256,
            experts_held=2), dtype=jnp.float32)

    check_units_keep_the_forward(monkeypatch, nemotron_h, model, forced, 1)

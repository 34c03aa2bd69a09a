"""models/ouro.py through the normal path, against the plain reference
(benchmarks/reference/ouro.py): logits, loss, every leaf's gradient, three
`fit()` steps fed by `SyntheticTokenSource`, the reference's planted faults
told apart from the sound program, and the loop tied to the model: one set of
parameters whose gradient is the sum over the passes."""

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmarks.reference import ouro as ref
from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
from pytorchvideo_accelerate_tpu.models import (
    create_model,
    model_input_spec,
    model_task,
)
from pytorchvideo_accelerate_tpu.models import lm_common, ouro

# the toy of models/__init__.py `ouro_t`, under the reference's keys: two
# layers run three times
ARCH = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, intermediate_size=96,
            rope_theta=1e6, rms_norm_eps=1e-6, vocab_size=256,
            total_ut_steps=3, exit_entropy_beta=0.1)
T = 150  # no multiple of the loss block

# float32 policy: program and reference differ by summation order (blocked
# attention against dense, the loss's blocks, log-space exit products against
# plain ones): 1e-5 of the largest entry (read: 6e-7 logits, 2e-6 the worst
# gradient leaf). bfloat16 policy: every projection operand and activation is
# rounded to 8 bits of mantissa (2^-8 = 4e-3 relative) through 2 x 3 layer
# executions, each sub-layer's output normed to unit size again: logits within
# 6e-2 of their range (read: 1.5e-2), loss within 2e-3 (read: 1e-5), a leaf's
# gradient NORM within 10%.
POLICIES = [pytest.param("fp32", 1e-5, 1e-6, id="float32_tight"),
            pytest.param("bf16", 6e-2, 2e-3, id="bfloat16_loose")]
FAULTS = ("one_pass", "norm_once", "pre_norm_only", "gate_ignored",
          "entropy_dropped", "last_loss_only")


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda: ref.init_params(ARCH, 3))()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, T), 0, ARCH["vocab_size"])


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's last pass's logits, loss, by-pass means and gradient
    (float32)."""
    logits = jax.jit(lambda p: ref.logits(p, tokens, ARCH))(params)
    (loss, ut), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_and_ut(p, tokens, ARCH), has_aux=True))(params)
    return logits, loss, ut, grads


def _model(policy="fp32"):
    return create_model(ModelConfig(name="ouro_t"), policy)


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


def test_registry_declares_the_task_and_the_tree_is_the_references(params):
    assert model_task("ouro_t") == model_task("ouro_2_6b") == "next_token"
    model = _model()
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    got = {n: x.shape for n, x in _leaves(shapes["params"]).items()}
    assert got == {n: x.shape for n, x in _leaves(params).items()}
    # ONE set of layer parameters, whatever the number of passes: 2 layers'
    # leaves (4 norms, 4 attention and 3 MLP matrices each), not 6
    assert sum(n.startswith("['stack']['layer_") for n in got) == 2 * 11
    assert "['stack']['layer_1']['attn']['k_proj']" in got
    assert "['stack']['layer_0']['mlp_out_norm']['scale']" in got
    assert got["['exit_gate']"] == (64,) and got["['exit_bias']"] == ()
    # the program's own initialiser: every matrix N(0, 0.02), the embedding
    # too (the benchmark's unit-variance embedding is its reference's)
    made = jax.jit(model.init)(jax.random.key(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    for name in ("embed", "lm_head"):
        assert 0.018 < float(jnp.std(made[name])) < 0.022, name
    assert float(made["exit_bias"]) == 0.0


@pytest.mark.parametrize("policy,logit_tol,loss_tol", POLICIES)
def test_logits_and_loss_against_the_reference(params, tokens, want, policy,
                                               logit_tol, loss_tol):
    model = _model(policy)
    want, want_loss, want_ut, _ = want            # the LAST pass's logits
    # (the reference's scan over the passes is its Python loop over them)
    assert float(jnp.abs(jax.jit(lambda p: ref.logits(
        p, tokens, ARCH, remat=False))(params) - want).max()) < \
        1e-5 * float(jnp.abs(want).max())
    got = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    assert got.dtype == jnp.float32 and got.shape == (2, T, 256)
    assert float(jnp.abs(got - want).max()) < logit_tol * float(jnp.abs(want).max())
    loss, out = jax.jit(lambda p: _program_loss(model, p, tokens))(params)
    assert abs(float(loss) - float(want_loss)) < loss_tol * float(want_loss)
    for name in ("exit_mass", "loss"):            # by pass, what the logger gets
        got_ut = out["ut"][name] / out["count"]
        assert float(jnp.abs(got_ut - want_ut[name]).max()) < \
            max(loss_tol, 1e-3 * (policy == "bf16")) * float(want_ut[name].max())
    assert "expert_rows" not in out


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
def test_every_leafs_gradient_against_the_reference(params, tokens, want,
                                                    policy):
    model = _model(policy)
    got = _leaves(jax.jit(jax.grad(
        lambda p: _program_loss(model, p, tokens)[0]))(params))
    want = _leaves(want[3])
    assert got.keys() == want.keys() and len(want) == 2 * 11 + 5
    for name, w in want.items():
        g = got[name]
        if policy == "fp32":
            assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max()), name
        elif w.size >= 512:
            norm = float(jnp.linalg.norm(w))
            assert abs(float(jnp.linalg.norm(g)) - norm) <= 0.1 * norm, name


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_told_apart_from_the_sound_program(params, tokens,
                                                            want, fault):
    """Each of the loop's faults moves the toy's loss or a gradient leaf by
    far more than the float32 tolerance above (1e-5), so the comparison can
    tell it from the sound program (read: 1.0 to 3.2 of a leaf's largest
    entry)."""
    _, want_loss, _, want_grads = want
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_and_ut(p, tokens, ARCH, fault=fault)[0]))(params)
    want = _leaves(want_grads)
    moved = max(float(jnp.abs(g - want[n]).max()) / float(jnp.abs(want[n]).max())
                for n, g in _leaves(grads).items())
    moved = max(moved, abs(float(loss) - float(want_loss)) / float(want_loss))
    assert moved > 100 * 1e-5, (fault, moved)


def test_shared_leafs_gradient_is_the_sum_over_the_passes(params, tokens):
    """The model applies ONE set of layer parameters `total_ut_steps` times.
    A copy with a set of its own for every pass (the model's own `_Stack`,
    head and loss, driven by hand) gives the same loss, and its per-pass
    gradients add up to the model's gradient of the shared leaves."""
    model = _model()
    arch = model.arch
    targets, weights = _scored(tokens)

    def untied(stacks, rest):
        x = jnp.take(rest["embed"], tokens, axis=0)
        hidden = []
        for stack in stacks:
            x, _ = ouro._Stack(arch, jnp.float32, True).apply({"params": stack}, x)
            hidden.append(x)
        out = lm_common.looped_lm_outputs(
            jnp.stack(hidden), rest["lm_head"], rest["exit_gate"],
            rest["exit_bias"], targets, weights, arch.exit_entropy_beta, 64)
        return out["loss_sum"] / out["count"]

    rest = {k: v for k, v in params.items() if k != "stack"}
    copies = [params["stack"]] * arch.total_ut_steps
    loss, (per_pass, _) = jax.jit(jax.value_and_grad(
        untied, argnums=(0, 1)))(copies, rest)
    tied_loss, tied = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, tokens)[0]))(params)
    assert abs(float(loss) - float(tied_loss)) < 1e-6 * float(tied_loss)
    assert len(per_pass) == 3
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    for name, want in _leaves(tied["stack"]).items():
        got = _leaves(summed)[name]
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(jnp.abs(want).max()), name
        # and no single pass's gradient is the whole of it
        one = _leaves(per_pass[0])[name]
        assert float(jnp.abs(one - want).max()) > 1e-2 * float(jnp.abs(want).max()), name


def test_one_pass_without_entropy_is_a_plain_decoders_loss(params, tokens):
    arch = ouro.OuroArch(**{**ARCH, "total_ut_steps": 1, "exit_entropy_beta": 0.0})
    model = ouro.Ouro(arch, dtype=jnp.float32)
    loss, out = jax.jit(lambda p: _program_loss(model, p, tokens))(params)
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    targets, weights = _scored(tokens)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    plain = (ce * weights).sum() / weights.sum()
    assert abs(float(loss) - float(plain)) < 1e-6 * float(plain)
    assert float(out["ut"]["exit_mass"][0]) == pytest.approx(float(out["count"]))
    # lm_common's one-pass loss, which the other families train on, agrees
    hidden = jax.jit(lambda p: ref.trunk(
        p, tokens, {**ARCH, "total_ut_steps": 1})[0])(params)
    summed, _hits = lm_common.next_token_loss(
        hidden.reshape(-1, 64), params["lm_head"], targets.reshape(-1),
        weights.reshape(-1), 64)
    assert abs(float(summed / weights.sum()) - float(plain)) < 1e-5 * float(plain)


def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    key = jax.random.key(5)
    hidden = jax.random.normal(key, (4, 24, 16))
    head = 0.1 * jax.random.normal(jax.random.key(6), (16, 32))
    gate = jax.random.normal(jax.random.key(7), (16,))
    targets = jnp.arange(24) % 32
    weight = jnp.ones((24,)).at[-3:].set(0.0)

    def run(bias, beta=0.1):
        return lm_common.exit_weighted_loss(hidden, head, gate, bias, targets,
                                            weight, beta, 8)

    out = run(0.3)
    assert float(out["ut"]["exit_mass"].sum()) == pytest.approx(21.0, rel=1e-6)
    assert bool(jnp.all(out["ut"]["exit_mass"] > 0))
    # against the equations written out plainly (the reference's exit_pdf)
    z = jnp.einsum("pnd,d->pn", hidden, gate) + 0.3
    p = ref.exit_pdf(z)
    assert float(jnp.abs(p.sum(axis=0) - 1.0).max()) < 1e-6
    lam = jax.nn.sigmoid(z)
    assert jnp.allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), atol=1e-7)
    assert jnp.allclose(out["ut"]["exit_mass"], (p * weight).sum(axis=1), rtol=1e-5)
    # a gate that never fires leaves everything to the last pass, whose
    # cross-entropy is then the whole loss; one that always fires, to the first
    never, always = run(-60.0), run(60.0)
    assert jnp.allclose(never["ut"]["exit_mass"], jnp.array([0, 0, 0, 21.0]), atol=1e-5)
    assert float(never["loss_sum"]) == pytest.approx(float(never["ut"]["loss"][3]), rel=1e-5)
    assert jnp.allclose(always["ut"]["exit_mass"], jnp.array([21.0, 0, 0, 0]), atol=1e-5)
    assert float(always["loss_sum"]) == pytest.approx(float(always["ut"]["loss"][0]), rel=1e-5)
    # the entropy term: beta 0 is the exit-weighted cross-entropy alone
    entropy = -(p * jnp.log(p)).sum(axis=0)
    assert float(run(0.3, beta=0.0)["loss_sum"] - out["loss_sum"]) == \
        pytest.approx(0.1 * float((entropy * weight).sum()), rel=1e-4)


def _step_metric_keys(name, **model_kw):
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    model = create_model(ModelConfig(name=name, **model_kw), "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=len(jax.devices())))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    state = jax.eval_shape(lambda p: TrainState.create(p, {}, tx),
                           shapes["params"])
    batch = {"tokens": jax.ShapeDtypeStruct((len(jax.devices()), 64), jnp.int32)}
    _state, metrics = jax.eval_shape(make_lm_step(model, tx, mesh), state,
                                     batch, jax.random.key(0))
    return set(metrics)


COMMON = {"loss", "grad_norm", "accuracy", "tokens", "moe_local_pairs"}
MOE = {"moe_expert_rows_max", "moe_expert_rows_mean", "moe_local_pair_share",
       "moe_expert_load_max_over_mean", "moe_tight_buffer_share"}


@pytest.mark.parametrize("name,kw,want", [
    ("ouro_t", {}, COMMON | {"ut_expected_steps"}
     | {f"ut_{k}_{t}" for k in ("exit_mass", "loss") for t in (1, 2, 3)}),
    ("qwen3_next_t", {"experts_held": 2}, COMMON | MOE),
    ("smallthinker_t", {"experts_held": 2}, COMMON | MOE),
])
def test_step_outputs_by_family(name, kw, want):
    """A model without experts puts out `moe_local_pairs` 0 and none of the
    other `moe_*`; a looped one its `ut_*`; the mixture families' outputs are
    what they were."""
    from pytorchvideo_accelerate_tpu.obs import get_registry
    from pytorchvideo_accelerate_tpu.trainer.steps import lm_log_values

    assert _step_metric_keys(name, **kw) == want
    # the gauge set while the step is traced: passes of the layer stack
    assert get_registry().get("pva_ut_steps").value() == (3 if name == "ouro_t" else 1)
    logged = lm_log_values({k: 0.0 for k in want})
    assert {k for k in logged if k.startswith("ut_")} == \
        {k for k in want if k.startswith("ut_")}
    assert "obs/moe_local_pair_share" in logged or name == "ouro_t"


_FIT_DRIVER = """
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from benchmarks.reference import ouro as ref
from pytorchvideo_accelerate_tpu.config import config_from_dict
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

arch = {arch!r}
cfg = config_from_dict({{
    "model": {{"name": "ouro_t"}},
    "data": {{"synthetic": True, "seq_len": 96, "batch_size": 2,
              "synthetic_num_videos": 6, "num_workers": 2,
              "limit_val_batches": 0}},
    "optim": {{"optimizer": "adamw", "lr": 3e-3, "weight_decay": 0.1,
               "grad_clip_norm": 1.0, "schedule": "cosine", "num_epochs": 1}},
    "mixed_precision": "fp32", "seed": 11,
    "checkpoint": {{"output_dir": {out!r}}},
    "tracking": {{"with_tracking": True, "trackers": "jsonl",
                  "logging_dir": {out!r} + "/runs", "log_every": 1}}}})
trainer = Trainer(cfg)
params0 = jax.jit(lambda: ref.init_params(arch, 4))()
trainer.state = trainer.state.replace(params=jax.tree.map(jnp.copy, params0))
batches = [{{"tokens": jnp.asarray(b["tokens"])}}
           for b in trainer.train_loader.epoch(0)]
trainer.train_loader.state = type(trainer.train_loader.state)()
logged = []


class Tracker:
    name = "t"
    def start(self, *a): pass
    def log(self, values, step): logged.append((step, dict(values)))
    def finish(self): pass


trainer.trackers.trackers = trainer.trackers.trackers + [Tracker()]
fit = trainer.fit()
optim = {{"lr": 3e-3, "weight_decay": 0.1, "grad_clip_norm": 1.0,
          "total_steps": 3}}
want = ref.follow(arch, optim, params0, batches)
ut = [jax.device_get(jax.jit(lambda p, t: ref.loss_and_ut(p, t, arch)[1])(
    params0, batches[0]["tokens"]))]
print(json.dumps({{"task": trainer.task, "steps": fit["steps"], "logged": logged,
                   "losses": want["losses"], "pairs": want["pairs"],
                   "exit_mass": [float(x) for x in ut[0]["exit_mass"]],
                   "ut_loss": [float(x) for x in ut[0]["loss"]]}}))
"""


def test_three_fit_steps_reproduce_the_references_losses(tmp_path):
    """`Trainer.fit()` with the loader, the prefetcher, the deferred logger
    and AdamW as `build_optimizer` builds it, against the reference's AdamW
    written out, on the same batches from the same weights, in a process of
    its own (one CPU device, as a one-chip run has). float32: 1e-5."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, "-c", _FIT_DRIVER.format(root=root, arch=ARCH,
                                                  out=str(tmp_path))],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["task"] == "next_token" and got["steps"] == 3
    assert got["pairs"] == [0, 0, 0]
    steps = {s: v for s, v in got["logged"] if "train_loss_step" in v}
    for i, loss in enumerate(got["losses"], 1):
        assert abs(steps[i]["train_loss_step"] - loss) < 1e-5 * loss, i
        # the step's own counters ride the same deferred fetch: no pair was
        # computed, and none of the mixture's ratios is there
        assert steps[i]["moe_local_pairs"] == 0
        assert steps[i]["tokens"] == 2 * 96
        assert not [k for k in steps[i] if "moe_" in k and k != "moe_local_pairs"]
        mass = [steps[i][f"ut_exit_mass_{t}"] for t in (1, 2, 3)]
        assert sum(mass) == pytest.approx(1.0, abs=1e-5)
        assert steps[i]["ut_expected_steps"] == pytest.approx(
            mass[0] + 2 * mass[1] + 3 * mass[2], rel=1e-5)
    # the first step's by-pass means are the reference's at the first weights
    for t in (1, 2, 3):
        assert steps[1][f"ut_exit_mass_{t}"] == pytest.approx(
            got["exit_mass"][t - 1], rel=1e-4)
        assert steps[1][f"ut_loss_{t}"] == pytest.approx(
            got["ut_loss"][t - 1], rel=1e-5)
    # the trace-time gauges, once: three passes; the flash kernels are taken
    # on a TPU at heads of 128 (one site a LAYER there: the passes are one
    # scan), not here
    once = lambda k: [v[k] for _s, v in got["logged"] if k in v]  # noqa: E731
    assert once("obs/ut_steps") == [3]
    assert once("obs/attn_kernel_sites") == [0]
    assert once("obs/attn_kept_sites") == [0]
    assert once("obs/attn_window_sites") == [0]


def test_model_input_spec_and_share_validation():
    from pytorchvideo_accelerate_tpu.config import DataConfig

    spec = model_input_spec(ModelConfig(name="ouro_t"), DataConfig(seq_len=77))
    assert spec == {"tokens": (1, 77)}
    with pytest.raises(ValueError, match="has no experts"):
        create_model(ModelConfig(name="ouro_2_6b", experts_held=2), "fp32")
    with pytest.raises(ValueError, match="has no experts"):
        create_model(ModelConfig(name="ouro_t", expert_offset=1), "fp32")
    full = create_model(ModelConfig(name="ouro_2_6b"), "bf16").arch
    assert (full.num_hidden_layers, full.hidden_size, full.intermediate_size,
            full.num_attention_heads, full.num_key_value_heads, full.head_dim,
            full.vocab_size, full.total_ut_steps, full.rope_theta) == \
        (48, 2048, 5632, 16, 16, 128, 49152, 4, 1e6)
    cut = create_model(ModelConfig(name="ouro_2_6b", num_layers=8), "bf16")
    assert (cut.arch.num_hidden_layers, cut.arch.vocab_size) == (8, 49152)
    shapes = jax.eval_shape(
        lambda: cut.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # 8 layers' leaves, not 32: the issue's 612,438,017
    assert sum(x.size for x in jax.tree.leaves(shapes["params"])) == 612_438_017


def test_kernel_sites_are_counted_a_layer(params, tokens, monkeypatch):
    """The passes are one scan over the stack: the traced step holds one
    attention call a LAYER whatever the number of passes (2 in the toy, 8 in
    the cell), which is what `obs/attn_kernel_sites` counts where the flash
    kernels are taken."""
    from pytorchvideo_accelerate_tpu.ops import attention

    model = _model()
    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    monkeypatch.setattr(attention, "kernel_shapes", lambda t, d: True)
    monkeypatch.setattr(
        attention.pallas_attention, "causal_flash_attention",
        lambda q, k, v, scale, window, interpret: q)
    with attention.count_kernel_sites() as sites:
        jax.eval_shape(lambda: model.apply({"params": params}, tokens))
    assert len(sites) == 2 and {w for _s, w, _k in sites} == {None}


@pytest.mark.parametrize("forced", [True, False], ids=["kernel", "xla_form"])
def test_layers_keep_the_flash_forwards_results(monkeypatch, forced):
    """Heads 128 wide over 1024 tokens, the kernels taken as on a TPU: the
    scan's body holds one forward kernel a layer, kept for the backward pass
    of every pass (the parent's plain `nn.remat` ran it again in the
    transposed scan); on the CPU's own rule the step is a plain
    `nn.remat`'s."""
    from kept_attention import check_units_keep_the_forward

    def model():
        return ouro.Ouro(ouro.OuroArch(
            hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, head_dim=128, intermediate_size=96,
            vocab_size=256, total_ut_steps=3), dtype=jnp.float32)

    check_units_keep_the_forward(monkeypatch, ouro, model, forced, 2)

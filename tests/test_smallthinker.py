"""models/smallthinker.py through the normal path, against the plain reference
(benchmarks/reference/smallthinker.py): logits, loss, every leaf's gradient,
three `fit()` steps fed by `SyntheticTokenSource`, and the reference's planted
faults told apart from the sound program."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.reference import smallthinker as ref
from pytorchvideo_accelerate_tpu.config import ModelConfig
from pytorchvideo_accelerate_tpu.models import (
    create_model,
    model_input_spec,
    model_task,
)

# the toy of models/__init__.py `smallthinker_t`, under the reference's keys:
# 7 query heads on 1 key-value head (the odd group), window 32
ARCH = dict(hidden_size=64, num_hidden_layers=4, num_attention_heads=7,
            num_key_value_heads=1, head_dim=16, rope_theta=1.5e6,
            sliding_window_size=32, rope_layout=[0, 1, 1, 1],
            sliding_window_layout=[0, 1, 1, 1], moe_num_primary_experts=8,
            moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
            norm_topk_prob=True, rms_norm_eps=1e-6, vocab_size=256,
            experts_held=2, expert_offset=2)
T = 150  # past the window, and no multiple of it

# float32 policy: program and reference differ by summation order (sliced key
# ranges against masked ones, grouped against masked expert products): 1e-5
# of the largest entry (read: 4e-7 logits, 8e-7 the worst gradient leaf).
# bfloat16 policy: every projection operand and activation is rounded to 8
# bits of mantissa (2^-8 = 4e-3 relative) through 4 layers, and a token near a
# tie of the router goes to another expert (its hidden state then moves by a
# whole expert's output): logits within 6e-2 of their range (read: 2.0e-2),
# loss within 2e-3, a leaf's gradient NORM within 10%.
POLICIES = [pytest.param("fp32", 1e-5, 1e-6, id="float32_tight"),
            pytest.param("bf16", 6e-2, 2e-3, id="bfloat16_loose")]
FAULTS = ("window_ignored", "rope_everywhere", "router_after_attention",
          "silu_experts")


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda: ref.init_params(ARCH, 3))()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, T), 0, ARCH["vocab_size"])


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits, loss, rows and gradient (float32)."""
    logits = jax.jit(lambda p: ref.logits(p, tokens, ARCH))(params)
    (loss, rows), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_and_rows(p, tokens, ARCH), has_aux=True))(params)
    return logits, loss, rows, grads


def _model(policy):
    return create_model(ModelConfig(name="smallthinker_t", experts_held=2,
                                    expert_offset=2), policy)


def _program_loss(model, params, tokens):
    targets = jnp.roll(tokens, -1, axis=1)
    weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    out = model.apply({"params": params}, tokens, targets=targets,
                      weights=weights, train=True)
    return out["loss_sum"] / out["count"], out["expert_rows"]


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_registry_declares_the_task_and_the_tree_is_the_references(params):
    assert model_task("smallthinker_t") == model_task("smallthinker_21b_a3b") \
        == "next_token"
    model = _model("fp32")
    assert model.arch.period == 4
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    got = {n: x.shape for n, x in _leaves(shapes["params"]).items()}
    assert got == {n: x.shape for n, x in _leaves(params).items()}
    # the full layer under `attn`, the windowed under `swa`, the router in
    # the mixer, beside the attention whose input it reads
    assert "['mixer_0']['attn']['k_proj']" in got
    assert "['mixer_1']['swa']['k_proj']" in got
    assert "['mixer_3']['moe']['router']" in got
    assert "['mixture_3']['moe']['w_gate']" in got
    # the program's own initialiser: every matrix N(0, 0.02), the embedding
    # too (the benchmark's unit-variance embedding is its reference's)
    made = jax.jit(model.init)(jax.random.key(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    for name in ("embed", "lm_head"):
        assert 0.018 < float(jnp.std(made[name])) < 0.022, name


@pytest.mark.parametrize("policy,logit_tol,loss_tol", POLICIES)
def test_logits_and_loss_against_the_reference(params, tokens, want, policy,
                                               logit_tol, loss_tol):
    model = _model(policy)
    want, want_loss, want_rows, _ = want
    got = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    assert got.dtype == jnp.float32 and got.shape == (2, T, 256)
    assert float(jnp.abs(got - want).max()) < logit_tol * float(jnp.abs(want).max())
    loss, rows = jax.jit(lambda p: _program_loss(model, p, tokens))(params)
    assert abs(float(loss) - float(want_loss)) < loss_tol * float(want_loss)
    if policy == "fp32":
        assert bool(jnp.all(rows == want_rows))  # the same routing, pair by pair
    else:
        assert abs(int(rows.sum()) - int(want_rows.sum())) <= 0.05 * int(want_rows.sum())


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
def test_every_leafs_gradient_against_the_reference(params, tokens, want,
                                                    policy):
    model = _model(policy)
    got = _leaves(jax.jit(jax.grad(
        lambda p: _program_loss(model, p, tokens)[0]))(params))
    want = _leaves(want[3])
    assert got.keys() == want.keys() and len(want) == 43
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
    """Each of the family's faults moves the toy's loss or a gradient leaf by
    far more than the float32 tolerance above (1e-5), so the comparison can
    tell it from the sound program (read: 0.8 to 1.4 of a leaf's largest
    entry)."""
    _, want_loss, _, want_grads = want
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_and_rows(p, tokens, ARCH, fault=fault),
        has_aux=True))(params)
    want = _leaves(want_grads)
    moved = max(float(jnp.abs(g - want[n]).max()) / float(jnp.abs(want[n]).max())
                for n, g in _leaves(grads).items())
    moved = max(moved, abs(float(loss) - float(want_loss)) / float(want_loss))
    assert moved > 100 * 1e-5, (fault, moved)


_FIT_DRIVER = """
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from benchmarks.reference import smallthinker as ref
from pytorchvideo_accelerate_tpu.config import config_from_dict
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

arch = {arch!r}
cfg = config_from_dict({{
    "model": {{"name": "smallthinker_t", "experts_held": 2, "expert_offset": 2}},
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
print(json.dumps({{"task": trainer.task, "steps": fit["steps"], "logged": logged,
                   "losses": want["losses"], "pairs": want["pairs"]}}))
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
    steps = {s: v for s, v in got["logged"] if "train_loss_step" in v}
    for i, loss in enumerate(got["losses"], 1):
        assert abs(steps[i]["train_loss_step"] - loss) < 1e-5 * loss, i
        # the step's own counters ride the same deferred fetch
        assert steps[i]["moe_local_pairs"] == got["pairs"][i - 1]
        assert steps[i]["tokens"] == 2 * 96
        assert 0.3 < steps[i]["obs/moe_local_pair_share"] < 0.7  # 2/8 x 2
    # the trace-time gauge, once: three layers lowered under the band
    sites = [v["obs/attn_window_sites"] for _s, v in got["logged"]
             if "obs/attn_window_sites" in v]
    assert sites == [3]
    # and none took the flash kernels: not on the CPU, not at 16-wide heads
    assert [v["obs/attn_kernel_sites"] for _s, v in got["logged"]
            if "obs/attn_kernel_sites" in v] == [0]
    assert [v["obs/attn_kept_sites"] for _s, v in got["logged"]
            if "obs/attn_kept_sites" in v] == [0]


def test_model_input_spec_and_share_validation():
    from pytorchvideo_accelerate_tpu.config import DataConfig

    spec = model_input_spec(ModelConfig(name="smallthinker_t"),
                            DataConfig(seq_len=77))
    assert spec == {"tokens": (1, 77)}
    with pytest.raises(ValueError, match="whole periods"):
        create_model(ModelConfig(name="smallthinker_21b_a3b", num_layers=6), "fp32")
    with pytest.raises(ValueError, match="more than the 4 layers published"):
        create_model(ModelConfig(name="smallthinker_t", num_layers=8), "fp32")
    with pytest.raises(ValueError, match="not among the model's 64"):
        create_model(ModelConfig(name="smallthinker_21b_a3b", experts_held=16,
                                 expert_offset=56), "fp32")
    full = create_model(ModelConfig(name="smallthinker_21b_a3b"), "bf16").arch
    assert (full.num_hidden_layers, full.moe_num_primary_experts, full.held,
            full.vocab_size, full.period) == (52, 64, 64, 151936, 4)
    cut = create_model(ModelConfig(name="smallthinker_21b_a3b", num_layers=4,
                                   vocab_size=37984, experts_held=16), "bf16").arch
    assert (cut.num_hidden_layers, cut.held, cut.vocab_size) == (4, 16, 37984)
    assert cut.sliding_window_layout[:4] == cut.rope_layout[:4] == (0, 1, 1, 1)


def test_window_sites_are_counted_while_the_model_is_traced(params, tokens):
    from pytorchvideo_accelerate_tpu.ops import attention

    model = _model("fp32")
    with attention.count_window_sites() as sites:
        jax.eval_shape(lambda: model.apply({"params": params}, tokens))
    assert sites == [(T, 32)] * 3
    with attention.count_window_sites() as sites:  # no longer than the window
        jax.eval_shape(lambda: model.apply({"params": params}, tokens[:, :32]))
    assert sites == []


@pytest.mark.parametrize("forced", [True, False], ids=["kernel", "xla_form"])
def test_mixers_keep_the_flash_forwards_results(monkeypatch, forced):
    """Heads 128 wide over 1024 tokens, the kernels taken as on a TPU: the
    four mixers (one full layer, three under the band) run the forward kernel
    once each and keep its `o` and `lse` for the backward pass; on the CPU's
    own rule the step is a plain `nn.remat`'s."""
    from kept_attention import check_units_keep_the_forward
    from pytorchvideo_accelerate_tpu.models import smallthinker

    def model():
        return smallthinker.SmallThinker(smallthinker.SmallThinkerArch(
            hidden_size=64, num_hidden_layers=4, num_attention_heads=7,
            num_key_value_heads=1, head_dim=128, sliding_window_size=32,
            rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
            moe_num_primary_experts=8, moe_num_active_primary_experts=2,
            moe_ffn_hidden_size=32, vocab_size=256), dtype=jnp.float32)

    check_units_keep_the_forward(monkeypatch, smallthinker, model, forced, 4)

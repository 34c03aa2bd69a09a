"""models/qwen3_next.py through the normal path, against the plain reference
(benchmarks/reference/qwen3_next.py): logits, loss, every leaf's gradient,
and three `fit()` steps fed by `SyntheticTokenSource`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as ref
from pytorchvideo_accelerate_tpu.config import ModelConfig
from pytorchvideo_accelerate_tpu.models import (
    create_model,
    model_input_spec,
    model_task,
)

# the toy of models/__init__.py `qwen3_next_t`, under the reference's keys
ARCH = dict(hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=8,
            num_experts_per_tok=2, norm_topk_prob=True, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, rms_norm_eps=1e-6,
            vocab_size=256, experts_held=2, expert_offset=2)
T = 150  # not a multiple of the DeltaNet chunk (64)

# float32 policy: program and reference differ by summation order (chunked
# scan against per-token recurrence, grouped against masked expert products):
# 1e-5 of the largest entry; gradients of single parameters that are sums of
# thousands of float32 terms (A_log, dt_bias: |g| ~ 1e-6) read 2e-3 relative,
# so a leaf is held to 5e-3 of ITS largest entry.
# bfloat16 policy: every projection operand and activation is rounded to 8
# bits of mantissa (2^-8 = 4e-3 relative) through 4 layers, and a token near a
# tie of the router goes to another expert (its hidden state then moves by a
# whole expert's output): logits within 6e-2 of their range (read: 3.2e-2),
# loss within 2e-3, a leaf's gradient NORM within 10%.
POLICIES = [pytest.param("fp32", 1e-5, 1e-6, id="float32_tight"),
            pytest.param("bf16", 6e-2, 2e-3, id="bfloat16_loose")]


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
    return create_model(ModelConfig(name="qwen3_next_t", experts_held=2,
                                    expert_offset=2), policy)


def _program_loss(model, params, tokens):
    targets = jnp.roll(tokens, -1, axis=1)
    weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    out = model.apply({"params": params}, tokens, targets=targets,
                      weights=weights, train=True)
    return out["loss_sum"] / out["count"], out["expert_rows"]


def test_registry_declares_the_task_and_the_tree_is_the_references(params):
    assert model_task("qwen3_next_t") == model_task("qwen3_next_80b_a3b") \
        == "next_token"
    assert model_task("videomae_t_pretrain") == "reconstruct"
    assert model_task("slowfast_r50") == model_task("x3d_s") == "classify"
    model = _model("fp32")
    assert [model.arch.layer_type(i) for i in range(4)] == \
        ["linear_attention"] * 3 + ["full_attention"]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))

    def flat(tree):
        return {jax.tree_util.keystr(p): x.shape for p, x in
                jax.tree_util.tree_flatten_with_path(tree)[0]}

    assert flat(shapes["params"]) == flat(params)


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
    got = jax.jit(jax.grad(
        lambda p: _program_loss(model, p, tokens)[0]))(params)
    want = want[3]
    got, want = (dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (got, want))
    assert got.keys() == want.keys() and len(want) == 70
    for path, w in want.items():
        g, name = got[path], jax.tree_util.keystr(path)
        if policy == "fp32":
            assert float(jnp.abs(g - w).max()) <= 5e-3 * float(jnp.abs(w).max()), name
        elif w.size >= 512:
            norm = float(jnp.linalg.norm(w))
            assert abs(float(jnp.linalg.norm(g)) - norm) <= 0.1 * norm, name


def test_token_source_writes_rows_in_place_inside_the_slice():
    from pytorchvideo_accelerate_tpu.data.pipeline import (
        ClipLoader,
        SyntheticTokenSource,
    )

    source = SyntheticTokenSource(seq_len=32, vocab_size=50, num_sequences=16,
                                  seed=5)
    row = {"tokens": np.full(32, -1, np.int32)}
    out = source.get(3, 0, out=row)
    assert out["tokens"] is row["tokens"]
    assert np.array_equal(row["tokens"], source.get(3, 0)["tokens"])
    assert not np.array_equal(row["tokens"], source.get(4, 0)["tokens"])
    assert not np.array_equal(row["tokens"], source.get(3, 1)["tokens"])
    loader = ClipLoader(source, 4, shuffle=True, drop_last=True, seed=5,
                        num_workers=2)
    batches = list(loader.epoch(0))
    loader.close()
    assert len(batches) == 4
    for b in batches:
        assert set(b) == {"tokens"} and b["tokens"].shape == (4, 32)
        assert b["tokens"].dtype == np.int32
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 50
    assert len({r.tobytes() for b in batches for r in b["tokens"]}) == 16


_FIT_DRIVER = """
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from benchmarks.reference import qwen3_next as ref
from pytorchvideo_accelerate_tpu.config import config_from_dict
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

arch = {arch!r}
cfg = config_from_dict({{
    "model": {{"name": "qwen3_next_t", "experts_held": 2, "expert_offset": 2}},
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
        assert steps[i]["obs/moe_expert_load_max_over_mean"] >= 1.0
        assert steps[i]["moe_expert_rows_max"] >= steps[i]["moe_expert_rows_mean"]
    assert any("obs/tokens_per_s" in v for _s, v in got["logged"])


def test_model_input_spec_and_share_validation():
    from pytorchvideo_accelerate_tpu.config import DataConfig

    spec = model_input_spec(ModelConfig(name="qwen3_next_t"), DataConfig(seq_len=77))
    assert spec == {"tokens": (1, 77)}
    with pytest.raises(ValueError, match="whole periods"):
        create_model(ModelConfig(name="qwen3_next_t", num_layers=6), "fp32")
    with pytest.raises(ValueError, match="not among the model's 8"):
        create_model(ModelConfig(name="qwen3_next_t", experts_held=4,
                                 expert_offset=6), "fp32")
    full = create_model(ModelConfig(name="qwen3_next_80b_a3b"), "bf16").arch
    assert (full.num_hidden_layers, full.num_experts, full.held,
            full.vocab_size) == (48, 512, 512, 151936)


@pytest.mark.parametrize("forced", [True, False], ids=["kernel", "xla_form"])
def test_mixers_keep_the_flash_forwards_results(monkeypatch, forced):
    """Heads 128 wide over 1024 tokens, the kernels taken as on a TPU: the
    period's one attention mixer runs the forward kernel once and keeps its
    `o` and `lse` for the backward pass (the three DeltaNet mixers have
    nothing so named); on the CPU's own rule the step is a plain
    `nn.remat`'s."""
    from kept_attention import check_units_keep_the_forward
    from pytorchvideo_accelerate_tpu.models import qwen3_next

    def model():
        return qwen3_next.Qwen3Next(qwen3_next.Qwen3NextArch(
            hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=128, linear_num_key_heads=1,
            linear_num_value_heads=2, linear_key_head_dim=16,
            linear_value_head_dim=16, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            vocab_size=256), dtype=jnp.float32)

    check_units_keep_the_forward(monkeypatch, qwen3_next, model, forced, 1)

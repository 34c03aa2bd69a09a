"""The token cell's part of the benchmark: the work count, the new readers'
scopes in the compiled step, readers that fail loudly, the comparison's own
arithmetic, and the whole command at the toy geometry."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.jobs import train_fit_tokens as job
from benchmarks.lib import flops, hlo
from benchmarks.lib import work_qwen3_next as work_lib
from benchmarks.lib.spec import Spec, metric_module
from benchmarks.reference import plain
from benchmarks.reference import qwen3_next as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "qwen3_next_80b_a3b.train_8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("gdn_scan_roofline", "causal_attention_roofline",
               "moe_expert_roofline", "gdn_ms_per_step", "moe_ms_per_step",
               "moe_expert_load_max_over_mean")


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.fixture(scope="module")
def toy(spec):
    return job.arch_of(spec.config("qwen3_next_80b_a3b"), rehearse=True)


def test_configuration_keeps_every_published_width(spec):
    """Against the catalog row of the model-configs guide, copied here: every
    number under its own key, but for the three cuts `reduced` lists."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "vocab_size": 151936}
    config = spec.config("qwen3_next_80b_a3b")
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert set(config["reduced"]) == differs | {"num_experts"}
    assert config["experts_held"] == 32 and config["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    # floors of the guide: a whole period and four layers, 8 experts, an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    model = config["train_config"]["model"]
    assert (model["num_layers"], model["vocab_size"], model["experts_held"]) == \
        (config["num_hidden_layers"], config["vocab_size"], config["experts_held"])
    arch = job.arch_of(config, rehearse=False)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: ref.init_params(arch, 0))))
    assert n == 625_667_136  # the issue's 625.7M: 10.0 GB at 16 bytes


def test_dot_class_equals_the_flop_counter_on_the_references_matmuls(toy):
    """`work_qwen3_next`'s `dot` class against `lib/flops.py` walking the
    reference's loss-and-gradient jaxpr with the three other classes' parts
    taken out (the reference's `skip` hook): the same 2 M K N, product by
    product."""
    batch, seq = 2, 128
    params = jax.eval_shape(lambda: ref.init_params(toy, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss(p, t):
        return ref.loss_and_rows(p, t, toy, remat=False,
                                 skip=("gdn_scan", "attn_core", "moe_experts"))[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens)
    found = flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)
    assert {c for c, *_ in found} == {"dot"}
    counted = sum(f for _c, f, _e in found)
    work = work_lib.step_work(toy, batch, seq, routed_rows=0.0, peaks=PEAKS)
    assert work["by_class"]["dot"]["flops"] == counted
    assert work["by_class"]["dot"]["n"] == len(found)


def test_work_of_the_cell_by_hand(spec):
    arch = job.arch_of(spec.config("qwen3_next_80b_a3b"), rehearse=False)
    work = work_lib.step_work(arch, 2, 8192, routed_rows=4 * 10240.0, peaks=PEAKS)
    by = work["by_class"]
    tokens = 2 * 8192
    # one DeltaNet layer's projections, forward: 2048 x (12288 + 64) in, 4096
    # x 2048 out; three such layers, three products each
    gdn = 3 * 3 * 2.0 * tokens * (2048 * (12288 + 64) + 4096 * 2048)
    assert by["dot"]["flops"] > gdn
    assert by["gdn_scan"]["flops"] == 3 * 3 * tokens * 32 * 7.0 * 128 * 128
    pairs = 2 * 16 * 8192 * 8193 / 2
    assert by["attn_core"]["flops"] == 6 * 2.0 * pairs * 256
    assert by["moe_experts"]["flops"] == 4 * 3 * 3 * 2.0 * 10240 * 2048 * 512
    # near the weight-bandwidth ridge: an expert's 320 rows against its
    # 2048 x 512 weights read once a product
    assert 0 < by["moe_experts"]["memory_bound"] <= by["moe_experts"]["n"]
    assert work["flops_per_step"] == sum(c["flops"] for c in by.values())
    assert 15e12 < work["flops_per_step"] < 30e12  # the issue reckoned 23.4


@pytest.fixture(scope="module")
def toy_step_scopes():
    """{instruction: scopes} of the toy model's compiled next-token step."""
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    model = create_model(ModelConfig(name="qwen3_next_t", experts_held=2), "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=len(jax.devices())))
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    state = TrainState.create(variables["params"], {}, tx)
    step = make_lm_step(model, tx, mesh)
    batch = {"tokens": jnp.zeros((len(jax.devices()), 128), jnp.int32)}
    text = step.lower(state, batch, jax.random.key(0)).compile().as_text()
    return hlo.scopes(text)


@pytest.mark.parametrize("metric", NEW_METRICS[:5])
def test_readers_scope_matches_the_compiled_step(toy_step_scopes, metric):
    pattern = re.compile(metric_module(metric).SCOPE)
    hits = [s for s in toy_step_scopes.values() if pattern.search(s)]
    assert hits, metric
    # forward and backward both: the transpose's ops carry the scope too
    assert any("transpose(" in s for s in hits), metric


@pytest.mark.parametrize("scope", job.MODEL_SCOPES)
def test_every_model_scope_is_in_the_compiled_step(toy_step_scopes, scope):
    pattern = re.compile("/" + scope)
    assert any(pattern.search(s) for s in toy_step_scopes.values()), scope


def _results(seconds_under_scope, scope, least_s):
    ops = [(f"jit(step)/jvp(Qwen3Next)/mixer_0{scope}dot_general", "fusion.1",
            "", seconds_under_scope)]
    by = {c: {"least_s": least_s} for c in work_lib.CLASSES}
    return {"trace": {"ops": ops, "traced_steps": 2}, "chips": 1,
            "work": {"by_class": by}, "counters": {}}


@pytest.mark.parametrize("metric,scope", [
    ("gdn_scan_roofline", "/gdn/scan/"),
    ("causal_attention_roofline", "/attn/core/"),
    ("moe_expert_roofline", "/moe/cond/branch_1_fun/experts/")])
def test_a_share_over_100_fails_loudly(metric, scope):
    reader = metric_module(metric)
    # 2 steps, 0.5 s under the scope: 0.25 s a step against a least time of
    # 0.1 s is 40%
    assert reader.read(_results(0.5, scope, 0.1)) == pytest.approx(40.0)
    with pytest.raises(ValueError, match="least"):
        reader.read(_results(0.5, scope, 0.3))  # 120%: never reported
    assert reader.read(_results(0.5, "/elsewhere/", 0.1)) is None


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    empty = {"trace": None, "work": None, "peaks": None, "chips": 1}
    for name in NEW_METRICS:
        assert metric_module(name).read(empty) is None, name
    counted = {**empty, "counters": {"moe_expert_load_max_over_mean": [1.5, 1.3]}}
    assert metric_module("moe_expert_load_max_over_mean").read(counted) == \
        pytest.approx(1.4)
    ms = metric_module("gdn_ms_per_step").read(_results(0.5, "/gdn/conv/", 0.1))
    assert ms == pytest.approx(250.0)


def test_cell_lists_what_its_trace_must_report(spec):
    per_layer = spec.metric_names("per_layer", CELL)
    assert {f"{m}.device_paced" for m in NEW_METRICS} <= set(per_layer)
    assert "conv_roofline.device_paced" not in per_layer
    assert "step_mfu.device_paced" in per_layer and "compile_s" in per_layer
    assert spec.metric_names("end_to_end", CELL) == [
        "clips_per_s_per_chip.device_paced", "setup_s"]
    for m in NEW_METRICS:
        entry = spec.metric(f"{m}.device_paced")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "clips_per_s_per_chip.device_paced"


def test_comparison_arithmetic():
    assert job.routed_rows_gap([100, 90], [100, 100]) == pytest.approx(0.1)
    assert job.routed_rows_gap([100, None], [100, 100]) == float("inf")
    assert job.routed_rows_gap([100], [100, 100]) == float("inf")
    import numpy as np

    good = {"tokens": np.arange(12, dtype=np.int32).reshape(2, 6)}
    assert job.token_input_numbers([good], 2, 6, 12) == {
        "input_shape_gap": 0, "input_range_out": 0, "duplicate_rows": 0}
    twice = {"tokens": np.stack([good["tokens"][0]] * 2)}
    bad = job.token_input_numbers([twice, {"tokens": good["tokens"].astype(np.int64)},
                                   {**good, "label": 1}], 2, 6, 3)
    assert bad == {"input_shape_gap": 2, "input_range_out": 6, "duplicate_rows": 1}
    # Adam's first moment after one step is (1 - b1) g
    mu = {"a": {"w": np.full(4, 0.1, np.float32)}}
    assert job.program_grad_norms(mu)["a/w"] == pytest.approx(2.0)
    assert job.find_adam_mu((object(), (type("S", (), {"mu": mu})(),))) is mu


def test_adamw_written_out_is_the_programs_optimizer():
    """The reference's AdamW against `build_optimizer`'s chain
    (clip_by_global_norm, optax.adamw with the cosine schedule), 3 steps."""
    import optax

    from pytorchvideo_accelerate_tpu.config import OptimConfig
    from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer

    optim = {"lr": 3e-4, "weight_decay": 0.1, "grad_clip_norm": 1.0,
             "total_steps": 7}
    tx = build_optimizer(OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                                     grad_clip_norm=1.0, schedule="cosine"), 7)
    ks = jax.random.split(jax.random.key(0), 4)
    params = {"w": jax.random.normal(ks[0], (5, 3)), "b": jax.random.normal(ks[1], (3,))}
    state = tx.init(params)
    mine = (params, *(jax.tree.map(jnp.zeros_like, params) for _ in range(2)))
    for i in range(3):
        grads = jax.tree.map(lambda p, k=ks[2 + i % 2]: 3.0 * jax.random.normal(
            k, p.shape) * (i + 1), params)
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        mine = ref.adamw_update(*mine, grads, jnp.float32(i), optim)[:3]
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(mine[0])):
            assert float(jnp.abs(a - b).max()) < 1e-6


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "jobs",
                                      "train_fit_tokens.py"),
         "--workload", CELL, "--seed", "3000000021", "--seconds", "1",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearsal_walks_the_whole_command_and_the_faults_fail():
    """The cell's toy geometry through the one command on the CPU, traced,
    with the control and the planted faults judged beside the sound run."""
    line = _rehearse("--trace", "1", "--stand-in", "control", "--stand-in",
                     "state_unchanged", "--stand-in", "experts_skipped",
                     "--stand-in", "half_batch")
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert {"routed_rows_gap", "grad_gap_median", "delta_gap_median",
            "duplicate_rows", "recompiles"} <= set(line["compared"])
    assert "moe_expert_load_max_over_mean.device_paced" in line["metrics"]
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    for name, numbers in line["stand_ins"].items():
        failed = [k for k, v in numbers.items()
                  if k in limits and not v <= limits[k]]
        assert failed, name  # each is not correct, by one limit at least
        if name.startswith("experts_skipped"):
            assert numbers["routed_rows_gap"] == 1.0
        if name.startswith("state_unchanged"):
            assert numbers["delta_gap_median"] > 0.9

"""The Granite-4.0-H token cell's part of the benchmark: the configuration
against the catalog row, the work count, the readers' scopes in the compiled
step, the cell's listing, and the whole command at the toy geometry with the
family's planted faults."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.jobs import train_fit_lm as job
from benchmarks.lib import flops, hlo
from benchmarks.lib import work_granite_h as work_lib
from benchmarks.lib.spec import Spec, metric_module
from benchmarks.reference import granite_h as ref
from benchmarks.reference import plain

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "granite_4_0_h_micro"
CELL = "granite_4_0_h_micro.train_8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the Mamba-2 readers' twins this cell's regime lists it under, and the
# readers of the layers it shares with the other token cells
READERS = ("mamba_ms_per_step", "ssd_scan_roofline", "mlp_roofline",
           "causal_attention_roofline")


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.fixture(scope="module")
def toy(spec):
    return job.arch_of(spec.config(CONFIG), rehearse=True)


def test_configuration_keeps_every_published_width(spec):
    """Against the catalog row of the model-configs guide, copied here: every
    key under its own name, but for the two cuts `reduced` lists."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 8192,
        "layer_types": ["attention" if i % 10 == 5 else "mamba"
                        for i in range(40)],
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    config = spec.config(CONFIG)
    differs = {k for k, v in published.items()
               if k not in config or config[k] != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] in config["source"]
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert config["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert config["family"] == "granite_h"
    assert "8 chips share each layer" in config["deployment"]
    assert config["layers_run"]["layer_types"] == published["layer_types"][:10]
    assert {"init", "positions", "optimizer", "precision", "seq_len"} <= set(
        config["assumed"])
    assert {"dropout", "packing", "long_context", "unused_keys"} <= set(
        config["departures"])
    assert config["parameters"]["count"] == 772_160_448
    assert config["train_config"]["model"] == {
        "name": CONFIG, "num_layers": 10, "vocab_size": 12544}
    # the cell's traffic: the other token cells' optimizer, 1 x 8,192
    # tokens a step, 8 loader threads, device prefetch depth 2
    assert config["train_config"]["optim"] == spec.config(
        "qwen3_next_80b_a3b")["train_config"]["optim"]
    assert config["train_config"]["data"] == {"seq_len": 8192, "batch_size": 1}
    cell = spec.cell(CELL)
    data = cell["train_config"]["data"]
    assert (data["num_workers"], data["prefetch_batches"],
            data["device_prefetch_depth"]) == (8, 2, 2)
    assert (cell["check_steps"], cell["warmup_steps"]) == (3, 7)
    assert (cell["tokens"]["seq_len"], cell["tokens"]["sequences_per_step"]) \
        == (8192, 1)
    arch = job.arch_of(config, rehearse=False)
    assert ref.layer_types(arch) == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: ref.init_params(arch, 0))))
    assert n == 9 * 76_182_976 + 60_821_504 + 12544 * 2048 + 2048 == 772_160_448


def test_the_job_finds_its_files_by_the_family(spec):
    ref_lib, work = job.family_modules(spec.config(CONFIG))
    assert ref_lib is ref and work is work_lib
    assert spec.cell(CELL)["job"] == "train_fit_lm"
    assert set(ref.STAND_INS) == {"control", "half_batch", "state_unchanged",
                                  "head_untied", *ref.FAULTS}
    assert set(ref.FAULTS) == {
        "residual_unscaled", "embedding_unscaled", "attn_scale_sqrt_d",
        "logits_unscaled", "groups_as_8", "state_dropped_between_chunks",
        "d_skip_dropped", "decay_ignored"}
    assert set(ref.DIRECTION_LEAVES.values()) == {"in_proj", "embed"}
    assert set(spec.cell(CELL)["limits"]) >= set(ref.DIRECTION_LEAVES)


def test_seed_is_an_argument_of_the_jitted_initialiser(toy):
    a = ref.init_variables(toy, 4200000021)["params"]
    b = ref.init_variables(toy, 4200000022)["params"]
    again = ref.init_variables(toy, 4200000021)["params"]
    assert float(jnp.abs(a["embed"] - b["embed"]).max()) > 0
    assert bool(jnp.all(a["embed"] == again["embed"]))
    text = [str(jax.make_jaxpr(lambda s: ref.init_params(toy, s))(jnp.uint32(s)))
            for s in (1, 2)]
    assert text[0] == text[1]
    assert 0.09 < float(jnp.std(a["embed"])) < 0.11   # the tied table's N(0, 0.1)
    mlp = a["layer_0"]["ffn"]["mlp"]["input_linear"]
    assert 0.018 < float(jnp.std(mlp)) < 0.022


def test_dot_and_mlp_equal_the_flop_counter_on_the_references_matmuls(toy):
    """`work_granite_h`'s `dot` and `mlp` against `lib/flops.py` walking the
    reference's loss-and-gradient jaxpr with the recurrence and the attention
    core taken out (the reference's `skip` hooks): the same 2 M K N, product
    by product."""
    batch, seq = 2, 128
    params = jax.eval_shape(lambda: ref.init_params(toy, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss(p, t):
        return ref.loss_and_count(p, t, toy, remat=False,
                                  skip=("ssd_scan", "attn_core"))[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens)
    found = flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)
    assert {c for c, *_ in found} == {"dot"}
    by = work_lib.step_work(toy, batch, seq, 0.0, PEAKS)["by_class"]
    assert by["dot"]["flops"] + by["mlp"]["flops"] == sum(f for _c, f, _e in found)
    # 9 mixers x 2 + 1 attention x 4 + 10 MLPs x 2 + the head, 3 products each
    assert by["dot"]["n"] + by["mlp"]["n"] == len(found) == (18 + 4 + 20 + 1) * 3


def test_work_of_the_cell_by_hand(spec):
    """The cell's arithmetic: 39.34 TFLOP a step, 1.60 GFLOP a token forward
    (the MLPs 1.01 of it, the projections and the head 0.54, attention's core
    0.034, the recurrence 0.024)."""
    arch = job.arch_of(spec.config(CONFIG), rehearse=False)
    tokens = 8192
    work = work_lib.step_work(arch, 1, tokens, 0.0, PEAKS)
    by = work["by_class"]
    assert by["mlp"]["flops"] == 10 * 3 * 2.0 * tokens * 3 * 2048 * 8192
    assert by["ssd_scan"]["flops"] == 9 * 3 * 5.0 * tokens * 64 * 64 * 128
    assert by["ssd_scan"]["n"] == 18 and by["attn_core"]["n"] == 2
    assert work["flops_per_step"] == pytest.approx(39.344e12, rel=1e-4)
    assert work["flops_per_step"] / 3 / tokens == pytest.approx(1.60e9, rel=5e-3)
    # about 6 x 772 M x 8,192 for the parameters' products, the rest attention
    assert by["dot"]["flops"] + by["mlp"]["flops"] == pytest.approx(
        6 * 772_160_448 * tokens, rel=0.02)
    # the recurrence's least time is its bytes': memory-bound
    assert by["ssd_scan"]["memory_bound"] == 18
    assert set(by) == set(work_lib.CLASSES)


@pytest.fixture(scope="module")
def toy_step_scopes():
    """{instruction: scopes} of the toy model's compiled next-token step."""
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    model = create_model(ModelConfig(name="granite_h_t"), "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    variables = jax.jit(model.init)(jax.random.key(0),
                                    jnp.zeros((1, 8), jnp.int32))
    state = TrainState.create(variables["params"], {}, tx)
    step = make_lm_step(model, tx, mesh)
    batch = {"tokens": jnp.zeros((1, 128), jnp.int32)}
    text = step.lower(state, batch, jax.random.key(0)).compile().as_text()
    return hlo.scopes(text)


@pytest.mark.parametrize("metric", READERS)
def test_readers_scope_matches_the_compiled_step(toy_step_scopes, metric):
    pattern = re.compile(metric_module(metric).SCOPE)
    hits = [s for s in toy_step_scopes.values() if pattern.search(s)]
    assert hits, metric
    # forward and backward both: the transpose's ops carry the scope too
    assert any("transpose(" in s for s in hits), metric
    assert any(re.search(r"/layer_\d/(mixer|ffn)/", s) for s in hits), metric


@pytest.mark.parametrize("scope", ref.MODEL_SCOPES)
def test_every_model_scope_is_in_the_compiled_step(toy_step_scopes, scope):
    pattern = re.compile("/" + scope)
    assert any(pattern.search(s) for s in toy_step_scopes.values()), scope


def test_cell_lists_what_its_trace_must_report(spec):
    """Membership only: a later PR appends cells and metrics after these. The
    cell is listed under one regime, the one its measured spread needs, with
    the Mamba-2 readers' twins of that regime listing it alone."""
    end_to_end = spec.metric_names("end_to_end", CELL)
    regimes = [m.split(".", 1)[1] for m in end_to_end
               if m.startswith("clips_per_s_per_chip.")]
    assert len(regimes) == 1 and "setup_s" in end_to_end
    regime = regimes[0]
    per_layer = set(spec.metric_names("per_layer", CELL))
    assert {f"{m}.{regime}" for m in READERS} <= per_layer
    assert {f"{m}.{regime}" for m in (
        "step_mfu", "device_step_ms", "peak_hbm_bytes", "device_idle_share",
        "input_wait_share", "dispatch_ms_per_step", "step_gap_p95_ms",
        "optimizer_ms_per_step", "health_gauges_ms_per_step",
        "unscoped_ms_per_step")} | {"compile_s"} <= per_layer
    assert not {m for m in per_layer
                if m.startswith(("gdn_", "conv_", "depthwise_", "swa_",
                                 "window_", "moe_", "exit_"))}
    for m in ("mamba_ms_per_step", "ssd_scan_roofline"):
        entry = spec.metric(f"{m}.{regime}")
        assert CELL in entry["workloads"]
        assert entry["moves"] == f"clips_per_s_per_chip.{regime}"
        assert entry["source"] == "device_trace"
    entry = next(w for w in spec.doc["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "one group" in entry["why"] and "host" in entry["why"]


def test_reference_imports_nothing_from_the_program():
    text = open(os.path.join(ROOT, "benchmarks", "reference",
                             "granite_h.py")).read()
    assert "pytorchvideo_accelerate_tpu" not in text.split('"""', 2)[2]
    assert "precision=HI" in text


def test_rehearsal_walks_the_whole_command_and_the_faults_fail():
    """The cell's toy geometry through the one command on the CPU, traced,
    with the control and every planted fault judged beside the sound run:
    each fails its toy limits on every weight seed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "jobs",
                                      "train_fit_lm.py"),
         "--workload", CELL, "--seed", "4200000021", "--seconds", "1",
         "--rehearse", "--trace", "1",
         *(a for f in ref.STAND_INS for a in ("--stand-in", f))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert {"grad_gap_median", "delta_gap_median", "grad_dir_gap_in_proj",
            "grad_dir_gap_embed", "duplicate_rows",
            "recompiles"} <= set(line["compared"])
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    assert len(line["stand_ins"]) == 3 * len(ref.STAND_INS)
    for name, numbers in line["stand_ins"].items():
        failed = [k for k, v in numbers.items()
                  if k in limits and not v <= limits[k]]
        assert failed, name  # each is not correct, by one limit at least

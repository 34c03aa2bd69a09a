"""The Nemotron-H token cell's part of the benchmark: the configuration
against the catalog row, the work count, the readers' scopes in the compiled
step, readers that fail loudly or return nothing, the cell's listing, and the
whole command at the toy geometry with the family's planted faults."""

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
from benchmarks.lib import work_nemotron_h as work_lib
from benchmarks.lib.spec import Spec, metric_module
from benchmarks.reference import nemotron_h as ref
from benchmarks.reference import plain

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "nemotron_3_nano_30b_a3b"
CELL = "nemotron_3_nano_30b_a3b.train_8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("mamba_ms_per_step", "ssd_scan_roofline")


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.fixture(scope="module")
def toy(spec):
    return job.arch_of(spec.config(CONFIG), rehearse=True)


def test_configuration_keeps_every_published_width(spec):
    """Against the catalog row of the model-configs guide, copied here: every
    key under its own name, but for the three cuts `reduced` lists."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    config = spec.config(CONFIG)
    differs = {k for k, v in published.items()
               if k not in config or config[k] != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert set(config["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                      "vocab_size"}
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] in config["source"]
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["experts_held"], config["expert_offset"]) == (9, 16384, 8, 0)
    assert config["published"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128, "vocab_size": 131072}
    assert config["family"] == "nemotron_h"
    assert "16 chips share each layer" in config["deployment"]
    assert config["layers_run"]["hybrid_override_pattern"] == "MEMEM*EME"
    assert {"positions", "init", "optimizer", "seq_len"} <= set(config["assumed"])
    assert {"mtp", "correction_bias_update", "dropout", "packing",
            "unused_keys"} <= set(config["departures"])
    assert config["parameters"]["count"] == 666_963_456
    model = config["train_config"]["model"]
    assert model == {"name": CONFIG, "num_layers": 9, "vocab_size": 16384,
                     "experts_held": 8, "expert_offset": 0}
    # the cell's traffic: the other token cells' optimizer,
    # 2 x 8,192 tokens a step, 8 loader threads, device prefetch depth 2
    assert config["train_config"]["optim"] == spec.config(
        "qwen3_next_80b_a3b")["train_config"]["optim"]
    assert config["train_config"]["data"] == {"seq_len": 8192, "batch_size": 2}
    cell = spec.cell(CELL)
    data = cell["train_config"]["data"]
    assert (data["num_workers"], data["prefetch_batches"],
            data["device_prefetch_depth"]) == (8, 2, 2)
    assert (cell["check_steps"], cell["warmup_steps"]) == (3, 7)
    assert (cell["tokens"]["seq_len"], cell["tokens"]["sequences_per_step"]) \
        == (8192, 2)
    arch = job.arch_of(config, rehearse=False)
    assert ref.pattern(arch) == "MEMEM*EME"
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: ref.init_params(arch, 0))))
    assert n == 4 * 38_744_896 + 4 * (20_302_592 + 8 * 9_977_856) \
        + 23_399_040 + 2 * 16384 * 2688 + 2688 == 666_963_456


def test_the_job_finds_its_files_by_the_family(spec):
    ref_lib, work = job.family_modules(spec.config(CONFIG))
    assert ref_lib is ref and work is work_lib
    assert spec.cell(CELL)["job"] == "train_fit_lm"
    assert set(ref.STAND_INS) == {"control", "half_batch", "state_unchanged",
                                  "experts_skipped", "bf16_router", *ref.FAULTS}
    assert set(ref.FAULTS) == {"softmax_router", "unscaled_routing",
                               "relu_experts", "d_skip_dropped",
                               "norm_ungrouped", "groups_shared",
                               "decay_ignored"}
    assert set(ref.DIRECTION_LEAVES.values()) == {"in_proj", "router"}
    assert set(spec.cell(CELL)["limits"]) >= set(ref.DIRECTION_LEAVES)


def test_seed_is_an_argument_of_the_jitted_initialiser(toy):
    a = ref.init_variables(toy, 4000000021)["params"]
    b = ref.init_variables(toy, 4000000022)["params"]
    again = ref.init_variables(toy, 4000000021)["params"]
    assert float(jnp.abs(a["lm_head"] - b["lm_head"]).max()) > 0
    assert float(jnp.abs(a["layer_0"]["mamba"]["dt_bias"]
                         - b["layer_0"]["mamba"]["dt_bias"]).max()) > 0
    assert bool(jnp.all(a["lm_head"] == again["lm_head"]))
    text = [str(jax.make_jaxpr(lambda s: ref.init_params(toy, s))(jnp.uint32(s)))
            for s in (1, 2)]
    assert text[0] == text[1]
    assert 0.9 < float(jnp.std(a["embed"])) < 1.1   # the benchmark's N(0, 1)
    assert 0.018 < float(jnp.std(a["lm_head"])) < 0.022


def test_dot_equals_the_flop_counter_on_the_references_matmuls(toy):
    """`work_nemotron_h`'s `dot` against `lib/flops.py` walking the
    reference's loss-and-gradient jaxpr with the recurrence, the attention
    core and the routed experts taken out (the reference's `skip` hooks): the
    same 2 M K N, product by product."""
    batch, seq = 2, 128
    params = jax.eval_shape(lambda: ref.init_params(toy, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss(p, t):
        return ref.loss_and_rows(p, t, toy, remat=False,
                                 skip=("ssd_scan", "attn_core", "moe_experts"))[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens)
    found = flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)
    assert {c for c, *_ in found} == {"dot"}
    by = work_lib.step_work(toy, batch, seq, 0.0, PEAKS)["by_class"]
    assert by["dot"]["flops"] == sum(f for _c, f, _e in found)
    # 3 mixers x 2 + 2 mixtures x 3 + 1 attention x 4 + the head, 3 products each
    assert by["dot"]["n"] == len(found) == (3 * 2 + 2 * 3 + 4 + 1) * 3


def test_work_of_the_cell_by_hand(spec):
    """The cell's arithmetic: about 714 MFLOP a token forward (the four
    mixers 320, of which the recurrence 2.6 each; the mixtures 192, the
    shared experts 160 of it; attention 114, its core 67; the head 88)."""
    arch = job.arch_of(spec.config(CONFIG), rehearse=False)
    tokens = 2 * 8192
    routed = 4 * tokens * 6 * 8 / 128          # expected held pairs, 4 layers
    work = work_lib.step_work(arch, 2, 8192, routed, PEAKS)
    by = work["by_class"]
    assert by["ssd_scan"]["flops"] == 4 * 3 * 5.0 * tokens * 64 * 64 * 128
    assert by["ssd_scan"]["flops"] / 3 / tokens / 4 == pytest.approx(2.62e6, rel=1e-3)
    assert by["ssd_scan"]["n"] == 8 and by["attn_core"]["n"] == 2
    forward = work["flops_per_step"] / 3 / tokens
    assert forward == pytest.approx(714e6, rel=5e-3)
    core = by["attn_core"]["flops"] / 3 / tokens
    assert core == pytest.approx(67e6, rel=1e-2)
    assert by["moe_experts"]["flops"] / 3 / tokens == pytest.approx(
        4 * 0.375 * 2 * 2 * 2688 * 1856, rel=1e-9)
    # the recurrence's least time is its bytes': memory-bound
    assert by["ssd_scan"]["memory_bound"] == 8
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

    model = create_model(ModelConfig(name="nemotron_h_t", experts_held=2), "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    state = TrainState.create(variables["params"], {}, tx)
    step = make_lm_step(model, tx, mesh)
    batch = {"tokens": jnp.zeros((1, 128), jnp.int32)}
    text = step.lower(state, batch, jax.random.key(0)).compile().as_text()
    return hlo.scopes(text)


@pytest.mark.parametrize("metric", NEW_METRICS + (
    "causal_attention_roofline", "moe_expert_roofline", "moe_ms_per_step"))
def test_readers_scope_matches_the_compiled_step(toy_step_scopes, metric):
    pattern = re.compile(metric_module(metric).SCOPE)
    hits = [s for s in toy_step_scopes.values() if pattern.search(s)]
    assert hits, metric
    # forward and backward both: the transpose's ops carry the scope too
    assert any("transpose(" in s for s in hits), metric
    assert any(re.search(r"/layer_\d/", s) for s in hits), metric


@pytest.mark.parametrize("scope", ref.MODEL_SCOPES)
def test_every_model_scope_is_in_the_compiled_step(toy_step_scopes, scope):
    pattern = re.compile("/" + scope)
    assert any(pattern.search(s) for s in toy_step_scopes.values()), scope


def _results(seconds_under_scope, scope, least_s, classes=work_lib.CLASSES):
    ops = [(f"jit(step)/jvp(NemotronH)/layer_0{scope}dot_general",
            "fusion.1", "", seconds_under_scope)]
    by = {c: {"least_s": least_s} for c in classes}
    return {"trace": {"ops": ops, "traced_steps": 2}, "chips": 1,
            "work": {"by_class": by}, "counters": {}}


def test_a_share_over_100_fails_loudly():
    reader = metric_module("ssd_scan_roofline")
    # 2 steps, 0.5 s under the scope: 0.25 s a step against a least time of
    # 0.1 s is 40%
    assert reader.read(_results(0.5, "/mamba/scan/", 0.1)) == pytest.approx(40.0)
    with pytest.raises(ValueError, match="least"):
        reader.read(_results(0.5, "/mamba/scan/", 0.3))  # 120%: never reported
    assert reader.read(_results(0.5, "/mamba/in_proj/", 0.1)) is None
    assert metric_module("mamba_ms_per_step").read(
        _results(0.5, "/mamba/in_proj/", 0.1)) == pytest.approx(250.0)


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """No trace, no work, and the other families' programs and work counts (no
    `mamba/` scope, no `ssd_scan` class): nothing, never 0, and no raise."""
    from benchmarks.lib import work_qwen3_next

    empty = {"trace": None, "work": None, "peaks": None, "chips": 1}
    for name in NEW_METRICS:
        assert metric_module(name).read(empty) is None, name
    other = _results(0.5, "/gdn/scan/", 0.1, work_qwen3_next.CLASSES)
    for name in NEW_METRICS:
        assert metric_module(name).read(other) is None, name
    # the scope under a work count without the class
    scoped = _results(0.5, "/mamba/scan/", 0.1, work_qwen3_next.CLASSES)
    assert metric_module("ssd_scan_roofline").read(scoped) is None


def test_cell_lists_what_its_trace_must_report(spec):
    """Membership only: a later PR appends cells and metrics after these. The
    cell's six runs on the chip spread 1.05% (PERF.md section 2), so it is
    listed under the bound that spread needs, `.host_paced` (0.07), and under
    none of the `.device_paced` metrics (0.01)."""
    per_layer = set(spec.metric_names("per_layer", CELL))
    assert {f"{m}.host_paced" for m in NEW_METRICS} <= per_layer
    assert {"step_mfu.host_paced", "device_step_ms.host_paced",
            "peak_hbm_bytes.host_paced", "device_idle_share.host_paced",
            "compile_s", "optimizer_ms_per_step.host_paced",
            "health_gauges_ms_per_step.host_paced",
            "unscoped_ms_per_step.host_paced"} <= per_layer
    # the attention and mixture readers the other token cells list under
    # `.device_paced`, as twins of their own that list this cell alone
    twins = {f"{m}.host_paced" for m in (
        "causal_attention_roofline", "moe_ms_per_step", "moe_expert_roofline",
        "moe_expert_load_max_over_mean")}
    assert twins <= per_layer
    for name in twins:
        entry = spec.metric(name)
        assert entry["workloads"] == [CELL]
        twin = spec.metric(name.replace(".host_paced", ".device_paced"))
        assert CELL not in twin["workloads"]
        assert {k: v for k, v in entry.items()
                if k not in ("name", "moves", "workloads")} \
            == {k: v for k, v in twin.items()
                if k not in ("name", "moves", "workloads")}
    assert not {m for m in per_layer
                if m.startswith(("gdn_", "conv_", "depthwise_", "swa_",
                                 "window_", "mlp_", "exit_"))
                or m.endswith(".device_paced")}
    assert set(spec.metric_names("end_to_end", CELL)) == {
        "clips_per_s_per_chip.host_paced", "setup_s"}
    for m in NEW_METRICS:
        entry = spec.metric(f"{m}.host_paced")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "clips_per_s_per_chip.host_paced"
        assert entry["source"] == "device_trace"
    entry = next(w for w in spec.doc["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "768 rows" in entry["why"] and "host" in entry["why"]


def test_reference_imports_nothing_from_the_program():
    text = open(os.path.join(ROOT, "benchmarks", "reference",
                             "nemotron_h.py")).read()
    assert "pytorchvideo_accelerate_tpu" not in text.split('"""', 2)[2]
    assert "precision=HI" in text


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "jobs",
                                      "train_fit_lm.py"),
         "--workload", CELL, "--seed", "4000000021", "--seconds", "1",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearsal_walks_the_whole_command_and_the_faults_fail():
    """The cell's toy geometry through the one command on the CPU, traced,
    with the control and every planted fault judged beside the sound run:
    each fails its toy limits on every weight seed."""
    line = _rehearse("--trace", "1",
                     *(a for f in ref.STAND_INS for a in ("--stand-in", f)))
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert {"routed_rows_gap", "grad_gap_median", "delta_gap_median",
            "grad_dir_gap_in_proj", "grad_dir_gap_router", "duplicate_rows",
            "recompiles"} <= set(line["compared"])
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    assert len(line["stand_ins"]) == 3 * len(ref.STAND_INS)
    for name, numbers in line["stand_ins"].items():
        failed = [k for k, v in numbers.items()
                  if k in limits and not v <= limits[k]]
        assert failed, name  # each is not correct, by one limit at least

"""The second token cell's part of the benchmark: the configuration against
the catalog row, the work count, the readers' scopes in the compiled step,
readers that fail loudly or return nothing, the job's own arithmetic, and the
whole command at the toy geometry with the planted faults."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.jobs import train_fit_lm as job
from benchmarks.lib import flops, hlo
from benchmarks.lib import work_smallthinker as work_lib
from benchmarks.lib.spec import Spec, metric_module
from benchmarks.reference import plain
from benchmarks.reference import smallthinker as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "smallthinker_21b_a3b"
CELL = "smallthinker_21b_a3b.train_16k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("window_attention_roofline", "swa_ms_per_step")
SHARED_METRICS = ("causal_attention_roofline", "moe_expert_roofline",
                  "moe_ms_per_step", "moe_expert_load_max_over_mean")
FAULTS = ("window_ignored", "rope_everywhere", "router_after_attention",
          "silu_experts")


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.fixture(scope="module")
def toy(spec):
    return job.arch_of(spec.config(CONFIG), rehearse=True)


def test_configuration_keeps_every_published_width(spec):
    """Against the catalog row of the model-configs guide, copied here: every
    number under its own key, but for the cuts `reduced` lists; the layouts
    whole."""
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True, "num_attention_heads": 28,
        "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": layout, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    config = spec.config(CONFIG)
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert set(config["reduced"]) == differs | {"moe_num_primary_experts"}
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] in config["source"]
    assert config["experts_held"] == 16 and config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert "4 chips share each layer" in config["deployment"]
    assert "router" in config["assumed"]["router_input"]
    # floors of the guide: a whole period and four layers, 8 experts, an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] % 4 == 0 and config["experts_held"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    model = config["train_config"]["model"]
    assert (model["num_layers"], model["vocab_size"], model["experts_held"]) == \
        (config["num_hidden_layers"], config["vocab_size"], config["experts_held"])
    # the cell's traffic is the issue's: the other token cell's optimizer,
    # 1 x 16,384 tokens a step, 8 loader threads, device prefetch depth 2
    assert config["train_config"]["optim"] == spec.config(
        "qwen3_next_80b_a3b")["train_config"]["optim"]
    assert config["train_config"]["optim"]["lr"] == 3e-4
    assert config["train_config"]["data"] == {"seq_len": 16384, "batch_size": 1}
    data = spec.cell(CELL)["train_config"]["data"]
    assert (data["num_workers"], data["device_prefetch_depth"]) == (8, 2)
    arch = job.arch_of(config, rehearse=False)
    assert arch["rope_layout"] == layout  # the lists are read too
    assert work_lib.windows(arch) == [None, 4096, 4096, 4096]
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: ref.init_params(arch, 0))))
    assert n == 656_529_920  # the issue's 656.5M: 10.50 GB at 16 bytes


def test_the_job_finds_its_files_by_the_family(spec):
    ref_lib, work = job.family_modules(spec.config(CONFIG))
    assert ref_lib is ref and work is work_lib
    # a family whose reference brings no tables of its own gets the other
    # token job's
    other_ref, other_work = job.family_modules(spec.config("qwen3_next_80b_a3b"))
    assert other_ref.__name__.endswith("qwen3_next")
    assert other_work.__name__.endswith("work_qwen3_next")
    assert not hasattr(other_ref, "MODEL_SCOPES")
    assert spec.cell(CELL)["job"] == "train_fit_lm"


def test_dot_class_equals_the_flop_counter_on_the_references_matmuls(toy):
    """`work_smallthinker`'s `dot` class against `lib/flops.py` walking the
    reference's loss-and-gradient jaxpr with the other classes' parts taken
    out (the reference's `skip` hook): the same 2 M K N, product by product."""
    batch, seq = 2, 128
    params = jax.eval_shape(lambda: ref.init_params(toy, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss(p, t):
        return ref.loss_and_rows(p, t, toy, remat=False,
                                 skip=("attn_core", "moe_experts"))[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens)
    found = flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)
    assert {c for c, *_ in found} == {"dot"}
    counted = sum(f for _c, f, _e in found)
    work = work_lib.step_work(toy, batch, seq, routed_rows=0.0, peaks=PEAKS)
    assert work["by_class"]["dot"]["flops"] == counted
    assert work["by_class"]["dot"]["n"] == len(found)


def test_work_of_the_cell_by_hand(spec):
    arch = job.arch_of(spec.config(CONFIG), rehearse=False)
    rows = 4 * 16384 * 6 * 16 / 64   # the expected load, all four layers
    work = work_lib.step_work(arch, 1, 16384, routed_rows=rows, peaks=PEAKS)
    by = work["by_class"]
    # pairs a head a sequence: causal, and under the 4096 band
    assert work_lib.pairs(16384) == 134_225_920
    assert work_lib.pairs(16384, 4096) == 4096 * 4097 / 2 + 12288 * 4096 == 58_722_304
    assert work_lib.pairs(4096, 4096) == work_lib.pairs(4096)  # no band
    assert work_lib.pairs(100, 4096) == 100 * 101 / 2
    assert by["attn_core"]["flops"] == 6 * 2.0 * 28 * 134_225_920 * 128
    assert by["attn_window"]["flops"] == 3 * 6 * 2.0 * 28 * 58_722_304 * 128
    assert by["attn_core"]["n"] == 2 and by["attn_window"]["n"] == 6
    # the issue's arithmetic: 5.77 and 3 x 2.53 TFLOP, projections 8.25 (and
    # the router's 0.06), head 9.56, held experts 3.48: 34.6 in all
    assert by["attn_core"]["flops"] == pytest.approx(5.77e12, rel=2e-3)
    assert by["attn_window"]["flops"] == pytest.approx(3 * 2.53e12, rel=2e-3)
    head = 3 * 2.0 * 16383 * 2560 * 37984
    assert head == pytest.approx(9.56e12, rel=1e-3)
    router = 4 * 3 * 2.0 * 16384 * 2560 * 64
    assert by["dot"]["flops"] - head - router == pytest.approx(8.25e12, rel=1e-3)
    assert by["moe_experts"]["flops"] == 4 * 3 * 3 * 2.0 * 24576 * 2560 * 768
    assert by["moe_experts"]["flops"] == pytest.approx(3.48e12, rel=2e-3)
    assert work["flops_per_step"] == sum(c["flops"] for c in by.values())
    assert work["flops_per_step"] == pytest.approx(34.6e12, rel=5e-3)
    # an attention core is compute-bound: its least time is its FLOPs' alone
    assert by["attn_window"]["memory_bound"] == 0
    assert by["attn_window"]["least_s"] == pytest.approx(
        by["attn_window"]["flops"] / 197e12)


@pytest.fixture(scope="module")
def toy_step_scopes():
    """{instruction: scopes} of the toy model's compiled next-token step."""
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    model = create_model(ModelConfig(name="smallthinker_t", experts_held=2), "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=len(jax.devices())))
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    state = TrainState.create(variables["params"], {}, tx)
    step = make_lm_step(model, tx, mesh)
    batch = {"tokens": jnp.zeros((len(jax.devices()), 128), jnp.int32)}
    text = step.lower(state, batch, jax.random.key(0)).compile().as_text()
    return hlo.scopes(text)


@pytest.mark.parametrize("metric", NEW_METRICS + SHARED_METRICS[:3])
def test_readers_scope_matches_the_compiled_step(toy_step_scopes, metric):
    pattern = re.compile(metric_module(metric).SCOPE)
    hits = [s for s in toy_step_scopes.values() if pattern.search(s)]
    assert hits, metric
    # forward and backward both: the transpose's ops carry the scope too
    assert any("transpose(" in s for s in hits), metric


@pytest.mark.parametrize("scope", ref.MODEL_SCOPES)
def test_every_model_scope_is_in_the_compiled_step(toy_step_scopes, scope):
    pattern = re.compile("/" + scope)
    assert any(pattern.search(s) for s in toy_step_scopes.values()), scope
    if scope == "moe/router/":  # opened in the mixer, where the routing is made
        assert any(re.search(r"/mixer_\d/moe/router/", s)
                   for s in toy_step_scopes.values())
        assert not any(re.search(r"/mixture_\d/moe/router/", s)
                       for s in toy_step_scopes.values())


def _results(seconds_under_scope, scope, least_s, classes=work_lib.CLASSES):
    ops = [(f"jit(step)/jvp(SmallThinker)/mixer_1{scope}dot_general",
            "fusion.1", "", seconds_under_scope)]
    by = {c: {"least_s": least_s} for c in classes}
    return {"trace": {"ops": ops, "traced_steps": 2}, "chips": 1,
            "work": {"by_class": by}, "counters": {}}


def test_a_share_over_100_fails_loudly():
    reader = metric_module("window_attention_roofline")
    # 2 steps, 0.5 s under the scope: 0.25 s a step against a least time of
    # 0.1 s is 40%
    assert reader.read(_results(0.5, "/swa/core/", 0.1)) == pytest.approx(40.0)
    with pytest.raises(ValueError, match="least"):
        reader.read(_results(0.5, "/swa/core/", 0.3))  # 120%: never reported
    assert reader.read(_results(0.5, "/attn/core/", 0.1)) is None


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """No trace, no work, and the other family's program and work count (no
    `swa` scope, no `attn_window` class): nothing, and no raise."""
    empty = {"trace": None, "work": None, "peaks": None, "chips": 1}
    for name in NEW_METRICS:
        assert metric_module(name).read(empty) is None, name
    from benchmarks.lib import work_qwen3_next

    other = _results(0.5, "/gdn/scan/", 0.1, work_qwen3_next.CLASSES)
    for name in NEW_METRICS:
        assert metric_module(name).read(other) is None, name
    # a program with the scope under a work count without the class
    swa = _results(0.5, "/swa/core/", 0.1, work_qwen3_next.CLASSES)
    assert metric_module("window_attention_roofline").read(swa) is None
    assert metric_module("swa_ms_per_step").read(swa) == pytest.approx(250.0)


def test_cell_lists_what_its_trace_must_report(spec):
    per_layer = spec.metric_names("per_layer", CELL)
    want = {f"{m}.device_paced" for m in NEW_METRICS + SHARED_METRICS}
    assert want <= set(per_layer)
    assert not {m for m in per_layer if m.startswith(("gdn_", "conv_"))}
    assert {"step_mfu.device_paced", "device_step_ms.device_paced",
            "compile_s"} <= set(per_layer)
    assert spec.metric_names("end_to_end", CELL) == [
        "clips_per_s_per_chip.device_paced", "setup_s"]
    for m in NEW_METRICS:
        entry = spec.metric(f"{m}.device_paced")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "clips_per_s_per_chip.device_paced"
    # appended, nothing before them moved: the two new entries are the last
    assert [m["name"] for m in spec.doc["per_layer"][-2:]] == [
        f"{m}.device_paced" for m in NEW_METRICS]
    assert spec.doc["workloads"][-1]["name"] == CELL
    cell = spec.cell(CELL)
    assert (cell["tokens"]["seq_len"], cell["tokens"]["sequences_per_step"]) == \
        (16384, 1)
    assert cell["train_config"]["data"]["num_workers"] == 8
    assert cell["train_config"]["data"]["device_prefetch_depth"] == 2


def test_direction_gap_arithmetic():
    want = {"mixer_0/attn/k_proj": np.array([3.0, 4.0]),
            "mixer_1/swa/k_proj": np.array([1.0, 0.0]),
            "mixer_0/moe/router": np.array([2.0, 0.0])}
    got = {"mixer_0/attn/k_proj": np.array([3.0, 4.0]),
           "mixer_1/swa/k_proj": np.array([0.0, 1.0]),   # turned, same norm
           "mixer_0/moe/router": np.array([2.0, 0.2])}
    gaps = job.direction_gaps(got, {"grad_leaves": want}, ref.DIRECTION_LEAVES)
    assert gaps["grad_dir_gap_k_proj"] == pytest.approx(2 ** 0.5)
    assert gaps["grad_dir_gap_router"] == pytest.approx(0.1)
    missing = job.direction_gaps({}, {"grad_leaves": want}, ref.DIRECTION_LEAVES)
    assert missing["grad_dir_gap_router"] == float("inf")
    # a reference that hands back no leaves has nothing to hold against
    assert job.direction_gaps(got, {}, {}) == {}
    numbers = job.judge(
        {"losses": [1.0], "pairs": [10], "grad_norms": {"a": 1.0},
         "delta_norms": {"a": 1.0}, "grad_leaves": got},
        {"losses": [1.0], "pairs": [10], "grad_norms": {"a": 1.0},
         "delta_norms": {"a": 1.0}, "sizes": {"a": 5000}, "grad_leaves": want},
        {"grad_dir_gap_k_proj": 0.5}, {}, ref.DIRECTION_LEAVES)
    by = {n["name"]: n for n in numbers}
    assert by["grad_dir_gap_k_proj"]["ok"] is False
    assert by["grad_dir_gap_router"]["limit"] is None  # shown, decides nothing
    assert by["grad_dir_gap_router"]["ok"] is True


def test_reference_imports_nothing_from_the_program():
    text = open(os.path.join(ROOT, "benchmarks", "reference",
                             "smallthinker.py")).read()
    assert "pytorchvideo_accelerate_tpu" not in text.split('"""', 2)[2]
    assert "Precision.HIGHEST" in text


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "jobs",
                                      "train_fit_lm.py"),
         "--workload", CELL, "--seed", "3000000021", "--seconds", "1",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearsal_walks_the_whole_command_and_the_faults_fail():
    """The cell's toy geometry through the one command on the CPU, traced,
    with the family's planted faults judged beside the sound run."""
    line = _rehearse("--trace", "1",
                     *(a for f in FAULTS for a in ("--stand-in", f)))
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert {"routed_rows_gap", "grad_gap_median", "delta_gap_median",
            "grad_dir_gap_k_proj", "grad_dir_gap_router", "duplicate_rows",
            "recompiles"} <= set(line["compared"])
    assert "moe_expert_load_max_over_mean.device_paced" in line["metrics"]
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    assert len(line["stand_ins"]) == 3 * len(FAULTS)
    for name, numbers in line["stand_ins"].items():
        failed = [k for k, v in numbers.items()
                  if k in limits and not v <= limits[k]]
        assert failed, name  # each is not correct, by one limit at least
        if name.startswith("rope_everywhere"):
            assert numbers["grad_dir_gap_k_proj"] > 0.3
        if name.startswith("router_after_attention"):
            # h = x + attn(n) stays near x under unit-variance embeddings, so
            # the router's other input turns its gradient by a tenth, not all
            assert numbers["grad_dir_gap_router"] > 0.05

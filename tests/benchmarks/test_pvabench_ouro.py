"""The looped token cell's part of the benchmark: the configuration against
the catalog row, the work count, the readers' scopes in the compiled step,
readers that fail loudly or return nothing, and the whole command at the toy
geometry with the loop's planted faults."""

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
from benchmarks.lib import work_ouro as work_lib
from benchmarks.lib.spec import Spec, metric_module
from benchmarks.reference import ouro as ref
from benchmarks.reference import plain

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "ouro_2_6b"
CELL = "ouro_2_6b.train_4k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("mlp_roofline", "exit_loss_ms_per_step")
FAULTS = ("one_pass", "norm_once", "pre_norm_only", "gate_ignored",
          "entropy_dropped", "last_loss_only")


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.fixture(scope="module")
def toy(spec):
    return job.arch_of(spec.config(CONFIG), rehearse=True)


def test_configuration_keeps_every_published_width(spec):
    """Against the catalog row of the model-configs guide, copied here: every
    key under its own name, but for the one cut `reduced` lists."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    config = spec.config(CONFIG)
    differs = {k for k, v in published.items()
               if k not in config or config[k] != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] in config["source"]
    assert config["num_hidden_layers"] == 8   # the guide's floor is four
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["family"] == "ouro"
    assert "6 pipeline stages of 8 layers" in config["deployment"]
    assert {"sandwich_norms", "norm_between_passes", "exit_gate", "objective",
            "init", "seq_len", "optimizer"} <= set(config["assumed"])
    assert {"stage_2_gate_training", "kv_cache_sharing", "dropout",
            "packing"} <= set(config["departures"])
    assert "early_exit_threshold" in config["unused_keys"]
    model = config["train_config"]["model"]
    assert model == {"name": "ouro_2_6b", "num_layers": 8, "vocab_size": 49152}
    # the cell's traffic is the issue's: the other token cells' optimizer,
    # 1 x 4,096 tokens a step, 8 loader threads, device prefetch depth 2
    assert config["train_config"]["optim"] == spec.config(
        "qwen3_next_80b_a3b")["train_config"]["optim"]
    assert config["train_config"]["data"] == {"seq_len": 4096, "batch_size": 1}
    cell = spec.cell(CELL)
    data = cell["train_config"]["data"]
    assert (data["num_workers"], data["prefetch_batches"],
            data["device_prefetch_depth"]) == (8, 2, 2)
    assert (cell["check_steps"], cell["warmup_steps"], cell["trace_seconds"]) \
        == (3, 7, 5)
    assert (cell["tokens"]["seq_len"], cell["tokens"]["sequences_per_step"]) \
        == (4096, 1)
    arch = job.arch_of(config, rehearse=False)
    assert (arch["total_ut_steps"], arch["exit_entropy_beta"],
            arch["intermediate_size"]) == (4, 0.1, 5632)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: ref.init_params(arch, 0))))
    assert n == 8 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049 == 612_438_017


def test_the_job_finds_its_files_by_the_family(spec):
    ref_lib, work = job.family_modules(spec.config(CONFIG))
    assert ref_lib is ref and work is work_lib
    assert spec.cell(CELL)["job"] == "train_fit_lm"
    assert set(ref.STAND_INS) == {"control", "half_batch", "state_unchanged",
                                  *FAULTS}
    assert set(ref.DIRECTION_LEAVES.values()) == {"exit_gate", "k_proj"}


def test_seed_is_an_argument_of_the_jitted_initialiser(toy):
    """PERF.md section 7 item 7: a new seed traces and compiles nothing new
    (one jaxpr whatever the seed), and gives other weights."""
    a = ref.init_variables(toy, 3600000021)["params"]
    b = ref.init_variables(toy, 3600000022)["params"]
    again = ref.init_variables(toy, 3600000021)["params"]
    assert float(jnp.abs(a["lm_head"] - b["lm_head"]).max()) > 0
    assert bool(jnp.all(a["lm_head"] == again["lm_head"]))
    text = [str(jax.make_jaxpr(lambda s: ref.init_params(toy, s))(jnp.uint32(s)))
            for s in (1, 2)]
    assert text[0] == text[1]
    assert 0.9 < float(jnp.std(a["embed"])) < 1.1   # the benchmark's N(0, 1)
    assert 0.018 < float(jnp.std(a["lm_head"])) < 0.022


def test_dot_and_mlp_equal_the_flop_counter_on_the_references_matmuls(toy):
    """`work_ouro`'s `dot` + `mlp` against `lib/flops.py` walking the
    reference's loss-and-gradient jaxpr with the attention cores taken out
    (the reference's `skip` hook): the same 2 M K N, product by product, every
    pass counted."""
    batch, seq = 2, 128
    params = jax.eval_shape(lambda: ref.init_params(toy, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss(p, t):
        return ref.loss_and_ut(p, t, toy, remat=False, skip=("attn_core",))[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens)
    found = flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)
    assert {c for c, *_ in found} == {"dot"}
    counted = sum(f for _c, f, _e in found)
    by = work_lib.step_work(toy, batch, seq, 0.0, PEAKS)["by_class"]
    assert by["dot"]["flops"] + by["mlp"]["flops"] == counted
    # product by product, but for the gate: the reference makes the three
    # passes' gate logits in one product (and its two gradients) of the size
    # of the three that the count lists a pass at a time
    assert by["dot"]["n"] + by["mlp"]["n"] == len(found) + 3 * (3 - 1)
    # 3 passes x (2 layers x 4 projections + gate + head), x 3 products each
    assert by["dot"]["n"] == 3 * (2 * 4 + 2) * 3
    assert by["mlp"]["n"] == 3 * 2 * 3 * 3


def test_work_of_the_cell_by_hand(spec):
    arch = job.arch_of(spec.config(CONFIG), rehearse=False)
    work = work_lib.step_work(arch, 1, 4096, 0.0, PEAKS)
    by = work["by_class"]
    executions = 4 * 8           # passes x layers
    projections = executions * 4 * 3 * 2.0 * 4096 * 2048 * 2048
    heads = 4 * 3 * 2.0 * 4095 * 2048 * 49152
    gates = 4 * 3 * 2.0 * 4095 * 2048
    assert by["dot"]["flops"] == projections + heads + gates
    assert by["mlp"]["flops"] == executions * 3 * 3 * 2.0 * 4096 * 2048 * 5632
    pairs = 4096 * 4097 / 2
    assert by["attn_core"]["flops"] == executions * 6 * 2.0 * 16 * pairs * 128
    assert by["attn_core"]["n"] == 2 * executions
    # the issue's arithmetic: 13.2, 27.2, 6.6 and 9.9 TFLOP: 56.9 in all
    assert projections == pytest.approx(13.2e12, rel=3e-3)
    assert by["mlp"]["flops"] == pytest.approx(27.2e12, rel=3e-3)
    assert by["attn_core"]["flops"] == pytest.approx(6.6e12, rel=3e-3)
    assert heads == pytest.approx(9.9e12, rel=3e-3)
    assert work["flops_per_step"] == sum(c["flops"] for c in by.values())
    assert work["flops_per_step"] == pytest.approx(56.9e12, rel=2e-3)
    # the MLP's products are compute-bound: their least time is their FLOPs'
    assert by["mlp"]["memory_bound"] == 0
    assert by["mlp"]["least_s"] == pytest.approx(by["mlp"]["flops"] / 197e12)
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

    model = create_model(ModelConfig(name="ouro_t"), "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=len(jax.devices())))
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    state = TrainState.create(variables["params"], {}, tx)
    step = make_lm_step(model, tx, mesh)
    batch = {"tokens": jnp.zeros((len(jax.devices()), 128), jnp.int32)}
    text = step.lower(state, batch, jax.random.key(0)).compile().as_text()
    return hlo.scopes(text)


@pytest.mark.parametrize("metric", NEW_METRICS + ("causal_attention_roofline",))
def test_readers_scope_matches_the_compiled_step(toy_step_scopes, metric):
    pattern = re.compile(metric_module(metric).SCOPE)
    hits = [s for s in toy_step_scopes.values() if pattern.search(s)]
    assert hits, metric
    # forward and backward both: the transpose's ops carry the scope too
    assert any("transpose(" in s for s in hits), metric
    # inside the pass loop: under the stack's layers, or under the head's
    # scan over (pass, block)
    assert any(re.search(r"/stack/(?:checkpoint/)?layer_\d/|/while/", s)
               for s in hits), metric


@pytest.mark.parametrize("scope", ref.MODEL_SCOPES)
def test_every_model_scope_is_in_the_compiled_step(toy_step_scopes, scope):
    pattern = re.compile("/" + scope)
    assert any(pattern.search(s) for s in toy_step_scopes.values()), scope
    if scope.startswith(("attn/", "mlp/")):
        for i in (0, 1):   # under the one stack every pass runs
            assert any(re.search(rf"/stack/.*layer_{i}/" + scope, s)
                       for s in toy_step_scopes.values()), (scope, i)


def _results(seconds_under_scope, scope, least_s, classes=work_lib.CLASSES):
    ops = [(f"jit(step)/jvp(Ouro)/stack/layer_1{scope}dot_general",
            "fusion.1", "", seconds_under_scope)]
    by = {c: {"least_s": least_s} for c in classes}
    return {"trace": {"ops": ops, "traced_steps": 2}, "chips": 1,
            "work": {"by_class": by}, "counters": {}}


def test_a_share_over_100_fails_loudly():
    reader = metric_module("mlp_roofline")
    # 2 steps, 0.5 s under the scope: 0.25 s a step against a least time of
    # 0.1 s is 40%
    assert reader.read(_results(0.5, "/mlp/gate_up/", 0.1)) == pytest.approx(40.0)
    with pytest.raises(ValueError, match="least"):
        reader.read(_results(0.5, "/mlp/down/", 0.3))  # 120%: never reported
    assert reader.read(_results(0.5, "/attn/core/", 0.1)) is None


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """No trace, no work, and the other families' programs and work counts (no
    `mlp/` or `exit/` scope, no `mlp` class): nothing, never 0, and no raise."""
    empty = {"trace": None, "work": None, "peaks": None, "chips": 1}
    for name in NEW_METRICS:
        assert metric_module(name).read(empty) is None, name
    from benchmarks.lib import work_smallthinker

    other = _results(0.5, "/swa/core/", 0.1, work_smallthinker.CLASSES)
    for name in NEW_METRICS:
        assert metric_module(name).read(other) is None, name
    # a program with the scope under a work count without the class
    mlp = _results(0.5, "/mlp/down/", 0.1, work_smallthinker.CLASSES)
    assert metric_module("mlp_roofline").read(mlp) is None
    # a head and a loss without an exit gate are another family's
    head = _results(0.5, "/lm_head/", 0.1)
    assert metric_module("exit_loss_ms_per_step").read(head) is None
    # with one: every op under any of the three scopes, each op once
    looped = _results(0.5, "/exit/gate/", 0.1)
    looped["trace"]["ops"] += [
        ("jit(step)/jvp(Ouro)/while/body/checkpoint/lm_head/dot_general | "
         "jit(step)/jvp(Ouro)/while/body/checkpoint/loss/reduce_max",
         "fusion.2", "", 0.3),
        ("jit(step)/jvp(Ouro)/stack/layer_0/mlp/down/dot_general",
         "fusion.3", "", 9.0)]
    assert metric_module("exit_loss_ms_per_step").read(looped) == \
        pytest.approx(1e3 * (0.5 + 0.3) / 2)


def test_cell_lists_what_its_trace_must_report(spec):
    """Membership only: a later PR appends cells and metrics after these."""
    per_layer = set(spec.metric_names("per_layer", CELL))
    assert {f"{m}.device_paced" for m in NEW_METRICS} <= per_layer
    assert {"causal_attention_roofline.device_paced", "step_mfu.device_paced",
            "device_step_ms.device_paced", "peak_hbm_bytes.device_paced",
            "device_idle_share.device_paced", "compile_s"} <= per_layer
    assert not {m for m in per_layer
                if m.startswith(("moe_", "gdn_", "conv_", "swa_", "window_"))}
    assert {"clips_per_s_per_chip.device_paced", "setup_s"} <= set(
        spec.metric_names("end_to_end", CELL))
    for m in NEW_METRICS:
        entry = spec.metric(f"{m}.device_paced")
        assert CELL in entry["workloads"]
        assert entry["moves"] == "clips_per_s_per_chip.device_paced"
        assert entry["source"] == "device_trace"
    entry = next(w for w in spec.doc["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "head" in entry["why"] and "host" in entry["why"]


def test_reference_imports_nothing_from_the_program():
    text = open(os.path.join(ROOT, "benchmarks", "reference", "ouro.py")).read()
    assert "pytorchvideo_accelerate_tpu" not in text.split('"""', 2)[2]
    assert "Precision.HIGHEST" in text and "HI" in text.split('"""', 2)[2]


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "jobs",
                                      "train_fit_lm.py"),
         "--workload", CELL, "--seed", "3600000021", "--seconds", "1",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearsal_walks_the_whole_command_and_the_faults_fail():
    """The cell's toy geometry through the one command on the CPU, traced,
    with the control and every planted fault judged beside the sound run."""
    line = _rehearse("--trace", "1",
                     *(a for f in ref.STAND_INS for a in ("--stand-in", f)))
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert {"routed_rows_gap", "grad_gap_median", "delta_gap_median",
            "grad_dir_gap_exit_gate", "grad_dir_gap_k_proj", "duplicate_rows",
            "recompiles"} <= set(line["compared"])
    assert line["compared"]["routed_rows_gap"] == {"value": 0.0, "limit": 0}
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    assert len(line["stand_ins"]) == 3 * len(ref.STAND_INS)
    for name, numbers in line["stand_ins"].items():
        failed = [k for k, v in numbers.items()
                  if k in limits and not v <= limits[k]]
        assert failed, name  # each is not correct, by one limit at least
        if name.startswith(("one_pass", "gate_ignored", "last_loss_only")):
            # the gate gets no gradient at all, or another one entirely
            assert numbers["grad_dir_gap_exit_gate"] > 0.9

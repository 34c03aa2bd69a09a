"""The step's own scopes and the time no scope names.

`trainer/steps.py` `_make_update_step` runs the optimizer under `optim/` and
the in-graph health gauges under `health/`; `optimizer_ms_per_step` and
`health_gauges_ms_per_step` read them (lib/scoped.py), and
`unscoped_ms_per_step` reads what no scope of the program names
(lib/unscoped.py). This file lowers toy steps of both kinds (a next-token step
with the clip and AdamW, a conv step with SGD) and shows where the update's
and the gauges' ops land, then checks the readers on hand-built and recorded
traces and the cells that list them.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.lib import hlo, unscoped, xtrace
from benchmarks.lib.spec import Spec, metric_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace_x3d_s.json")
READERS = ("optimizer_ms_per_step", "health_gauges_ms_per_step",
           "unscoped_ms_per_step")
OPTAX = os.path.dirname(optax.__file__)
# an instruction of the step itself, under no scope: `jit(step)/<primitive>`
TOP = re.compile(r'^\s+(?:ROOT )?%?[\w.\-]+ = (\S+) '
                 r'.*op_name="jit\(step\)/([\w\-]+)"')
# what the accumulation scan adds around the gradients: its zeros, the loop
# and the average over micro-steps (unscoped: `unscoped_ms_per_step` reads it)
ACCUMULATION = {"broadcast_in_dim", "while", "div"}


def _mesh():
    from pytorchvideo_accelerate_tpu.config import MeshConfig
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh

    return make_train_mesh(MeshConfig(), devices=jax.devices()[:1])


def lm_step(health=True, guard=False):
    """(the jitted toy next-token step, its arguments): AdamW behind the
    global-norm clip, as the token cells run it."""
    from pytorchvideo_accelerate_tpu.config import ModelConfig, OptimConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.trainer import (TrainState,
                                                     build_optimizer)
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step

    model = create_model(ModelConfig(name="ouro_t"), "bf16")
    tx = build_optimizer(OptimConfig(optimizer="adamw", grad_clip_norm=1.0,
                                     weight_decay=0.1), total_steps=10)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    state = TrainState.create(variables["params"], {}, tx)
    step = make_lm_step(model, tx, _mesh(), health_metrics=health,
                        guard_skip=guard)
    return step, (state, {"tokens": jnp.zeros((1, 32), jnp.int32)},
                  jax.random.key(0))


def conv_step(health=True, accum=1, ema=0.0):
    """The same for a toy X3D: SGD with momentum and weight decay, as the
    conv cells run it; `accum` > 1 takes the accumulation scan."""
    from pytorchvideo_accelerate_tpu.config import OptimConfig
    from pytorchvideo_accelerate_tpu.models.x3d import X3D
    from pytorchvideo_accelerate_tpu.trainer import (TrainState,
                                                     build_optimizer,
                                                     make_train_step)

    model = X3D(num_classes=5, depths=(1, 1), stem_features=8,
                stage_features=(8, 16), head_features=32, dropout_rate=0.0)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 4, 16, 16, 3)))
    tx = build_optimizer(OptimConfig(), total_steps=10)
    state = TrainState.create(variables["params"], variables["batch_stats"],
                              tx)
    if ema:
        state = state.replace(ema_params=state.params)
    batch = {"video": np.zeros((2, 4, 16, 16, 3), np.float32),
             "label": np.zeros(2, np.int32)}
    if accum > 1:
        batch = {k: np.stack([v] * accum) for k, v in batch.items()}
    step = make_train_step(model, tx, _mesh(), accum_steps=accum,
                           ema_decay=ema, health_metrics=health)
    return step, (state, batch, jax.random.key(0))


STEPS = {
    "lm": lambda: lm_step(),
    "lm_guard": lambda: lm_step(guard=True),
    "conv": lambda: conv_step(),
    "conv_accum_ema": lambda: conv_step(accum=2, ema=0.9),
}


def _equations(jaxpr, prefix=""):
    """(name stack, equation) of every equation, inner jaxprs' included, each
    under its whole name stack."""
    for eqn in jaxpr.eqns:
        stack = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                         if p)
        yield stack, eqn
        for value in eqn.params.values():
            for inner in _inner(value):
                yield from _equations(inner, stack)


def _inner(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _inner(v)


def _from_optax(eqn):
    tb = eqn.source_info.traceback
    return tb is not None and any(f.file_name.startswith(OPTAX)
                                  for f in tb.frames)


@pytest.mark.parametrize("which", sorted(STEPS))
def test_every_optax_equation_of_the_update_is_under_its_scope(which):
    """Every equation that optax emits outside the forward and backward
    (`tx.update`, `apply_updates`, the norms) is under `optim/` or, for the
    gauges' norms, `health/`; the gauges' reductions are under `health/`."""
    step, args = STEPS[which]()
    found = {"optim": 0, "health": 0, "health_reduce": 0}
    for stack, eqn in _equations(step.trace(*args).jaxpr.jaxpr):
        first = stack.split("/")[0]
        if first in ("optim", "health"):
            found[first] += 1
            if first == "health" and eqn.primitive.name == "reduce_sum":
                found["health_reduce"] += 1
        elif _from_optax(eqn):
            # the loss's own optax calls are the forward's
            assert first.startswith(("jvp(", "transpose(")), (stack, eqn)
    assert found["optim"] > 0 and found["health_reduce"] > 0, found


def _compiled(step, args):
    return step.lower(*args).compile().as_text()


@pytest.mark.parametrize("which", sorted(STEPS))
def test_no_parameter_sized_op_of_the_compiled_step_is_left_unscoped(which):
    """In the compiled step, what sits right under `jit(step)/` is scalar
    bookkeeping (the step counter, the loss's and accuracy's divisions) and,
    under accumulation, the scan's own work around the gradients; the
    update runs under `optim/` and the gauges' reductions under `health/`,
    where the two readers find them."""
    text = _compiled(*STEPS[which]())
    found = [m.groups() for m in map(TOP.match, text.splitlines()) if m]
    wide = {op for shape, op in found
            if not re.fullmatch(r"(f32|s32|pred)\[\]", shape)}
    assert wide == (ACCUMULATION if "accum" in which else set()), wide
    scopes = hlo.scopes(text).values()
    for reader, primitive in (("optimizer_ms_per_step", "mul"),
                              ("health_gauges_ms_per_step", "reduce_sum")):
        pattern = re.compile(metric_module(reader).SCOPE + primitive)
        assert any(pattern.search(s) for s in scopes), reader


@pytest.mark.parametrize("build", [lm_step, conv_step], ids=["lm", "conv"])
def test_no_health_scope_without_the_gauges(build):
    text = _compiled(*build(health=False))
    scopes = hlo.scopes(text).values()
    assert not any(re.search(metric_module("health_gauges_ms_per_step").SCOPE,
                             s) for s in scopes)
    assert any(re.search(metric_module("optimizer_ms_per_step").SCOPE, s)
               for s in scopes)


# --- the readers -----------------------------------------------------------


def _results(ops, steps=2):
    return {"trace": {"ops": [(s, f"fusion.{i}", "", sec)
                              for i, (s, sec) in enumerate(ops)],
                      "traced_steps": steps}, "chips": 1}


SCOPED = [
    "jit(step)/jvp(Qwen3Next)/layers_0/attn/core/dot_general",
    "checkpoint/lm_head/dot_general",
    "jit(step)/optim/mul",
    "jit(step)/health/reduce_sum",
    "jit(step)/transpose(jvp(X3D))/res2_block0/conv_b/conv_general_dilated",
    "jit(step)/jvp(Ouro)/while/body/checkpoint/rematted_computation/"
    "stack/layer_1/mlp/down/dot_general",
]
UNSCOPED = [
    "jit(step)/jvp(SlowFast)/reduce_window_max",
    "ragged-dot-none",
    "copy-done",
    "",
    "jit(step)/mul",
    "reduce_sum",
    "batch['video']",
    "jit(step)/transpose(jvp(Ouro))/while/body/closed_call",
    "jit(step)/jvp(Ouro)/cond/branch_1_fun/pjit/shard_map/scan/remat/add",
    "jit(step)/jvp(Qwen3Next)/bhrd,bkhd->bhrk/dot_general",
]


@pytest.mark.parametrize("scope", SCOPED)
def test_a_scope_of_the_program_is_scoped(scope):
    assert not unscoped.is_unscoped(scope)
    # one such part makes a fusion scoped, whatever else it holds
    assert not unscoped.is_unscoped(" | ".join(UNSCOPED + [scope]))


@pytest.mark.parametrize("scope", UNSCOPED)
def test_what_names_no_scope_is_unscoped(scope):
    assert unscoped.is_unscoped(scope)


def test_readers_return_nothing_where_nothing_matches():
    for name in READERS:
        reader = metric_module(f"{name}.device_paced")
        assert reader is metric_module(name)
        assert reader.read({"trace": None, "chips": 1}) is None, name
        assert reader.read(_results([("jit(step)/optim/mul", 0.5)],
                                    steps=0)) is None, name
    every_op_scoped = _results([(s, 0.5) for s in SCOPED[:2]])
    nothing_unscoped = _results([(s, 0.5) for s in UNSCOPED])
    for name, results in (("optimizer_ms_per_step", nothing_unscoped),
                          ("health_gauges_ms_per_step", every_op_scoped),
                          ("unscoped_ms_per_step", every_op_scoped)):
        assert metric_module(name).read(results) is None, name


def test_readers_give_device_ms_a_step():
    results = _results([
        ("jit(step)/optim/mul | jit(step)/optim/add", 0.030),
        ("jit(step)/optim/reduce_sum | jit(step)/health/reduce_sum", 0.010),
        ("jit(step)/health/sqrt", 0.002),
        ("jit(step)/jvp(SlowFast)/reduce_window_max", 0.004),
        ("ragged-dot-none", 0.100),
        ("jit(step)/mul | jit(step)/jvp(X3D)/stem_t/conv_general_dilated",
         0.500),
    ], steps=2)
    read = {name: metric_module(name).read(results) for name in READERS}
    # a fusion across the two scopes counts under both
    assert read["optimizer_ms_per_step"] == pytest.approx(1e3 * 0.040 / 2)
    assert read["health_gauges_ms_per_step"] == pytest.approx(1e3 * 0.012 / 2)
    assert read["unscoped_ms_per_step"] == pytest.approx(1e3 * 0.104 / 2)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded chip trace")
def test_scoped_and_unscoped_add_up_on_the_recorded_trace():
    with open(FIXTURE) as f:
        doc = json.load(f)
    out = xtrace.reduce(xtrace.from_json(doc["planes"]), step_name="jit_step")
    ops = out["ops"]
    total = sum(s for *_x, s in ops)
    scoped = sum(s for scope, _n, _c, s in ops
                 if any(not unscoped.names_nothing(p)
                        for p in scope.split(" | ")))
    alone = unscoped.seconds(ops)
    assert 0 < alone < total
    assert scoped + alone == pytest.approx(total, rel=1e-12)
    # the ops on the batch as it arrives are named after the argument
    assert unscoped.seconds([op for op in ops if op[0].startswith("batch[")]) > 0
    ms = metric_module("unscoped_ms_per_step").read({"trace": out})
    assert ms == pytest.approx(1e3 * alone / out["traced_steps"])


# --- the cells that list them ----------------------------------------------


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


def _cells_of(spec, regime):
    return spec.metric(f"clips_per_s_per_chip.{regime}")["workloads"]


@pytest.mark.parametrize("regime", ["device_paced", "host_paced"])
@pytest.mark.parametrize("name", READERS)
def test_every_cell_of_the_regime_lists_the_reader(spec, name, regime):
    """Membership only: a later PR appends cells and metrics after these."""
    entry = spec.metric(f"{name}.{regime}")
    assert {k: entry[k] for k in ("unit", "better", "source", "moves")} == {
        "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": f"clips_per_s_per_chip.{regime}"}
    for cell in _cells_of(spec, regime):
        assert cell in entry["workloads"]
        assert entry["name"] in spec.metric_names("per_layer", cell)
    layer = {"optimizer_ms_per_step": "optimizer: trainer/steps.py",
             "health_gauges_ms_per_step": "in-graph health gauges:",
             "unscoped_ms_per_step": "device"}[name]
    assert entry["layer"].startswith(layer)

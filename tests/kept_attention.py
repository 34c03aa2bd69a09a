"""What the token families' tests of the remat units that keep the flash
forward's `o` and `lse` share (models/lm_common.py
`remat_keeping_attention`): the kernel calls of a traced program counted by
name, and a toy model's next-token step traced at shapes only."""

import flax.linen as nn
import jax
import optax

from pytorchvideo_accelerate_tpu import obs
from pytorchvideo_accelerate_tpu.analysis.gc_flops import pallas_kernel_name
from pytorchvideo_accelerate_tpu.config import MeshConfig
from pytorchvideo_accelerate_tpu.ops import attention
from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

# past one block of the kernels: a shorter sequence keeps the XLA form
SEQ = 1024


def pallas_calls(jaxpr, name: str) -> int:
    """`pallas_call`s of the kernel `name` in `jaxpr` and every jaxpr inside
    it (the remat units' and the scans' bodies)."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += pallas_kernel_name(eqn) == name
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    count += pallas_calls(sub.jaxpr, name)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    count += pallas_calls(sub, name)
    return count


def trace_step(model):
    """The model's next-token step traced on one sequence of SEQ tokens (no
    product runs) and the attention gauges the trace set."""
    tokens = jax.ShapeDtypeStruct((1, SEQ), "int32")
    mesh = make_train_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tx = optax.sgd(0.01)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.key(0), t), tokens)["params"]
    state = jax.eval_shape(lambda p: TrainState.create(p, {}, tx), params)
    traced = make_lm_step(model, tx, mesh).trace(state, {"tokens": tokens},
                                                 jax.random.key(0))
    registry = obs.get_registry()
    return traced, {name: registry.get(name).value() for name in
                    ("pva_attn_kernel_sites", "pva_attn_kept_sites")}


def check_units_keep_the_forward(monkeypatch, family, make_model, forced,
                                 sites):
    """With the kernels taken (`forced`), the step holds one forward kernel a
    site (the parent's plain `nn.remat` holds two: the unit's recomputation
    runs it again) and every site counts as kept; with the XLA form both
    gauges read 0 and the lowered step is the plain `nn.remat`'s."""
    if forced:
        monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    traced, gauges = trace_step(make_model())
    want = sites if forced else 0
    assert gauges == {"pva_attn_kernel_sites": want,
                      "pva_attn_kept_sites": want}
    assert pallas_calls(traced.jaxpr.jaxpr, "pva_attn_fwd") == want
    assert pallas_calls(traced.jaxpr.jaxpr, "pva_attn_dq") == want
    monkeypatch.setattr(family, "remat_keeping_attention", nn.remat)
    plain, gauges = trace_step(make_model())
    assert gauges["pva_attn_kept_sites"] == 0
    assert pallas_calls(plain.jaxpr.jaxpr, "pva_attn_fwd") == 2 * want
    if not forced:
        assert traced.lower().as_text() == plain.lower().as_text()

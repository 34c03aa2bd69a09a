"""Every kernel case of `described_v5e.CASES` but the long attention ones,
and `nemotron_3_nano_30b_a3b.train_8k`'s whole step, compiled for a described
TPU v5e (the account of why is in described_v5e.py)."""

import jax
import jax.numpy as jnp
import pytest

from described_v5e import (  # noqa: F401 - v5e is a fixture
    CASES,
    DTYPES,
    LONG_ATTENTION,
    compiled_case,
    v5e,
)
from pytorchvideo_accelerate_tpu.ops import attention


@DTYPES
@pytest.mark.parametrize("name", sorted(set(CASES) - set(LONG_ATTENTION)))
def test_kernel_compiles_for_v5e(v5e, name, dtype):
    assert "tpu_custom_call" in compiled_case(v5e, name, dtype).as_text()


def test_nemotron_cell_step_fits_the_chip(v5e, monkeypatch):
    """`nemotron_3_nano_30b_a3b.train_8k`'s whole train step (9 layers
    MEMEM*EME, 8 of 128 experts, 16,384 vocabulary rows, 2 x 8,192 tokens,
    AdamW behind the clip, the health gauges), compiled for one chip of a
    described v5e with the attention kernels taken as on a TPU: the Mamba-2
    mixers' four scopes are in the compiled step, the flash kernels serve the
    groups of 16, and the step's scratch beside the state (float32 masters
    and Adam's two moments, 8.0 GB) stays under the chip's 15.75 GiB (14.6 GB
    read)."""
    import re

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from pytorchvideo_accelerate_tpu.config import ModelConfig, OptimConfig
    from pytorchvideo_accelerate_tpu.models import create_model
    from pytorchvideo_accelerate_tpu.parallel.mesh import TRAIN_MESH_AXIS_NAMES
    from pytorchvideo_accelerate_tpu.trainer.optim import build_optimizer
    from pytorchvideo_accelerate_tpu.trainer.steps import make_lm_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), TRAIN_MESH_AXIS_NAMES)
    model = create_model(ModelConfig(name="nemotron_3_nano_30b_a3b",
                                     num_layers=9, vocab_size=16384,
                                     experts_held=8), "bf16")
    tx = build_optimizer(OptimConfig(optimizer="adamw", lr=3e-4,
                                     weight_decay=0.1, grad_clip_norm=1.0),
                         1000)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    replicated = NamedSharding(mesh, PartitionSpec())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(lambda p: TrainState.create(p, {}, tx), params))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, 8192), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec("data")))}
    compiled = make_lm_step(model, tx, mesh, health_metrics=True).lower(
        state, batch, jax.random.key(0)).compile()
    text = compiled.as_text()
    assert set(re.findall(r"/mamba/(\w+)/", text)) == {"in_proj", "conv",
                                                       "scan", "out"}
    assert re.search(r"custom-call.*pva_attn_fwd", text)
    memory = compiled.memory_analysis()
    state_bytes = memory.argument_size_in_bytes
    assert 7.9e9 < state_bytes < 8.1e9
    assert state_bytes + memory.temp_size_in_bytes < 15.75 * 2 ** 30

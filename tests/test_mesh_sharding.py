"""Mesh + sharding tests on the 8-fake-device CPU backend (SURVEY §4.1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.config import MeshConfig
from pytorchvideo_accelerate_tpu.parallel.mesh import (
    AXIS_DATA,
    data_shard_count,
    make_mesh,
    resolve_mesh_shape,
)
from pytorchvideo_accelerate_tpu.parallel.sharding import (
    batch_sharding,
    fsdp_spec,
    shard_batch,
    shard_params,
)


def test_resolve_infers_data_axis():
    assert resolve_mesh_shape(MeshConfig(), 8) == (8, 1, 1, 1)
    assert resolve_mesh_shape(MeshConfig(fsdp=2), 8) == (4, 2, 1, 1)
    assert resolve_mesh_shape(MeshConfig(fsdp=2, context=2), 8) == (2, 2, 1, 2)


def test_resolve_rejects_bad_shapes():
    with pytest.raises(ValueError):
        resolve_mesh_shape(MeshConfig(fsdp=3), 8)
    with pytest.raises(ValueError):
        resolve_mesh_shape(MeshConfig(data=3), 8)


def test_mesh_axes(mesh8):
    assert mesh8.shape[AXIS_DATA] == 8
    assert data_shard_count(mesh8) == 8


def test_shard_batch_places_on_all_devices(mesh8):
    batch = {"video": np.ones((16, 4, 8, 8, 3), np.float32), "label": np.arange(16)}
    global_batch = shard_batch(mesh8, batch)
    assert global_batch["video"].shape == (16, 4, 8, 8, 3)
    assert len(global_batch["video"].addressable_shards) == 8
    # each shard holds 16/8 = 2 samples
    assert global_batch["video"].addressable_shards[0].data.shape[0] == 2
    assert global_batch["video"].sharding == batch_sharding(mesh8)


def test_fsdp_spec_prefers_large_divisible_dim():
    s = jax.ShapeDtypeStruct((512, 256), jnp.float32)
    spec = fsdp_spec(s, fsdp_size=4)
    assert spec == jax.sharding.PartitionSpec("fsdp", None)
    tiny = jax.ShapeDtypeStruct((8,), jnp.float32)
    assert fsdp_spec(tiny, fsdp_size=4) == jax.sharding.PartitionSpec()


def test_shard_params_fsdp(devices8):
    mesh = make_mesh(MeshConfig(data=2, fsdp=4), devices=devices8)
    params = {"w": np.ones((1024, 64), np.float32), "b": np.zeros((64,), np.float32)}
    placed = shard_params(mesh, params)
    # w sharded 4-way on dim0 over fsdp; b replicated
    w_shard = placed["w"].addressable_shards[0].data
    assert w_shard.shape == (256, 64)
    b_shard = placed["b"].addressable_shards[0].data
    assert b_shard.shape == (64,)


def test_psum_over_mesh(mesh8):
    """Sharded-autodiff gradient reduction sanity: mean over a sharded batch
    differentiates to a cross-shard-correct gradient (DDP-allreduce moral
    equivalent, with no Reducer: SURVEY §2.3-N6)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.arange(16.0, dtype=np.float32)
    xs = jax.device_put(x, NamedSharding(mesh8, P(("data", "fsdp"))))
    w = jax.device_put(jnp.float32(2.0), NamedSharding(mesh8, P()))

    def loss(w, x):
        return jnp.mean(w * x)

    g = jax.jit(jax.grad(loss))(w, xs)
    np.testing.assert_allclose(np.asarray(g), np.mean(x), rtol=1e-6)


def test_in_graph_collective_facade(mesh8):
    """psum/all_gather wrappers under shard_map with replication checking
    off — the documented pattern for returning a replicated gather."""
    from jax.sharding import PartitionSpec as P

    from pytorchvideo_accelerate_tpu.parallel.collectives import (
        all_gather, psum,
    )

    f = jax.shard_map(lambda x: psum(x, ("data", "fsdp")), mesh=mesh8,
                      in_specs=P(("data", "fsdp")), out_specs=P(),
                      check_vma=False)
    np.testing.assert_allclose(np.asarray(f(jnp.ones(8))), [8.0])

    g = jax.shard_map(lambda x: all_gather(x, "data"), mesh=mesh8,
                      in_specs=P("data"), out_specs=P(None, "fsdp"),
                      check_vma=False)
    out = g(jnp.arange(16.0).reshape(8, 2))
    assert out.shape == (8, 2)
    np.testing.assert_allclose(np.asarray(out),
                               np.arange(16.0).reshape(8, 2))


def test_host_collective_facade_single_process():
    """accelerator gather/broadcast/reduce equivalents: single-process
    semantics (gather adds a leading process axis; broadcast/reduce are
    identity/pass-through). Multi-process behavior rides jax
    multihost_utils and is exercised by the 2-process launch tests."""
    from pytorchvideo_accelerate_tpu.parallel.collectives import (
        host_allgather, host_broadcast, host_reduce_sum,
    )

    x = {"a": np.arange(3.0, dtype=np.float32), "b": np.float32(2.0),
         "run": "run-2026/ckpts"}
    g = host_allgather({"a": x["a"]})
    assert g["a"].shape == (1, 3)
    b = host_broadcast(x)
    np.testing.assert_array_equal(b["a"], x["a"])  # numpy array on every rank
    assert b["run"] == "run-2026/ckpts"            # strings survive intact
    assert isinstance(b["run"], str)
    r = host_reduce_sum({"a": x["a"], "b": x["b"]})
    np.testing.assert_array_equal(r["a"], x["a"])
    assert float(r["b"]) == 2.0

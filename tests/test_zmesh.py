"""2-D (data, model) GSPMD train-mesh backbone (parallel/mesh.py,
docs/PARALLELISM.md): shape resolution, portable axis lookup, the
mesh-identity sharding cache, per-family model-axis rules, the
context-parallel lane on the train mesh, 1-vs-8-device loss parity,
mesh-reshape checkpoint restore, the forced-host subprocess helper, and
the `mesh-discipline` lint rule.

Late-alphabet name on purpose: tier-1 is timeout-bound and kills
mid-suite — cheap early-alphabet tests protect the DOTS count, and the
parity/restore tests here each pay a tiny3d train-step compile.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pytorchvideo_accelerate_tpu.analysis import lint_source
from pytorchvideo_accelerate_tpu.config import MeshConfig
from pytorchvideo_accelerate_tpu.parallel import sharding as psh
from pytorchvideo_accelerate_tpu.parallel.mesh import (
    batch_axes,
    cp_axis,
    data_shard_count,
    make_mesh,
    make_train_mesh,
    model_axis,
    resolve_train_mesh_shape,
)

HOT = "pytorchvideo_accelerate_tpu/trainer/loop.py"  # any declared-hot path


# --- mesh construction ------------------------------------------------------

def test_train_mesh_resolution(devices8):
    m = make_train_mesh(MeshConfig(), devices=devices8)
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 8, "model": 1}  # DP degenerate case
    m24 = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    assert dict(m24.shape) == {"data": 2, "model": 4}
    # -1 on data infers from the model axis
    assert resolve_train_mesh_shape(MeshConfig(model=4), 8) == (2, 4)
    with pytest.raises(ValueError, match="does not divide"):
        resolve_train_mesh_shape(MeshConfig(model=3), 8)
    with pytest.raises(ValueError, match="needs"):
        resolve_train_mesh_shape(MeshConfig(data=3, model=4), 8)


def test_legacy_config_falls_back_to_library_mesh(devices8):
    m = make_train_mesh(MeshConfig(fsdp=2), devices=devices8)
    assert m.axis_names == ("data", "fsdp", "tensor", "context")
    assert dict(m.shape)["fsdp"] == 2
    with pytest.raises(ValueError, match="pick one layout"):
        make_train_mesh(MeshConfig(model=2, tensor=2), devices=devices8)


def test_axis_resolution_portable_across_layouts(devices8):
    train = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    lib = make_mesh(MeshConfig(data=2, fsdp=2, context=2), devices=devices8)
    assert batch_axes(train) == ("data",)
    assert batch_axes(lib) == ("data", "fsdp")
    assert model_axis(train) == "model"
    assert model_axis(lib) == "tensor"
    assert cp_axis(train) == "model"
    assert cp_axis(lib) == "context"
    assert data_shard_count(train) == 2
    assert data_shard_count(lib) == 4


# --- the mesh-identity sharding cache ---------------------------------------

def test_sharding_cache_keys_on_mesh_identity(devices8):
    m1 = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    s1 = psh.batch_sharding(m1)
    assert s1.mesh is m1
    assert psh.batch_sharding(m1) is s1  # memo hit, not a rebuild
    # a reshaped mesh must get its own entry, never a stale alias
    m2 = make_train_mesh(MeshConfig(data=8, model=1), devices=devices8)
    s2 = psh.batch_sharding(m2)
    assert s2.mesh is m2 and s2 is not s1
    # equal-construction mesh: whatever object identity this jax gives
    # (0.4.37 memoizes Mesh, so equal meshes are the same object), the
    # cache contract is that the returned sharding's .mesh IS the mesh
    # passed in — the exact property the old Mesh.__eq__-keyed lru broke
    m3 = Mesh(np.array(devices8).reshape(2, 4), ("data", "model"))
    assert psh.batch_sharding(m3).mesh is m3


def test_sharding_cache_guards_id_reuse(devices8):
    """A dead entry whose id() got recycled (mesh GC'd, new allocation at
    the same address) must be detected via the weakref and rebuilt."""
    from pytorchvideo_accelerate_tpu.parallel import mesh as pmesh

    m = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)

    class _Gone:
        pass

    o = _Gone()
    dead = weakref.ref(o)
    del o
    gc.collect()
    assert dead() is None
    pmesh._mesh_memos[id(m)] = (
        dead, {"namedshardings": {P(("data",)): "stale-poison"}})
    s = psh.batch_sharding(m)
    assert s.mesh is m and s != "stale-poison"


def test_mesh_memo_store_stays_bounded():
    """Memoized values reference their mesh, so weakref death alone cannot
    bound the store — past _MESH_MEMO_MAX it must evict oldest-first (a
    live mesh's evicted memo just rebuilds)."""
    from pytorchvideo_accelerate_tpu.parallel import mesh as pmesh

    class _M:  # stand-in: mesh_memo needs only identity + weakref-ability
        pass

    keep = [_M() for _ in range(pmesh._MESH_MEMO_MAX * 2)]
    for o in keep:
        pmesh.mesh_memo(o, "t")["k"] = o  # value pins its "mesh", as real
    assert len(pmesh._mesh_memos) <= pmesh._MESH_MEMO_MAX
    # the newest entry survived the eviction pass
    assert id(keep[-1]) in pmesh._mesh_memos


def test_cp_wrapper_cache_keys_on_mesh_identity(devices8):
    """make_ring/ulysses_attention memoize per mesh identity — two calls on
    the same mesh reuse one wrapper (and its shape cache); a different mesh
    never aliases it."""
    from pytorchvideo_accelerate_tpu.parallel.ring_attention import (
        make_ring_attention,
    )

    m1 = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    m2 = make_train_mesh(MeshConfig(data=1, model=8), devices=devices8)
    a1 = make_ring_attention(m1)
    assert make_ring_attention(m1) is a1
    assert make_ring_attention(m2) is not a1


# --- placement rules --------------------------------------------------------

def test_shard_batch_and_constrain_on_train_mesh(devices8):
    mesh = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    host = {"video": np.arange(4 * 6, dtype=np.float32).reshape(4, 6)}
    placed = psh.shard_batch(mesh, host)
    v = placed["video"]
    assert v.sharding.mesh is mesh
    assert v.sharding == psh.batch_sharding(mesh)  # batch over `data` only
    np.testing.assert_array_equal(np.asarray(v), host["video"])

    @jax.jit
    def f(x):
        return psh.constrain_block(x * 2.0, mesh)

    with mesh:
        np.testing.assert_array_equal(np.asarray(f(v)), host["video"] * 2)


def test_param_sharding_per_family_model_axis(devices8):
    assert psh.family_uses_tp("mvit_b")
    assert psh.family_uses_tp("videomae_b_pretrain")
    assert not psh.family_uses_tp("tiny3d")
    assert not psh.family_uses_tp("slowfast_r50")

    mesh = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    params = {
        "block0": {"attn": {"qkv": {"kernel": np.zeros((32, 96), np.float32),
                                    "bias": np.zeros((96,), np.float32)},
                            "proj": {"kernel": np.zeros((32, 32), np.float32)}},
                   "conv": {"kernel": np.zeros((3, 3, 3, 8, 8), np.float32)}},
    }
    tree = psh.param_sharding(mesh, params)
    attn = tree["block0"]["attn"]
    # column-parallel: output features over `model`; row-parallel: input dim
    assert attn["qkv"]["kernel"].spec == P(None, "model")
    assert attn["qkv"]["bias"].spec == P("model")
    assert attn["proj"]["kernel"].spec == P("model", None)
    assert tree["block0"]["conv"]["kernel"].spec == P()  # conv: replicated
    # tp=False (the CP lane / conv families): nothing touches the model axis
    off = psh.param_sharding(mesh, params, tp=False)
    assert all("model" not in str(s.spec)
               for s in jax.tree.leaves(off, is_leaf=lambda x: hasattr(x, "spec")))


# --- context-parallel lane on the train mesh --------------------------------

def test_cp_attention_resolves_train_mesh_model_axis(devices8):
    """ring/ulysses spend the train mesh's `model` axis on token sharding —
    the router must resolve it without the library mesh's `context` axis."""
    from pytorchvideo_accelerate_tpu.ops.attention import (
        dense_attention, dot_product_attention,
    )

    mesh = make_train_mesh(MeshConfig(data=2, model=4), devices=devices8)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 32, 4, 16)), jnp.float32)
               for _ in range(3))
    want = dense_attention(q, k, v)
    for backend in ("ring", "ulysses"):
        with mesh:
            got = jax.jit(lambda a, b, c, be=backend: dot_product_attention(
                a, b, c, backend=be, mesh=mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"backend={backend}")


# --- loss parity and mesh-reshape restore (the tentpole contracts) ----------

K_STEPS = 3
PARITY_RTOL = 1e-3  # fp32: cross-layout reduction-order noise only


def _setup(devices, data, model):
    from pytorchvideo_accelerate_tpu.utils.bench_setup import build_step_setup

    # dropout off: the pinned jax's threefry is not partitionable, so
    # in-graph random masks are not layout-invariant across mesh shapes
    return build_step_setup(
        "tiny3d", frames=4, crop=24, batch_per_chip=1, num_classes=8,
        global_batch=8, devices=list(devices), total_steps=K_STEPS + 2,
        mesh_cfg=MeshConfig(data=data, model=model),
        mixed_precision="fp32", overrides={"dropout_rate": 0.0},
    )


def _run(setup, k=K_STEPS):
    from pytorchvideo_accelerate_tpu.utils.bench_setup import fetch_loss

    # the train step donates its state argument; the module-scoped setups
    # are shared across tests, so run from a copy and leave setup.state live
    state = jax.tree.map(lambda x: x.copy(), setup.state)
    losses = []
    for i in range(k):
        state, metrics = setup.step(state, setup.device_batch(i),
                                    jax.random.key(i))
        losses.append(fetch_loss(metrics))
    return state, losses


@pytest.fixture(scope="module")
def ref_point(devices8):
    return _setup(devices8[:1], 1, 1)


@pytest.fixture(scope="module")
def mesh_point(devices8):
    return _setup(devices8, 2, 4)


def test_loss_parity_1_vs_8_devices(ref_point, mesh_point):
    """Same fixed global batch, same steps: the (2, 4) 8-device mesh must
    reproduce the 1-device loss trajectory — sharding changes the
    schedule, never the math."""
    _, ref = _run(ref_point)
    _, got = _run(mesh_point)
    np.testing.assert_allclose(got, ref, rtol=PARITY_RTOL)


@pytest.mark.parametrize("point", ["ref_point", "mesh_point"])
def test_step_compiles_once_over_its_steps(point, request):
    """Zero steady-state recompiles under the (data, model) layout too,
    not just on one device: after a first step, further steps on fresh
    batches add no executable to the step's jit cache."""
    from pytorchvideo_accelerate_tpu.analysis import RecompileGuard
    from pytorchvideo_accelerate_tpu.obs.registry import Registry

    setup = request.getfixturevalue(point)
    _run(setup, k=1)
    guard = RecompileGuard(setup.step, registry=Registry())
    guard.arm()
    assert guard.supported
    _run(setup)
    assert guard.sample() == 0


def test_mesh_reshape_checkpoint_roundtrip(tmp_path, ref_point, mesh_point,
                                           devices8):
    """A checkpoint written under (2, 4) restores under (8, 1) AND under a
    single-device mesh at the same step, and the next step's loss is
    identical — the mesh-portable restore contract (orbax reshards into
    the CURRENT mesh's layouts; docs/PARALLELISM.md runbook)."""
    from pytorchvideo_accelerate_tpu.trainer.checkpoint import Checkpointer
    from pytorchvideo_accelerate_tpu.utils.bench_setup import fetch_loss

    state, _ = _run(mesh_point, k=1)
    ckpt = Checkpointer(str(tmp_path), use_async=False)
    try:
        ckpt.save(1, state)
        ckpt.wait()
        _, m_ref = mesh_point.step(state, mesh_point.device_batch(9),
                                   jax.random.key(9))
        want = fetch_loss(m_ref)
        for point in (_setup(devices8, 8, 1), ref_point):
            restored, _, step = ckpt.restore(point.state, step=1,
                                             mesh=point.mesh)
            assert step == 1
            shape = dict(point.mesh.shape)
            leaf = jax.tree.leaves(restored.params)[0]
            assert leaf.sharding.mesh is point.mesh, shape
            _, m2 = point.step(restored, point.device_batch(9),
                               jax.random.key(9))
            got = fetch_loss(m2)
            assert got == pytest.approx(want, rel=PARITY_RTOL), shape
    finally:
        ckpt.close()


# --- forced-host subprocess helper ------------------------------------------

@pytest.mark.slow
def test_forcehost_subprocess_overrides_ambient_flag():
    """`run_forced_host` must REPLACE tier-1's ambient 8-device flag (XLA
    honors the first occurrence), not append after it. Slow-marked: the
    child pays a full fresh jax import."""
    from pytorchvideo_accelerate_tpu.utils.forcehost import run_forced_host

    out = run_forced_host(
        "import jax, json\n"
        "print(json.dumps({'n': len(jax.devices()),"
        " 'platform': jax.devices()[0].platform}))\n",
        4, timeout=300.0)
    assert out == {"n": 4, "platform": "cpu"}


def test_forcehost_env_replaces_flag():
    from pytorchvideo_accelerate_tpu.utils.forcehost import forced_host_env

    env = forced_host_env(4, extra_env=None)
    flags = env["XLA_FLAGS"].split()
    ours = [f for f in flags if "xla_force_host_platform_device_count" in f]
    assert ours == ["--xla_force_host_platform_device_count=4"]
    assert env["JAX_PLATFORMS"] == "cpu"


# --- mesh-discipline lint rule ----------------------------------------------

def test_mesh_discipline_fires_in_hot_modules():
    src = ("import jax\n"
           "import jax.sharding\n"
           "def place(x, devs):\n"
           "    a = jax.device_put(x, devs[0])\n"
           "    m = jax.sharding.Mesh(devs, ('data',))\n")
    # ledger-discipline (PR 18) also fires on device_put in hot modules;
    # this test owns only the mesh-discipline verdicts.
    found = [f for f in lint_source(src, HOT) if f.rule == "mesh-discipline"]
    assert [f.rule for f in found] == ["mesh-discipline"] * 2
    assert [f.line for f in found] == [4, 5]


def test_mesh_discipline_sees_through_aliases():
    src = ("import jax.sharding as js\n"
           "from jax.sharding import Mesh as M\n"
           "from jax import device_put as dp\n"
           "def f(x, devs):\n"
           "    a = js.Mesh(devs, ('data',))\n"
           "    b = M(devs, ('data',))\n"
           "    c = dp(x)\n")
    found = [f for f in lint_source(src, HOT) if f.rule == "mesh-discipline"]
    assert [f.rule for f in found] == ["mesh-discipline"] * 3


def test_mesh_discipline_cold_modules_and_suppression():
    src = ("import jax\n"
           "def f(x):\n"
           "    return jax.device_put(x)\n")
    assert lint_source(src, "pytorchvideo_accelerate_tpu/data/manifest.py") == []
    sup = ("import jax\n"
           "def f(x):\n"
           "    return jax.device_put(x)  "
           "# pva: disable=mesh-discipline -- host-only staging buffer\n")
    # only mesh-discipline is suppressed; ledger-discipline may still fire here
    assert [f for f in lint_source(sup, HOT) if f.rule == "mesh-discipline"] == []

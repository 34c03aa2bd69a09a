"""pva-tpu-graphcheck (analysis/graphcheck + gc_* passes): one seeded
violation + one clean fixture per pass, the donation round-trip on the
real tiny3d train step (disarmed AND guard-armed), the analytic FLOP
count of the real step, the frames both passes name their sites by, the
dtype-literal lint rule, the `diff_rounds` null-vs-number "appeared"
semantics, CLI exit codes, the doctor snapshot, and the full-tree clean
gate.

Late-alphabet name on purpose: tier-1 is timeout-bound and kills
mid-suite — the expensive step-building integration lives behind ONE
module-scoped run_graphcheck() fixture shared by every assertion.
"""

import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pytorchvideo_accelerate_tpu.analysis.gc_donation import (  # noqa: E402
    check_donation,
    parse_input_output_aliases,
)
from pytorchvideo_accelerate_tpu.analysis.gc_dtype import check_dtype  # noqa: E402
from pytorchvideo_accelerate_tpu.analysis.gc_flops import (  # noqa: E402
    check_flops,
    jaxpr_flops,
)
from pytorchvideo_accelerate_tpu.analysis.gc_sharding import (  # noqa: E402
    check_sharding,
)
from pytorchvideo_accelerate_tpu.analysis.graphcheck import (  # noqa: E402
    finding_count,
    graphcheck_snapshot,
    main as graphcheck_main,
    run_graphcheck,
)
from pytorchvideo_accelerate_tpu.precision import f32_island  # noqa: E402


@pytest.fixture(scope="module")
def report():
    """ONE full graphcheck run over the real tiny3d train/eval/serve
    steps; every integration assertion reads this report."""
    return run_graphcheck(model="tiny3d", smoke=True)


# --- donation pass ----------------------------------------------------------

def test_donation_seeded_drift_detected():
    def drift(state, x):
        return {"a": state["a"] + 1.0,
                "b": state["b"].astype(jnp.float32)}, x.sum()

    st = {"a": jnp.zeros((32, 32)), "b": jnp.zeros((16,), jnp.bfloat16)}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's own unused-donation warning
        findings, summary = check_donation(
            jax.jit(drift, donate_argnums=0), (st, jnp.ones(4)))
    assert summary["declared_unaliased"] == 1  # the dtype-drifted leaf
    assert summary["aliased"] == 1             # the healthy leaf aliased
    assert any("NOT aliased" in f["message"] for f in findings)
    assert summary["bytes_failed"] == 16 * 2   # the bf16 leaf's bytes


def test_donation_seeded_undeclared_detected():
    findings, summary = check_donation(
        jax.jit(lambda st, x: ({"a": st["a"] * 2.0}, x.sum())),
        ({"a": jnp.zeros((8, 8))}, jnp.ones(4)))
    assert summary["undeclared_donatable"] == 1
    assert summary["bytes_undeclared"] == 8 * 8 * 4
    assert "donate_argnums" in findings[0]["message"]


def test_donation_clean_fn_is_clean():
    findings, summary = check_donation(
        jax.jit(lambda st, x: ({"a": st["a"] * 2.0}, x.sum()),
                donate_argnums=0),
        ({"a": jnp.zeros((8, 8))}, jnp.ones(4)))
    assert findings == []
    assert summary["aliased"] == summary["declared"] == 1


def test_alias_header_parse_handles_nesting():
    text = ("HloModule jit_f, is_scheduled=true, input_output_alias="
            "{ {0}: (0, {}, may-alias), {2}: (3, {}, must-alias) }, "
            "entry_computation_layout={(f32[8]{0})->f32[8]{0}}")
    assert parse_input_output_aliases(text) == {0: 0, 3: 2}
    assert parse_input_output_aliases("HloModule nothing_here") == {}


# --- dtype pass -------------------------------------------------------------

def test_dtype_seeded_upcast_detected():
    w = jnp.ones((16, 8), jnp.float32)
    xb = jnp.ones((4, 16), jnp.bfloat16)
    findings, summary = check_dtype(jax.make_jaxpr(
        lambda w, x: (x.astype(jnp.float32) @ w).sum())(w, xb))
    assert len(findings) == 1
    assert summary["tainted_dots"] == 1
    assert "f32_island" in findings[0]["message"]


def test_dtype_declared_island_is_clean():
    w = jnp.ones((16, 8), jnp.float32)
    xb = jnp.ones((4, 16), jnp.bfloat16)
    findings, summary = check_dtype(jax.make_jaxpr(
        lambda w, x: (f32_island(x) @ w).sum())(w, xb))
    assert findings == []
    assert summary["converts_allowlisted"] == 1


def test_dtype_downcast_ends_the_island():
    # an f32 excursion that returns to bf16 BEFORE the matmul is policy-
    # conformant compute, not a silent upcast
    w = jnp.ones((16, 8), jnp.bfloat16)
    xb = jnp.ones((4, 16), jnp.bfloat16)

    def fn(w, x):
        stats = x.astype(jnp.float32) * 2.0
        return (stats.astype(jnp.bfloat16) @ w).sum()

    findings, _ = check_dtype(jax.make_jaxpr(fn)(w, xb))
    assert findings == []


def test_dtype_fp32_policy_is_a_noop():
    findings, summary = check_dtype(
        jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones(4)), policy="fp32")
    assert findings == [] and summary["skipped"] is True


# --- the frames both passes name their sites by -----------------------------

def _named_for_frames(x, w):
    return x.astype(jnp.float32) @ w


@pytest.mark.parametrize("which", ["dtype", "sharding"])
def test_frames_name_the_user_function(which):
    """An equation traced from a named function yields that function's
    name: with no frames every site reads `<unknown>` and no allowlist
    entry of either pass can match."""
    import os

    from pytorchvideo_accelerate_tpu.analysis import gc_dtype, gc_sharding

    cj = jax.make_jaxpr(_named_for_frames)(
        jnp.ones((4, 16), jnp.bfloat16), jnp.ones((16, 8), jnp.float32))
    here = os.path.basename(__file__)
    for eqn in cj.jaxpr.eqns:
        if which == "dtype":
            frames = gc_dtype._frames(eqn)
            site = gc_dtype._site(frames)
        else:
            frames, site = gc_sharding._frames(eqn), gc_sharding._site(eqn)
        assert ("_named_for_frames", here) in frames, eqn
        assert site == f"{here}:_named_for_frames"


# --- sharding pass ----------------------------------------------------------

def test_sharding_seeded_contract_mismatch_detected():
    cj = jax.make_jaxpr(lambda x, w: x @ w)(jnp.ones((8, 512)),
                                            jnp.ones((512, 64)))
    findings, summary = check_sharding(cj, [{1: ("model",)}, {}],
                                       min_bytes=1)
    assert len(findings) == 1
    assert findings[0]["details"]["kind"] == "dot-contract"
    assert summary["dot_regathers"] == 1


def test_sharding_agreeing_contraction_is_clean():
    # the DP gradient psum plan: both operands sharded alike on the
    # contracted (batch) dim — partial matmul + psum, no regather
    cj = jax.make_jaxpr(lambda x, g: jnp.einsum("bd,bk->dk", x, g))(
        jnp.ones((8, 32)), jnp.ones((8, 16)))
    findings, _ = check_sharding(cj, [{0: ("data",)}, {0: ("data",)}],
                                 min_bytes=1)
    assert findings == []


def test_sharding_seeded_reshape_loss_detected():
    cj = jax.make_jaxpr(lambda x: x.reshape(48,))(jnp.ones((8, 6)))
    findings, _ = check_sharding(cj, [{1: ("model",)}], min_bytes=1)
    assert len(findings) == 1
    assert findings[0]["details"]["kind"] == "reshape-loss"


def test_sharding_fold_views_reshape_is_clean():
    # (B, V, ...) -> (B*V, ...): the sharded major dim keeps its block
    # structure (the eval/serving fold_views idiom)
    cj = jax.make_jaxpr(lambda x: x.reshape(32, 16))(jnp.ones((8, 4, 16)))
    findings, _ = check_sharding(cj, [{0: ("data",)}], min_bytes=1)
    assert findings == []


def test_sharding_seeded_concat_detected():
    cj = jax.make_jaxpr(
        lambda x, y: jnp.concatenate([x, y], axis=0))(
        jnp.ones((8, 32)), jnp.ones((8, 32)))
    findings, _ = check_sharding(cj, [{0: ("data",)}, {}], min_bytes=1)
    assert len(findings) == 1
    assert findings[0]["details"]["kind"] == "concat-sharded-dim"


def test_sharding_small_tensors_below_floor_ignored():
    cj = jax.make_jaxpr(lambda x, w: x @ w)(jnp.ones((2, 4)),
                                            jnp.ones((4, 2)))
    findings, _ = check_sharding(cj, [{1: ("model",)}, {}])
    assert findings == []  # default min_bytes floor: bias-sized noise


# --- flops pass -------------------------------------------------------------

def test_flops_matmul_exact():
    cj = jax.make_jaxpr(lambda a, b: a @ b)(jnp.ones((64, 32)),
                                            jnp.ones((32, 16)))
    assert jaxpr_flops(cj)["flops_total"] == 2 * 64 * 32 * 16


def test_flops_scan_multiplies_by_trip_count():
    def scanned(a, b):
        def body(c, _):
            return c @ b, None

        out, _ = jax.lax.scan(body, a, None, length=5)
        return out

    a, b = jnp.ones((16, 16)), jnp.ones((16, 16))
    base = jaxpr_flops(jax.make_jaxpr(lambda a, b: a @ b)(a, b))
    five = jaxpr_flops(jax.make_jaxpr(scanned)(a, b))
    assert five["by_class"]["dot"] == 5 * base["by_class"]["dot"]


def test_flops_conv_counts_only_valid_taps():
    from jax import lax

    x = jnp.ones((1, 8, 8, 3))
    w = jnp.ones((3, 3, 3, 4))
    cj = jax.make_jaxpr(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))(x, w)
    # SAME 8x8 with a 3-kernel: 3*8-2 = 22 valid taps per dim, not 24
    assert jaxpr_flops(cj)["by_class"]["conv"] == 2 * 1 * 4 * 3 * 22 * 22


# --- the real steps (ONE shared run) ----------------------------------------

def test_full_tree_clean_gate(report):
    assert report["findings_total"] == 0, (
        "graphcheck must be clean on the real train/eval/serve steps:\n"
        + "\n".join(
            f["message"] for t in report["targets"].values()
            for p in t["passes"].values() for f in p["findings"]))
    assert finding_count(report) == 0
    # train_step_pipelined joins on multi-device hosts (the tier-1
    # conftest's 8 fake devices qualify; a 1-device gate run skips it)
    assert set(report["targets"]) == {"train_step",
                                      "train_step_guard_armed",
                                      "eval_step", "serve_step",
                                      "train_step_pipelined",
                                      "train_step_fused",
                                      "serve_step_fused_pallas"}


def test_donation_round_trip_on_tiny3d(report):
    """The landed `donate_argnums=0` train step, PROVEN: every declared
    leaf aliased in the compiled module, zero donatable leaves left on
    the table — disarmed AND with the guard's in-graph skip armed (the
    jnp.where select must not break aliasing)."""
    for target in ("train_step", "train_step_guard_armed"):
        s = report["targets"][target]["passes"]["donation"]["summary"]
        assert s["declared"] > 0, (target, s)
        assert s["aliased"] == s["declared"], (target, s)
        assert s["declared_unaliased"] == 0, (target, s)
        assert s["undeclared_donatable"] == 0, (target, s)
        assert s["bytes_donated"] > 0, (target, s)
    assert report["donation_verified"] is True


def test_eval_and_serve_skip_donation_by_design(report):
    for target in ("eval_step", "serve_step", "serve_step_fused_pallas"):
        s = report["targets"][target]["passes"]["donation"]["summary"]
        assert s.get("skipped") is True, (target, s)


def test_fused_lowering_targets_stay_clean(report):
    """The fused-kernel knob (ModelConfig.fused_kernels) must not cost
    the graph its verified properties: donation still fully aliases
    through the fused-"auto" train step, and the forced-pallas serve
    forward's pallas_call eqns are COSTED by the registered FLOPs hooks
    (an opaque zero would silently deflate the count)."""
    s = report["targets"]["train_step_fused"]["passes"]["donation"][
        "summary"]
    assert s["declared"] > 0 and s["aliased"] == s["declared"], s
    assert s["undeclared_donatable"] == 0, s
    f = report["targets"]["serve_step_fused_pallas"]["passes"]["flops"][
        "summary"]
    assert f["eqn_counts"]["pallas_call"] > 0, f
    assert f["by_class"]["pallas"] > 0, f
    assert f["unregistered_pallas"] == [], f


def test_analytic_count_of_the_real_step_is_positive_and_counts_convs(
        report):
    s = report["targets"]["train_step"]["passes"]["flops"]["summary"]
    assert s["flops_total"] > 0
    assert s["by_class"]["conv"] > 0  # tiny3d is a conv net


def test_doctor_snapshot_after_run(report):
    snap = graphcheck_snapshot()
    assert snap["ran"] is True
    assert snap["findings_total"] == 0
    assert snap["donation_verified"] is True
    assert set(snap["findings_by_pass"]) == {"donation", "dtype",
                                             "sharding", "flops"}

    from pytorchvideo_accelerate_tpu.utils.device_doctor import (
        graphcheck_snapshot as doctor_snap,
    )

    assert doctor_snap()["findings_total"] == 0


def test_registry_gauges_published(report):
    from pytorchvideo_accelerate_tpu import obs

    reg = obs.get_registry()
    assert reg.get("pva_graphcheck_findings").value() == 0
    assert reg.get("pva_graphcheck_donation_verified").value() == 1.0


# --- recompile stability of the donated step, armed and disarmed ------------

@pytest.mark.parametrize("guard_skip", [False, True])
def test_donated_step_recompile_free(guard_skip):
    """train_recompiles == 0 must hold with donation landed, with and
    without the guard's in-graph skip branch."""
    import optax

    from pytorchvideo_accelerate_tpu.analysis import RecompileGuard
    from pytorchvideo_accelerate_tpu.config import MeshConfig
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.parallel.sharding import shard_state
    from pytorchvideo_accelerate_tpu.trainer.steps import _make_update_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    mesh = make_train_mesh(MeshConfig())
    tx = optax.sgd(0.1)

    def grad_fn(params, batch_stats, batch, key):
        loss = jnp.sum(params["w"] * batch["video"].mean())
        grads = {"w": jnp.ones_like(params["w"])}
        return (loss, (batch_stats, jnp.zeros(()), jnp.ones(()))), grads

    step = _make_update_step(grad_fn, tx, mesh, accum_steps=1,
                             lr_schedule=None, with_accuracy=False,
                             guard_skip=guard_skip)
    state = shard_state(mesh, TrainState.create(
        {"w": jnp.ones((4, 4))}, {}, tx))
    batch = {"video": jnp.ones((8, 2))}
    state, m = step(state, batch, jax.random.key(0))
    guard = RecompileGuard(step)
    guard.arm()
    for i in range(3):
        state, m = step(state, batch, jax.random.key(i + 1))
    assert guard.sample() == 0
    assert np.isfinite(float(m["loss"]))


def test_guard_rollback_never_reads_donated_buffers(tmp_path):
    """TrainGuard round-trip against the DONATED step: the LKG ring
    captures state whose device buffers later steps donate away
    (deleted); the rollback restore must re-materialize the saved bytes
    from disk, byte-equal to what was live at save time — never touch a
    donated buffer."""
    import optax

    from pytorchvideo_accelerate_tpu.config import GuardConfig, MeshConfig
    from pytorchvideo_accelerate_tpu.data.pipeline import LoaderState
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.parallel.sharding import shard_state
    from pytorchvideo_accelerate_tpu.reliability.guard import TrainGuard
    from pytorchvideo_accelerate_tpu.trainer.steps import _make_update_step
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    mesh = make_train_mesh(MeshConfig())
    tx = optax.sgd(0.1)

    def grad_fn(params, batch_stats, batch, key):
        loss = jnp.sum(params["w"]) * 1e-3
        grads = {"w": jnp.full_like(params["w"], batch["video"].mean())}
        return (loss, (batch_stats, jnp.zeros(()), jnp.ones(()))), grads

    step = _make_update_step(grad_fn, tx, mesh, accum_steps=1,
                             lr_schedule=None, with_accuracy=False,
                             guard_skip=True)
    state = shard_state(mesh, TrainState.create(
        {"w": jnp.ones((4, 4))}, {}, tx))
    guard = TrainGuard(
        GuardConfig(enabled=True, lkg_every_steps=1, lkg_keep=2,
                    rollback_after=1, max_rollbacks=1, warmup_steps=1000),
        output_dir=str(tmp_path), mesh=mesh, seed=1)
    batch = {"video": np.full((8, 2), 0.5, np.float32)}
    snapshots = {}
    try:
        for i in range(1, 5):
            # each call DONATES the previous state's buffers
            state, m = step(state, batch, jax.random.key(i))
            # the guard saves the LIVE state under the observation-time
            # gstep — snapshot under the same key the ring will use
            snapshots[i] = np.asarray(state.params["w"]).copy()
            host_m = {"loss": float(m["loss"]),
                      "grad_norm": float(m["grad_norm"])}
            action = guard.step(i, host_m, batch,
                                LoaderState(epoch=0, position=i), state)
            assert action is None
        assert guard.lkg_step is not None
        # anomaly -> immediate rollback (rollback_after=1)
        snapshots[5] = np.asarray(state.params["w"]).copy()
        nan_m = {"loss": float("nan"), "grad_norm": float("nan")}
        action = guard.step(5, nan_m, batch,
                            LoaderState(epoch=0, position=5), state)
        if action is None:  # the stashed step observes one call later
            action = guard.flush(state, LoaderState(epoch=0, position=5))
        assert action is not None and action.kind == "rollback"
        # restore with the LIVE state as template: the saved buffers were
        # donated away steps ago — orbax must serve copies from disk
        restored, lkg_step = guard.restore(state, action)
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), snapshots[lkg_step])
        # and the restored state is trainable through the donated step
        restored, m = step(restored, batch, jax.random.key(99))
        assert np.isfinite(float(m["loss"]))
    finally:
        guard.close()


# --- dtype-literal lint rule ------------------------------------------------

HOT = "pytorchvideo_accelerate_tpu/models/mvit.py"
COLD = "pytorchvideo_accelerate_tpu/data/manifest.py"


def _lint(src, path):
    from pytorchvideo_accelerate_tpu.analysis import lint_source

    return [f for f in lint_source(src, path) if f.rule == "dtype-literal"]


def test_dtype_literal_fires_on_bare_casts():
    src = ("import jax.numpy as jnp\n"
           "import numpy as np\n"
           "def f(x):\n"
           "    a = x.astype(jnp.float32)\n"
           "    b = jnp.asarray(x, jnp.float32)\n"
           "    c = np.array(x, dtype=np.float32)\n")
    found = _lint(src, HOT)
    assert [f.line for f in found] == [4, 5, 6]
    assert all("f32_island" in f.message for f in found)


def test_dtype_literal_is_alias_proof():
    src = ("import jax.numpy as J\n"
           "from numpy import float32 as f32\n"
           "from jax import numpy as jnumpy\n"
           "def f(x):\n"
           "    a = x.astype(J.float32)\n"
           "    b = x.astype(f32)\n"
           "    c = x.astype(jnumpy.float32)\n")
    assert [f.line for f in _lint(src, HOT)] == [5, 6, 7]


def test_dtype_literal_quiet_on_cold_modules_and_defaults():
    src = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    return x.astype(jnp.float32)\n")
    assert _lint(src, COLD) == []
    # dtype= defaults and creations are declarations, not casts
    src = ("import jax.numpy as jnp\n"
           "class M:\n"
           "    dtype = jnp.float32\n"
           "def f(n):\n"
           "    return jnp.zeros((n,), jnp.float32)\n")
    assert _lint(src, HOT) == []
    # bf16 casts are the policy direction, not an island
    assert _lint("import jax.numpy as jnp\n"
                 "def f(x):\n"
                 "    return x.astype(jnp.bfloat16)\n", HOT) == []


def test_dtype_literal_suppression():
    src = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    return x.astype(jnp.float32)  "
           "# pva: disable=dtype-literal -- conversion tool parity\n")
    assert _lint(src, HOT) == []


# --- perfdiff: null -> number is "appeared", not a regression ---------------

def test_perfdiff_null_mfu_to_number_is_appeared():
    from pytorchvideo_accelerate_tpu.analysis.perfdiff import diff_rounds

    # r02-shaped round: device numbers, but mfu was null (cost-model
    # capture failed) and mfu_analytic did not exist yet
    old = {"metric": "train clips/sec/chip (slowfast_r50)", "value": 2535.0,
           "unit": "clips/sec/chip", "mfu": None, "suspect": False,
           "models": {"slowfast_r50": 2535.0}}
    new = {"metric": "train clips/sec/chip (slowfast_r50)", "value": 2540.0,
           "mfu": 0.41, "mfu_analytic": 0.39, "mfu_source": "analytic",
           "models": {"slowfast_r50": 2540.0}}
    rep = diff_rounds(old, new)
    assert rep["ok"] is True
    assert rep["regressions"] == []
    assert "mfu" in rep["appeared"]
    assert "mfu_analytic" in rep["appeared"]
    assert rep["keys"]["mfu_analytic"] == {"old": None, "new": 0.39,
                                           "pct": None}


def test_perfdiff_numeric_regression_still_caught():
    from pytorchvideo_accelerate_tpu.analysis.perfdiff import diff_rounds

    old = {"value": 100.0, "mfu_analytic": 0.40}
    new = {"value": 100.0, "mfu_analytic": 0.30}
    rep = diff_rounds(old, new)
    assert rep["ok"] is False
    assert "mfu_analytic" in rep["regressions"]
    assert rep["appeared"] == []


# --- CLI exit codes ---------------------------------------------------------

def test_cli_selftest_exit_zero(capsys):
    assert graphcheck_main(["--selftest"]) == 0


def test_cli_usage_error_exit_two():
    assert graphcheck_main(["--no-such-flag"]) == 2


def test_cli_findings_exit_one(monkeypatch):
    import pytorchvideo_accelerate_tpu.analysis.graphcheck as gc

    monkeypatch.setattr(gc, "run_graphcheck", lambda **kw: {
        "model": "tiny3d", "smoke": True, "findings_total": 2,
        "donation_verified": False, "elapsed_s": 0.0,
        "targets": {"train_step": {"passes": {"donation": {
            "findings": [{"pass": "donation", "site": "x",
                          "message": "stubbed", "details": {}}] * 2,
            "summary": {}}}}}})
    assert gc.main([]) == 1

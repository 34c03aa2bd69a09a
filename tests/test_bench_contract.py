"""bench.py output contract: the driver parses exactly one JSON line with
{"metric", "value", "unit", "vs_baseline", ...} — lock the assembly logic
(finalize) without paying for a compile."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench", mod)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


def _model(name="slowfast_r50", **over):
    d = dict(clips_per_sec_per_chip=100.0, step_ms_blocked=10.0,
             step_ms_pipelined=9.0, frames=32, crop=256, suspect=False,
             tflops_per_sec_per_chip=50.0, mfu=0.25, platform="tpu",
             smoke=False)
    d.update(over)
    return {name: d}


def test_finalize_headline_fields():
    out = bench.finalize(_model(), {}, user_smoke=False)
    for key in ("metric", "value", "unit", "vs_baseline", "models"):
        assert key in out, key
    assert out["value"] == 100.0
    assert out["unit"] == "clips/sec/chip"
    assert out["mfu"] == 0.25
    assert "slowfast_r50" in out["metric"]
    assert "error" not in out  # real device number: nothing to flag


def test_finalize_flagship_fallback_on_error():
    models = {"slowfast_r50": {"error": "Timeout"}}
    models.update(_model("x3d_s", clips_per_sec_per_chip=42.0))
    out = bench.finalize(models, {}, user_smoke=False)
    assert out["value"] == 42.0
    assert "x3d_s" in out["metric"]
    # compact per-model summary: scalar or error head, never the full dict
    assert out["models"]["slowfast_r50"] == "err: Timeout"
    assert out["models"]["x3d_s"] == 42.0


def test_finalize_all_failed_is_flagged_not_silent():
    models = {"slowfast_r50": {"error": "boom"}}
    out = bench.finalize(models, {}, user_smoke=False)
    assert out["value"] == 0.0  # parseable, honest zero
    assert "none" in out["metric"]
    # an error-only flagship must not read as a real measurement
    assert out["suspect"] is True
    assert "device number" in out["error"]


def test_finalize_cpu_fallback_marks_suspect_and_error():
    models = _model(platform="cpu", smoke=True)
    out = bench.finalize(
        models, {"data_pipeline": {"decode_clips_per_sec": 5}},
        user_smoke=False)
    assert out["suspect"] is True
    assert "device number" in out["error"]
    # bulky host-bench blocks stay in bench_partial.json, not the line
    assert "data_pipeline" not in out


def test_finalize_user_smoke_is_not_an_error():
    out = bench.finalize(_model(platform="cpu", smoke=True), {},
                         user_smoke=True)
    assert "error" not in out
    assert "smoke" in out["metric"]


def test_finalize_extras_passthrough():
    out = bench.finalize(
        _model(),
        {"trainer_vs_rawstep": 0.934, "error": "watchdog: 10s",
         "trainer_input_wait_frac": 0.012},
        user_smoke=False)
    assert out["trainer_vs_rawstep"] == 0.934
    # the overlap-proof metric rides the headline line when present
    assert out["trainer_input_wait_frac"] == 0.012
    assert out["error"].startswith("watchdog")


def test_finalize_json_serializable():
    import json

    out = bench.finalize(_model(), {}, user_smoke=False)
    line = json.dumps(out)
    assert "\n" not in line
    assert json.loads(line)["value"] == 100.0


def test_feed_projection_draws_the_consequence():
    """r4's measured rates (4 thread workers, 1 core: 22.55 loader clips/s,
    57k page-cache-resident cache clips/s) must project to tens of decode
    workers per chip at plausible device rates — the table VERDICT r4 asked
    for, computed not narrated."""
    dp = {"loader_thread_clips_per_sec": 22.55, "num_workers": 4,
          "cache_clips_per_sec": 57134.0}
    proj = bench.feed_projection(dp)
    rows = {r["device_clips_per_sec"]: r for r in proj["rows"]}
    assert set(rows) == {100, 200, 400}
    # per-worker 5.64 clips/s -> 200 clips/s/chip needs ceil(200/5.64)=36
    assert rows[200]["decode_workers_per_chip"] == 36
    assert rows[400]["decode_workers_per_chip"] == 71
    # cache path: orders of magnitude cheaper in CPU terms
    assert rows[400]["cache_cores_per_chip"] < 1.0
    assert proj["basis"]["cache_is_page_cache_resident"] is True
    assert "mandatory" in proj["conclusion"]


def test_finalize_line_fits_driver_capture():
    """Round 4 arrived `parsed: null` because the one-line JSON outgrew
    the driver's ~2000-byte stdout tail capture. Lock the budget with a
    worst-case payload: every workload present, every lane's keys and
    long error strings."""
    import json

    models = {}
    for name in bench.WORKLOADS:
        models.update(_model(name))
    extras = {
        "trainer_vs_rawstep": 0.934, "trainer_mfu": 0.1234,
        "mfu_analytic": 0.1234, "mfu_source": "costmodel",
        "mfu_peak_source": "measured",
        "multichip_mfu_peak_source": "measured",
        "graphcheck_findings": 0, "spmdcheck_findings": 0,
        "spmd_schedule_divergence": 0, "spmd_divergence_detected": True,
        "obs_step_s": 0.012345, "obs_input_wait_frac": 0.0123,
        "obs_h2d_s": 0.001234, "train_recompiles": 0, "tsan_findings": 0,
        "chaos_findings": 0, "guard_rollbacks": 0, "quarantined_clips": 0,
        "mesh_parity": True, "mesh_ckpt_portable": True,
        "multichip_cps_per_chip": {"1": 123.456, "8": 117.89},
        "multichip_forced_host": True, "multichip_train_recompiles": 0,
        "multichip_mfu": 0.1234, "multichip_mfu_analytic": 0.1111,
        "multichip_error": "no trustworthy device numbers " + "z" * 200,
        "serve_rps": 123.456, "serve_p99_ms_under_load": 87.654,
        "swap_blackout_ms": 12.345, "fleet_shed_frac": 0.0123,
        "trace_sampled": 1234, "trace_overhead_frac": 0.01234,
        "fleet_error": "no trustworthy device numbers " + "w" * 200,
        "dataplane_cps": 49.71, "dataplane_input_wait_frac": 0.8294,
        "dataplane_workers": 2,
        "dataplane_error": "remote batch stream diverged " + "d" * 200,
        "pipeline_parity": True, "pipeline_donation_verified": True,
        "pipeline_train_recompiles": 0, "pipeline_cps_per_chip": 6.195,
        "pipeline_bubble_frac": 0.0171,
        "pipeline_bubble_frac_analytic": 0.2727, "pipeline_stages": 4,
        "pipeline_error": "no trustworthy device numbers " + "p" * 200,
        "stream_incremental_speedup": 4.144,
        "stream_h2d_bytes_frac": 0.125, "stream_p99_ms": 62.75,
        "stream_parity": True, "stream_recompiles": 0,
        "stream_trunk_speedup": 7.345, "stream_trunk_top1_delta": 0.0312,
        "stream_trunk_parity": True, "stream_trunk_recompiles": 0,
        "stream_trunk_error": "top-1 delta breached " + "q" * 200,
        "stream_error": "no trustworthy device numbers " + "s" * 200,
        "autoscale_converge_s": 0.373, "fleet_scaledown_shed_frac": 0.0,
        "canary_rollback": 1, "fleet_models_served": 2,
        "canary_promoted": True, "fleet_session_failures": 0,
        "fleet_auto_error": "no trustworthy device numbers " + "a" * 200,
        "hbm_peak_bytes": 283289720, "hbm_attributed_frac": 0.9876,
        "hbm_source": "estimate", "alert_false_positives": 0,
        "budget_lies_refused": True,
        "kbench_platform": "cpu", "kbench_parity_ok": True,
        "kbench_best": "dw_x3d_res3:118.167x",
        "kbench_dw_x3d_res3_speedup": 118.167,
        "kbench_pw_x3d_res3_speedup": 1.272,
        "kbench_conv133_sf_res4_speedup": 0.95,
        "kbench_conv311_sf_res4_speedup": 1.169,
        "kbench_error": "kernel parity violation " + "k" * 120,
        "trainer_error": "Traceback (most recent call last):\n" + "e" * 3000,
        "error": "watchdog fired: " + "y" * 3000,
        "data_pipeline": {"decode_clips_per_sec": 62.4, "k": "v" * 300},
        "transport_crossover": {"thread_clips_per_sec": 7.0, "k": "v" * 300},
    }
    out = bench.finalize(models, extras, user_smoke=False)
    line = json.dumps(out)
    assert "\n" not in line
    assert len(line.encode()) <= bench.MAX_LINE_BYTES, len(line.encode())
    parsed = json.loads(line)
    assert parsed["value"] == 100.0
    assert parsed["suspect"] is False
    assert set(parsed["models"]) == set(bench.WORKLOADS)


def test_finalize_obs_keys_ride_the_headline():
    """The telemetry-spine step-time breakdown (obs_step_s /
    obs_input_wait_frac / obs_h2d_s, sourced from the span registry via
    fit()'s perf dict) plumbs through finalize onto the headline line."""
    extras = {"obs_step_s": 0.0123, "obs_input_wait_frac": 0.02,
              "obs_h2d_s": 0.0011}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["obs_step_s"] == 0.0123
    assert out["obs_input_wait_frac"] == 0.02
    assert out["obs_h2d_s"] == 0.0011


def test_finalize_train_recompiles_rides_the_headline():
    """The steady-state recompile count (pva_train_recompiles gauge via
    fit()'s perf dict; analysis/recompile_guard.py) plumbs through
    finalize onto the headline line — the number `--smoke` asserts 0."""
    out = bench.finalize(_model(), {"train_recompiles": 0}, user_smoke=False)
    assert out["train_recompiles"] == 0
    out = bench.finalize(_model(), {"train_recompiles": 3}, user_smoke=False)
    assert out["train_recompiles"] == 3


def test_finalize_tsan_findings_ride_the_headline():
    """The dynamic-sanitizer verdict (pva-tpu-tsan stress pass;
    analysis/tsan.py) plumbs through finalize onto the headline line —
    the number `--smoke` asserts 0."""
    out = bench.finalize(_model(), {"tsan_findings": 0}, user_smoke=False)
    assert out["tsan_findings"] == 0
    out = bench.finalize(_model(), {"tsan_findings": 2}, user_smoke=False)
    assert out["tsan_findings"] == 2


def test_finalize_chaos_findings_ride_the_headline():
    """The resilience verdict (pva-tpu-chaos scenario;
    reliability/chaos.py) plumbs through finalize onto the headline
    line — the number `--smoke` asserts 0 at the gate site."""
    out = bench.finalize(_model(), {"chaos_findings": 0}, user_smoke=False)
    assert out["chaos_findings"] == 0
    out = bench.finalize(_model(), {"chaos_findings": 3}, user_smoke=False)
    assert out["chaos_findings"] == 3


def test_finalize_guard_keys_ride_the_headline():
    """The self-healing-guard verdicts (guard_rollbacks /
    quarantined_clips, sourced from fit()'s perf dict with the guard
    armed in the trainer lane; reliability/guard.py) plumb through
    finalize onto the headline line — the numbers `--smoke` asserts 0."""
    out = bench.finalize(
        _model(), {"guard_rollbacks": 0, "quarantined_clips": 0},
        user_smoke=False)
    assert out["guard_rollbacks"] == 0
    assert out["quarantined_clips"] == 0
    out = bench.finalize(
        _model(), {"guard_rollbacks": 2, "quarantined_clips": 5},
        user_smoke=False)
    assert out["guard_rollbacks"] == 2
    assert out["quarantined_clips"] == 5


def test_finalize_multichip_keys_ride_the_headline():
    """The MULTICHIP scaling lane's verdicts (mesh_parity /
    mesh_ckpt_portable — the numbers `--smoke` asserts true) and its
    clearly-labeled curve (cps/chip + forced_host provenance + per-chip
    MFU) plumb through finalize onto the headline line."""
    extras = {"mesh_parity": True, "mesh_ckpt_portable": True,
              "multichip_cps_per_chip": {"1": 10.0, "8": 9.5},
              "multichip_forced_host": True,
              "multichip_train_recompiles": 0, "multichip_mfu": 0.21}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["mesh_parity"] is True
    assert out["mesh_ckpt_portable"] is True
    assert out["multichip_cps_per_chip"] == {"1": 10.0, "8": 9.5}
    assert out["multichip_forced_host"] is True
    assert out["multichip_train_recompiles"] == 0
    assert out["multichip_mfu"] == 0.21
    # a suspect lane headlines its refusal, never its numbers
    out = bench.finalize(
        _model(), {"mesh_parity": True, "multichip_error": "cpu fallback"},
        user_smoke=False)
    assert out["multichip_error"] == "cpu fallback"
    assert "multichip_cps_per_chip" not in out


def test_finalize_spmdcheck_findings_ride_the_headline():
    """The collective-schedule static verdict (pva-tpu-spmdcheck;
    analysis/spmdcheck.py) plumbs through finalize onto the headline
    line — the number `--smoke` asserts 0 at the gate site."""
    out = bench.finalize(_model(), {"spmdcheck_findings": 0},
                         user_smoke=False)
    assert out["spmdcheck_findings"] == 0
    out = bench.finalize(_model(), {"spmdcheck_findings": 5},
                         user_smoke=False)
    assert out["spmdcheck_findings"] == 5


def test_finalize_spmd_schedule_verdicts_ride_the_headline():
    """The MULTICHIP lane's dynamic schedule verdicts
    (spmd_schedule_divergence — hosts that drifted, asserted 0 — and
    spmd_divergence_detected — the seeded-skew proof the differ is not
    blind, asserted True) plumb through finalize, and like mesh_parity
    they are VERDICTS: a suspect lane's refusal sheds the perf keys but
    never these."""
    extras = {"spmd_schedule_divergence": 0,
              "spmd_divergence_detected": True,
              "multichip_cps_per_chip": {"1": 10.0, "8": 9.5}}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["spmd_schedule_divergence"] == 0
    assert out["spmd_divergence_detected"] is True
    # refusal: perf keys shed, the schedule verdicts retained
    out = bench.finalize(
        _model(), {"spmd_schedule_divergence": 0,
                   "spmd_divergence_detected": True,
                   "multichip_cps_per_chip": {"1": 10.0},
                   "multichip_error": "cpu fallback"},
        user_smoke=False)
    assert out["multichip_error"] == "cpu fallback"
    assert "multichip_cps_per_chip" not in out
    assert out["spmd_schedule_divergence"] == 0
    assert out["spmd_divergence_detected"] is True


def test_finalize_spmd_keys_shed_before_mesh_verdicts():
    """In the size-shed ladder the spmd schedule verdicts drop just
    before the mesh verdicts (first-listed sheds first): a line too fat
    for the capture window keeps mesh_parity longest, and the static
    spmdcheck_findings count is not in the shed ladder at all — it rides
    to the end like the other gate counts."""
    import inspect

    src = inspect.getsource(bench.finalize)
    shed_start = src.index('"trace_overhead_frac", "trace_sampled"')
    i_det = src.index('"spmd_divergence_detected"', shed_start)
    i_div = src.index('"spmd_schedule_divergence"', shed_start)
    i_port = src.index('"mesh_ckpt_portable"', shed_start)
    i_par = src.index('"mesh_parity"', shed_start)
    assert i_det < i_div < i_port < i_par
    assert '"spmdcheck_findings"' not in src[shed_start:]


def test_finalize_pipeline_keys_ride_the_headline():
    """The PIPELINE lane's verdicts (pipeline_parity /
    pipeline_donation_verified / pipeline_train_recompiles — the values
    `--smoke` asserts) and perf keys (pipeline_cps_per_chip, analytic +
    measured bubble fractions, stage count) plumb through finalize; a
    suspect/failed lane headlines pipeline_error INSTEAD of the perf
    keys while the verdicts ride regardless (the multichip/fleet/
    dataplane refusal rule)."""
    extras = {"pipeline_parity": True, "pipeline_donation_verified": True,
              "pipeline_train_recompiles": 0,
              "pipeline_cps_per_chip": 6.195,
              "pipeline_bubble_frac": 0.0171,
              "pipeline_bubble_frac_analytic": 0.2727,
              "pipeline_stages": 4}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["pipeline_parity"] is True
    assert out["pipeline_donation_verified"] is True
    assert out["pipeline_train_recompiles"] == 0
    assert out["pipeline_cps_per_chip"] == 6.195
    assert out["pipeline_bubble_frac"] == 0.0171
    assert out["pipeline_bubble_frac_analytic"] == 0.2727
    assert out["pipeline_stages"] == 4
    # refusal: perf keys shed, verdicts retained
    out = bench.finalize(
        _model(), {"pipeline_parity": True,
                   "pipeline_cps_per_chip": 6.195,
                   "pipeline_error": "cpu fallback"},
        user_smoke=False)
    assert out["pipeline_error"] == "cpu fallback"
    assert out["pipeline_parity"] is True
    assert "pipeline_cps_per_chip" not in out
    assert "pipeline_bubble_frac" not in out


def test_finalize_kbench_keys_ride_the_headline():
    """The kernel-microbench lane's per-kernel speedup keys (the numbers
    pva-tpu-perfdiff attributes wins with), platform label, and parity
    verdict plumb through finalize; raw millisecond timings never do
    (they live in bench_partial.json only — the device-number refusal
    rule applied to kernels), and a failed/parity-broken lane headlines
    kbench_error like the multichip/fleet refusals."""
    extras = {"kbench_platform": "cpu", "kbench_parity_ok": True,
              "kbench_best": "dw_x3d_res3:118.167x",
              "kbench_dw_x3d_res3_speedup": 118.167,
              "kbench_pw_x3d_res3_speedup": 1.272,
              "kbench": {"kernels": {"dw_x3d_res3": {"ms_ref": 1111.7}}}}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["kbench_platform"] == "cpu"
    assert out["kbench_parity_ok"] is True
    assert out["kbench_best"] == "dw_x3d_res3:118.167x"
    assert out["kbench_dw_x3d_res3_speedup"] == 118.167
    assert out["kbench_pw_x3d_res3_speedup"] == 1.272
    assert "kbench" not in out  # the full record (with ms) stays off-line
    out = bench.finalize(_model(), {"kbench_error": "kernel parity "
                                    "violation"}, user_smoke=False)
    assert out["kbench_error"].startswith("kernel parity")


def test_finalize_fleet_lane_keys_ride_the_headline():
    """The SERVE_FLEET lane's four headline keys (achieved rps, p99 under
    open-loop load, hot-swap blackout, shed fraction — the numbers
    `--smoke` asserts) plumb through finalize; a suspect/failed lane
    headlines fleet_error INSTEAD of the numbers (the multichip refusal
    rule)."""
    extras = {"serve_rps": 118.2, "serve_p99_ms_under_load": 42.5,
              "swap_blackout_ms": 7.25, "fleet_shed_frac": 0.031}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["serve_rps"] == 118.2
    assert out["serve_p99_ms_under_load"] == 42.5
    assert out["swap_blackout_ms"] == 7.25
    assert out["fleet_shed_frac"] == 0.031

    out = bench.finalize(
        _model(), {**extras, "fleet_error": "cpu fallback"},
        user_smoke=False)
    assert out["fleet_error"] == "cpu fallback"
    for key in ("serve_rps", "serve_p99_ms_under_load",
                "swap_blackout_ms", "fleet_shed_frac"):
        assert key not in out


def test_finalize_trace_keys_ride_the_headline():
    """The fleet lane's distributed-tracing verdicts (sampled-trace count
    and the tracer's self-measured overhead fraction — `--smoke` asserts
    >=1 and <0.02 respectively) plumb through finalize; a failed/suspect
    fleet lane drops them with the rest of the lane's numbers (they are
    meaningless without the run that produced them)."""
    extras = {"serve_rps": 118.2, "trace_sampled": 42,
              "trace_overhead_frac": 0.0031}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["trace_sampled"] == 42
    assert out["trace_overhead_frac"] == 0.0031

    out = bench.finalize(
        _model(), {**extras, "fleet_error": "cpu fallback"},
        user_smoke=False)
    assert "trace_sampled" not in out
    assert "trace_overhead_frac" not in out


def test_finalize_stream_keys_ride_the_headline():
    """The STREAM lane's headline keys (per-label full/incremental cost
    ratio, exact per-advance H2D byte fraction, label p99 under open-loop
    stream load — the numbers `--smoke` asserts) plumb through finalize
    with the parity/recompile verdicts; a failed, parity-broken, or
    cpu-fallback lane headlines stream_error INSTEAD of the numbers
    while the verdicts ride regardless (the fleet/dataplane refusal
    rule)."""
    extras = {"stream_incremental_speedup": 4.1,
              "stream_h2d_bytes_frac": 0.125,
              "stream_p99_ms": 62.8,
              "stream_parity": True, "stream_recompiles": 0,
              "stream_trunk_speedup": 7.3,
              "stream_trunk_top1_delta": 0.0,
              "stream_trunk_parity": True, "stream_trunk_recompiles": 0}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["stream_incremental_speedup"] == 4.1
    assert out["stream_h2d_bytes_frac"] == 0.125
    assert out["stream_p99_ms"] == 62.8
    assert out["stream_parity"] is True
    assert out["stream_recompiles"] == 0
    assert out["stream_trunk_speedup"] == 7.3
    assert out["stream_trunk_top1_delta"] == 0.0
    assert out["stream_trunk_parity"] is True
    assert out["stream_trunk_recompiles"] == 0

    out = bench.finalize(
        _model(), {**extras, "stream_error": "cpu fallback"},
        user_smoke=False)
    assert out["stream_error"] == "cpu fallback"
    for key in ("stream_incremental_speedup", "stream_h2d_bytes_frac",
                "stream_p99_ms", "stream_trunk_speedup",
                "stream_trunk_top1_delta"):
        assert key not in out
    # verdicts ride the refusal, like pipeline_parity does
    assert out["stream_parity"] is True
    assert out["stream_recompiles"] == 0
    assert out["stream_trunk_parity"] is True
    assert out["stream_trunk_recompiles"] == 0


def test_finalize_fleet_auto_keys_ride_the_headline():
    """The FLEET_AUTO lane's headline keys (autoscaler convergence
    seconds, scale-down drain shed fraction, canary ladder rollbacks,
    model families served under the shared budget — the numbers
    `--smoke` asserts) plumb through finalize with the promoted/
    session-failure verdicts; a failed or cpu-fallback lane headlines
    fleet_auto_error INSTEAD of the numbers while the verdicts ride
    regardless (the fleet/stream refusal rule)."""
    extras = {"autoscale_converge_s": 0.373,
              "fleet_scaledown_shed_frac": 0.0,
              "canary_rollback": 1, "fleet_models_served": 2,
              "canary_promoted": True, "fleet_session_failures": 0}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["autoscale_converge_s"] == 0.373
    assert out["fleet_scaledown_shed_frac"] == 0.0
    assert out["canary_rollback"] == 1
    assert out["fleet_models_served"] == 2
    assert out["canary_promoted"] is True
    assert out["fleet_session_failures"] == 0

    out = bench.finalize(
        _model(), {**extras, "fleet_auto_error": "cpu fallback"},
        user_smoke=False)
    assert out["fleet_auto_error"] == "cpu fallback"
    for key in ("autoscale_converge_s", "fleet_scaledown_shed_frac",
                "canary_rollback", "fleet_models_served"):
        assert key not in out
    # verdicts ride the refusal, like stream_parity does
    assert out["canary_promoted"] is True
    assert out["fleet_session_failures"] == 0


def test_finalize_hbm_and_alert_keys_ride_the_headline():
    """The pva-tpu-hbm keys: the memory-ledger triple (peak bytes,
    attributed fraction, provenance label) plus the burn-rate and
    budget-admission verdicts plumb through finalize — and, being
    verdict-class keys, they ride even a fleet_auto_error refusal (an
    alert false positive on a refused round is still a false positive)."""
    extras = {"hbm_peak_bytes": 283289720, "hbm_attributed_frac": 1.0,
              "hbm_source": "estimate", "alert_false_positives": 0,
              "budget_lies_refused": True,
              "autoscale_converge_s": 0.373}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["hbm_peak_bytes"] == 283289720
    assert out["hbm_attributed_frac"] == 1.0
    assert out["hbm_source"] == "estimate"
    assert out["alert_false_positives"] == 0
    assert out["budget_lies_refused"] is True

    out = bench.finalize(
        _model(), {**extras, "fleet_auto_error": "cpu fallback"},
        user_smoke=False)
    assert "autoscale_converge_s" not in out  # the perf key obeys refusal
    assert out["hbm_source"] == "estimate"
    assert out["alert_false_positives"] == 0
    assert out["budget_lies_refused"] is True


def test_finalize_hbm_shed_order_source_outlives_bytes():
    """In the size-shed ladder the hbm triple drops as a unit-in-reverse:
    the bytes shed before the provenance label that qualifies them — a
    headline must never keep an unlabeled byte count that could read as
    a device claim."""
    import inspect

    src = inspect.getsource(bench.finalize)
    # locate the positions inside the shed tuple specifically (its first
    # member anchors it past the hoist list earlier in the function)
    shed_start = src.index('"trace_overhead_frac", "trace_sampled"')
    i_frac = src.index('"hbm_attributed_frac"', shed_start)
    i_peak = src.index('"hbm_peak_bytes"', shed_start)
    i_src = src.index('"hbm_source"', shed_start)
    assert i_frac < i_peak < i_src
    # and the alert/budget verdicts shed with the FLEET_AUTO group,
    # before any hbm key
    i_alert = src.index('"alert_false_positives"', shed_start)
    i_lies = src.index('"budget_lies_refused"', shed_start)
    assert max(i_alert, i_lies) < i_frac


def test_finalize_stream_trunk_quality_refusal():
    """The trunk-reuse quality gate (docs/SERVING.md § trunk-reuse): a
    round whose top-1 delta breached the gate carries the delta, the
    verdicts, and a truncated stream_trunk_error — and the lane never
    emitted stream_trunk_speedup, so nothing speedup-shaped headlines."""
    extras = {"stream_incremental_speedup": 4.1,
              "stream_parity": True, "stream_recompiles": 0,
              "stream_trunk_top1_delta": 0.31,
              "stream_trunk_parity": True, "stream_trunk_recompiles": 0,
              "stream_trunk_error": "top-1 delta 0.31 breaches " + "q" * 200}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert "stream_trunk_speedup" not in out
    assert out["stream_trunk_top1_delta"] == 0.31
    assert out["stream_trunk_parity"] is True
    assert len(out["stream_trunk_error"]) <= 120
    # the main stream keys are untouched by a trunk-only refusal
    assert out["stream_incremental_speedup"] == 4.1


def test_finalize_stream_keys_shed_order_and_line_budget():
    """The STREAM keys participate in the size-shed ladder (after the
    fleet group, before dataplane/kbench) and the worst-case payload
    still fits the driver's capture window with them present."""
    import json

    models = {}
    for name in bench.WORKLOADS:
        models.update(_model(name))
    extras = {
        "serve_rps": 123.456, "serve_p99_ms_under_load": 87.654,
        "swap_blackout_ms": 12.345, "fleet_shed_frac": 0.0123,
        "stream_incremental_speedup": 4.144,
        "stream_h2d_bytes_frac": 0.125, "stream_p99_ms": 62.75,
        "stream_parity": True, "stream_recompiles": 0,
        "stream_trunk_speedup": 7.345, "stream_trunk_top1_delta": 0.0312,
        "stream_trunk_parity": True, "stream_trunk_recompiles": 0,
        "stream_trunk_error": "top-1 delta breached " + "q" * 200,
        "stream_error": "no trustworthy device numbers " + "s" * 200,
        "dataplane_cps": 49.71, "dataplane_workers": 2,
        "error": "watchdog fired: " + "y" * 3000,
    }
    out = bench.finalize(models, extras, user_smoke=False)
    line = json.dumps(out)
    assert len(line.encode()) <= bench.MAX_LINE_BYTES, len(line.encode())


def test_finalize_serving_lane_keys():
    """The serving smoke's headline keys (p50/p99 latency + fill ratio)
    plumb through finalize; a failed serving lane surfaces as serve_error
    instead of vanishing."""
    extras = {"serving": {"serve_p50_ms": 3.2, "serve_p99_ms": 9.8,
                          "serve_fill_ratio": 0.75, "serve_rps": 120.0}}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["serve_p50_ms"] == 3.2
    assert out["serve_p99_ms"] == 9.8
    assert out["serve_fill_ratio"] == 0.75
    assert "serve_rps" not in out  # detail stays in bench_partial.json

    out = bench.finalize(_model(), {"serving": {"error": "boom"}},
                         user_smoke=False)
    assert out["serve_error"] == "boom"
    assert "serve_p50_ms" not in out


def test_finalize_mfu_analytic_keys_ride_the_headline():
    """The honest-MFU keys (analytic-counter MFU + its provenance label,
    sourced from fit()'s perf dict via the trainer lane;
    analysis/gc_flops.py) plumb through finalize onto the headline line —
    the values `--smoke` asserts non-null."""
    extras = {"mfu_analytic": 0.39, "mfu_source": "analytic",
              "mfu_peak_source": "measured"}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["mfu_analytic"] == 0.39
    assert out["mfu_source"] == "analytic"
    # the denominator's provenance rides too: a measured-peak MFU must
    # never read as a datasheet fraction in an archived round
    assert out["mfu_peak_source"] == "measured"


def test_finalize_graphcheck_findings_ride_the_headline():
    """The compiled-graph verdict (pva-tpu-graphcheck gate at the smoke
    gate site; analysis/graphcheck.py) plumbs through finalize onto the
    headline line — the number `--smoke` asserts 0."""
    out = bench.finalize(_model(), {"graphcheck_findings": 0},
                         user_smoke=False)
    assert out["graphcheck_findings"] == 0
    out = bench.finalize(_model(), {"graphcheck_findings": 4},
                         user_smoke=False)
    assert out["graphcheck_findings"] == 4


def test_finalize_multichip_mfu_analytic_obeys_the_refusal_rule():
    """multichip_mfu_analytic rides with the lane's perf keys and drops
    with them when the lane refuses its numbers (cpu fallback)."""
    extras = {"mesh_parity": True, "multichip_cps_per_chip": {"1": 10.0},
              "multichip_mfu_analytic": 0.21}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["multichip_mfu_analytic"] == 0.21
    out = bench.finalize(
        _model(), {**extras, "multichip_error": "cpu fallback"},
        user_smoke=False)
    assert "multichip_mfu_analytic" not in out


def test_finalize_dataplane_keys_ride_the_headline():
    """The DATA_PLANE lane's headline keys (remote clips/sec, remote
    input-wait fraction, worker count — the numbers `--smoke` asserts)
    plumb through finalize; a failed or parity-broken lane headlines
    dataplane_error INSTEAD of the numbers (the fleet/multichip refusal
    rule)."""
    extras = {"dataplane_cps": 49.7, "dataplane_input_wait_frac": 0.31,
              "dataplane_workers": 2}
    out = bench.finalize(_model(), extras, user_smoke=False)
    assert out["dataplane_cps"] == 49.7
    assert out["dataplane_input_wait_frac"] == 0.31
    assert out["dataplane_workers"] == 2

    out = bench.finalize(
        _model(),
        {**extras, "dataplane_error": "remote batch stream diverged"},
        user_smoke=False)
    assert out["dataplane_error"] == "remote batch stream diverged"
    for key in ("dataplane_cps", "dataplane_input_wait_frac",
                "dataplane_workers"):
        assert key not in out


def test_finalize_suspect_round_sheds_flagship_device_perf_keys():
    """Round-5 regression: a suspect round (a CPU run) headlined a
    literal `"tflops_per_sec": 0.0` beside `suspect: true` — a zero that
    pva-tpu-perfdiff could one day diff against a real device number.
    Suspect rounds must shed the flagship's device-shaped perf keys
    (tflops_per_sec, step_ms_blocked) under the same refusal rule the
    lane keys obey; a trusted round keeps them."""
    trusted = bench.finalize(_model(), {}, user_smoke=False)
    assert trusted["tflops_per_sec"] == 50.0
    assert trusted["step_ms_blocked"] == 10.0

    suspect = bench.finalize(
        _model(platform="cpu", smoke=True,
               tflops_per_sec_per_chip=0.0, suspect=True),
        {}, user_smoke=False)
    assert suspect["suspect"] is True
    assert "tflops_per_sec" not in suspect
    assert "step_ms_blocked" not in suspect
    # the child-flagged suspect shape (device round that self-flagged)
    # sheds too, independent of the cpu-fallback detector
    suspect2 = bench.finalize(_model(suspect=True), {}, user_smoke=False)
    assert "tflops_per_sec" not in suspect2

"""bench.py parent orchestration: every device bench runs in a child, a
non-smoke child that misses the TPU or fails ends the run without a
headline, the parent itself stays on the CPU backend, trainer-mode
selection — locked with fake children (no jax, no subprocesses)."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location(
        "bench_orch", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_orch"] = mod
    spec.loader.exec_module(mod)
    # keep artifacts out of the repo root
    monkeypatch.setattr(mod, "HERE", str(tmp_path))
    # main() hard-exits after the JSON line. Patch _exit to RAISE (confined
    # to _run_main's catch) rather than no-op: a no-op would disable
    # os._exit process-wide for anything else running during the test and
    # couldn't detect main() dropping the call.
    monkeypatch.setattr(mod.os, "_exit",
                        lambda code: (_ for _ in ()).throw(_ExitCalled(code)))
    mod.setup_jax_calls = []
    monkeypatch.setattr(mod, "_setup_jax",
                        lambda smoke: mod.setup_jax_calls.append(smoke))
    return mod


class _ExitCalled(BaseException):
    def __init__(self, code):
        self.code = code


def _fake_child(calls, device_results=None):
    """run_child stub: records (target, smoke) and returns a canned result."""
    device_results = device_results or {}

    def run_child(target, args, smoke, timeout):
        calls.append((target, bool(smoke)))
        if target == "__trainer__":
            return {"trainer_cps_chip": 10.0, "smoke": bool(smoke)}
        if smoke:
            return {"clips_per_sec_per_chip": 1.0, "platform": "cpu",
                    "smoke": True, "frames": 8, "crop": 64}
        return device_results.get(target) or {
            "clips_per_sec_per_chip": 50.0, "platform": "tpu",
            "smoke": False, "frames": 32, "crop": 256}

    return run_child


def _run_main(bench, monkeypatch, argv, calls, device_results=None):
    """Drive bench.main() over fake children. Returns (exit, stdout): the
    argument of the exit main() left through — os._exit(code) after a
    headline, sys.exit(message) without one — and what it printed."""
    monkeypatch.setattr(bench, "run_child",
                        _fake_child(calls, device_results))
    # --no-dataplane: that lane spawns real decode-worker SUBPROCESSES in
    # the parent (this module's contract is fake children only); its
    # finalize plumbing is locked by test_bench_contract instead. The
    # in-parent serving smoke compiles a model per main() call and is
    # locked by tests/test_zserving.py
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--no-data", "--no-dataplane",
                         "--no-serve-smoke"] + argv)
    import io
    import contextlib

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        try:
            bench.main()
            raise AssertionError("main() returned without exiting")
        except _ExitCalled as e:
            code = e.code
        except SystemExit as e:
            code = e.code
    return code, buf.getvalue()


def _headline(bench, code, stdout):
    assert code == 0, code
    line = stdout.strip().splitlines()[-1]
    # the driver's stdout tail capture is ~2000 bytes: every orchestration
    # path must produce a line that survives it
    assert len(line.encode()) <= bench.MAX_LINE_BYTES, len(line.encode())
    return json.loads(line)


def _detail(bench):
    """The full record (per-model dicts) that the compact line points at
    via "detail": bench_partial.json."""
    with open(os.path.join(bench.HERE, "bench_partial.json")) as f:
        return json.load(f)


def test_healthy_device_runs_everything_on_device(bench, monkeypatch):
    calls = []
    out = _headline(bench, *_run_main(
        bench, monkeypatch, ["--models", "slowfast_r50,x3d_s"], calls))
    assert out["value"] == 50.0
    assert "error" not in out
    assert ("slowfast_r50", False) in calls and ("x3d_s", False) in calls
    # trainer compared same-mode (device)
    assert ("__trainer__", False) in calls


def test_non_smoke_child_on_cpu_fails_the_run_without_headline(
        bench, monkeypatch):
    """No hidden CPU: a device bench whose child reports the CPU platform
    is not re-labelled or retried — the run exits non-zero, prints no
    headline, and no smoke stand-in is ever started."""
    calls = []
    code, stdout = _run_main(
        bench, monkeypatch, ["--models", "slowfast_r50,x3d_s"], calls,
        device_results={"slowfast_r50": {
            "clips_per_sec_per_chip": 1.0, "platform": "cpu",
            "smoke": False, "frames": 32, "crop": 256}})
    assert code not in (0, None)
    assert stdout.strip() == ""
    assert calls == [("slowfast_r50", False)]  # nothing ran after it
    assert _detail(bench)["results"]["slowfast_r50"]["platform"] == "cpu"


def test_child_error_is_recorded_and_the_run_fails(bench, monkeypatch):
    calls = []
    code, stdout = _run_main(
        bench, monkeypatch, ["--models", "slowfast_r50,x3d_s"], calls,
        device_results={"slowfast_r50": {"error": "child timeout after 900s",
                                         "smoke": False}})
    assert code not in (0, None)
    assert stdout.strip() == ""
    assert not any(smoke for _, smoke in calls)  # no smoke fallback
    results = _detail(bench)["results"]
    assert results["slowfast_r50"]["error"] == "child timeout after 900s"
    assert "x3d_s" not in results


def test_parent_never_initialises_a_non_cpu_backend(bench, monkeypatch):
    """The chip belongs to one process: the parent's only backend set-up is
    the CPU pin, and every device-facing target goes through run_child."""
    calls = []
    _headline(bench, *_run_main(
        bench, monkeypatch, ["--models", "slowfast_r50"], calls))
    assert bench.setup_jax_calls == [True]  # _setup_jax(smoke=True) once
    targets = {t for t, _ in calls}
    assert {"slowfast_r50", "__trainer__", "__multichip__", "__pipeline__",
            "__fleet__", "__fleet_auto__", "__stream__",
            "__kbench__"} <= targets
    # a lane child that found no TPU ends the run the same way
    no_tpu = {"error": "child exited 3", "smoke": False,
              "returncode": bench.NO_TPU_EXIT}
    calls.clear()
    code, stdout = _run_main(
        bench, monkeypatch, ["--models", "slowfast_r50"], calls,
        device_results={"__kbench__": no_tpu})
    assert code not in (0, None) and stdout.strip() == ""


def test_trainer_skipped_model_list_still_uses_device(bench, monkeypatch):
    calls = []
    out = _headline(bench, *_run_main(
        bench, monkeypatch, ["--models", "x3d_s"], calls))
    # no slowfast result exists; trainer must still run on the healthy
    # device, not silently in smoke mode
    assert ("__trainer__", False) in calls
    assert "trainer_cps_chip" in out
    assert "trainer_vs_rawstep" not in out  # no same-mode flagship to compare

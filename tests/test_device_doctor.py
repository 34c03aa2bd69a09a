"""Device doctor (utils/device_doctor.py): reachability probe, subprocess
attempt harness (stderr survives the kill), and the Trainer's
fail-loudly-instead-of-wedging guard (SURVEY §5 failure detection)."""

import os
import sys

import pytest

from pytorchvideo_accelerate_tpu.utils import device_doctor as dd


def test_env_snapshot_filters_device_vars(monkeypatch):
    monkeypatch.setenv("TPU_FAKE_TEST_VAR", "1")
    monkeypatch.setenv("UNRELATED_VAR", "x")
    snap = dd.env_snapshot()
    assert snap.get("TPU_FAKE_TEST_VAR") == "1"
    assert "UNRELATED_VAR" not in snap


def test_probe_landing_on_cpu_unasked_is_refused(monkeypatch):
    """No hidden CPU: a probe that came back from the CPU backend is a
    failure unless JAX_PLATFORMS=cpu asked for the CPU."""
    monkeypatch.setattr(dd, "quick_probe",
                        lambda t: {"ok": True, "elapsed_s": 1.0,
                                   "stdout": "cpu cpu"})
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="landed on the CPU"):
        dd.assert_device_reachable(30, log=lambda m: None)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert dd.assert_device_reachable(30, log=lambda m: None)["ok"] is True


def test_attempt_captures_output_on_success(tmp_path):
    code = ("import sys\n"
            "print('to stdout')\n"
            "print('to stderr', file=sys.stderr)\n")
    rec = dd._attempt(code, dict(os.environ), 30,
                      str(tmp_path / "err.txt"))
    assert rec["ok"] is True
    assert "to stdout" in rec["stdout"]
    assert "to stderr" in rec["stderr_tail"]


def test_attempt_preserves_stderr_across_timeout_kill(tmp_path):
    # the case the file redirect exists for: the child hangs, gets
    # SIGKILLed, and whatever it said before hanging must survive
    code = ("import sys, time\n"
            "print('pre-hang diagnostic', file=sys.stderr, flush=True)\n"
            "time.sleep(60)\n")
    rec = dd._attempt(code, dict(os.environ), 3, str(tmp_path / "err.txt"))
    assert rec["ok"] is False
    assert rec["error"] == "timeout (killed)"
    assert rec["elapsed_s"] < 30
    assert "pre-hang diagnostic" in rec["stderr_tail"]


def test_assert_device_reachable_passes_through_ok(monkeypatch):
    monkeypatch.setattr(dd, "quick_probe",
                        lambda t: {"ok": True, "elapsed_s": 1.0,
                                   "stdout": "tpu TPU v5 lite"})
    rec = dd.assert_device_reachable(30, log=lambda m: None)
    assert rec["ok"] is True


def test_assert_device_reachable_raises_with_recipe(monkeypatch):
    monkeypatch.setattr(dd, "quick_probe",
                        lambda t: {"ok": False,
                                   "error": "timeout (killed)"})
    with pytest.raises(RuntimeError) as e:
        dd.assert_device_reachable(30, log=lambda m: None)
    msg = str(e.value)
    assert "pva-tpu-doctor" in msg       # the diagnosis recipe
    assert "--device_init_timeout" in msg  # and the escape hatch


def test_trainer_guard_fails_loudly_not_hanging(monkeypatch, tmp_path):
    """--device_init_timeout turns a would-be wedge into a RuntimeError
    before the Trainer touches devices."""
    from pytorchvideo_accelerate_tpu.config import parse_cli
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    calls = []

    def fake_assert(timeout_s, log=None):
        calls.append(timeout_s)
        raise RuntimeError("device backend init did not complete")

    monkeypatch.setattr(dd, "assert_device_reachable", fake_assert)
    cfg = parse_cli([
        "--model.name", "tiny3d", "--synthetic",
        "--data.num_frames", "4", "--data.crop_size", "32",
        "--data.batch_size", "1",
        "--device_init_timeout", "7",
        "--checkpoint.output_dir", str(tmp_path),
    ])
    with pytest.raises(RuntimeError, match="did not complete"):
        Trainer(cfg)
    assert calls == [7]


def test_cli_skip_init_exits_zero(capsys):
    rc = dd.main(["--skip-init"])
    assert rc == 0
    import json

    rec = json.loads(capsys.readouterr().out)
    assert rec["probe"] == "diagnostics"
    assert "env" in rec and "files" in rec

"""What `Trainer.fit()` returns, and how often it traces its step.

The keys of the result are an interface: the benchmark's jobs read
`steps` and `train_recompiles` (`benchmarks/jobs/train_fit.py`),
docs/OBSERVABILITY.md and docs/INPUT_PIPELINE.md tell an operator to read
the others. `fit()` counts no FLOPs and reports no utilization (the
benchmark owns that count: `benchmarks/lib/flops.py`), so the keys that
did are asserted absent, and the train step is traced, lowered and
compiled exactly once in a run.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRESENT = (
    "steps", "train_recompiles", "input_wait_s", "input_wait_frac",
    "step_records", "epoch_train_times", "preempted", "train_loss",
    "val_accuracy", "val_accuracy_top5", "steps_per_sec", "clips_per_sec",
    # the run below is guard-armed: a clean run reads 0 for both
    "guard_rollbacks", "quarantined_clips",
)
ABSENT = (
    "mfu", "mfu_analytic", "mfu_source", "mfu_peak_source",
    "tflops_per_sec_per_chip", "flops_per_step", "analytic_flops_per_step",
    "obs_step_s", "obs_input_wait_frac", "obs_h2d_s",
)


@pytest.fixture(scope="module")
def fit_result(tmp_path_factory):
    """ONE guard-armed `fit()` of a tiny-depth slow_r50 (the
    tests/test_zobs.py idiom) on synthetic clips."""
    from pytorchvideo_accelerate_tpu import models
    from pytorchvideo_accelerate_tpu.config import parse_cli
    from pytorchvideo_accelerate_tpu.models.resnet3d import SlowR50
    from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

    def tiny(cfg, dtype, mesh=None):
        return SlowR50(num_classes=cfg.num_classes, depths=(1, 1, 1, 1),
                       stem_features=8, dropout_rate=cfg.dropout_rate,
                       dtype=dtype)

    out = tmp_path_factory.mktemp("fit_result")
    cfg = parse_cli([
        "--data.synthetic", "--data.synthetic_num_videos", "16",
        "--data.num_frames", "4", "--data.crop_size", "32",
        "--data.min_short_side_scale", "32",
        "--data.max_short_side_scale", "40",
        "--data.batch_size", "1", "--data.num_workers", "2",
        "--data.limit_val_batches", "1",
        "--model.name", "slow_r50", "--model.num_classes", "4",
        "--optim.num_epochs", "1", "--optim.lr", "0.01",
        "--optim.weight_decay", "0", "--model.dropout_rate", "0",
        "--guard.enabled", "true",
        "--checkpoint.output_dir", str(out),
    ])
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(models._REGISTRY, "slow_r50", tiny)
        mp.chdir(out)
        return Trainer(cfg).fit()


@pytest.mark.parametrize("key", PRESENT)
def test_fit_result_keys(fit_result, key):
    assert key in fit_result, sorted(fit_result)
    value = fit_result[key]
    if key == "steps":
        assert value == 2  # 16 clips over a global batch of 8
    elif key in ("train_recompiles", "guard_rollbacks",
                 "quarantined_clips"):
        assert value == 0
    elif key == "input_wait_frac":
        assert 0.0 <= value <= 1.0
    elif key == "step_records":
        assert [r["gstep"] for r in value] == [0, 1]
    elif key == "epoch_train_times":
        assert len(value) == 1 and value[0] > 0.0
    elif key == "preempted":
        assert value is False
    elif key in ("steps_per_sec", "clips_per_sec"):
        assert value > 0.0
    else:
        assert np.isfinite(value)


@pytest.mark.parametrize("key", ABSENT)
def test_fit_result_has_no_flops_or_epoch_wide_obs_key(fit_result, key):
    assert key not in fit_result, sorted(fit_result)


_CONFIGS = {
    "clip": {
        "model": {"name": "tiny3d", "num_classes": 4},
        "data": {"synthetic": True, "synthetic_num_videos": 4,
                 "num_frames": 4, "crop_size": 32,
                 "min_short_side_scale": 32, "max_short_side_scale": 40,
                 "batch_size": 2, "num_workers": 2, "limit_val_batches": 0},
        "optim": {"num_epochs": 1, "lr": 0.01},
    },
    "token": {
        "model": {"name": "qwen3_next_t", "experts_held": 2,
                  "expert_offset": 2},
        "data": {"synthetic": True, "seq_len": 64, "batch_size": 2,
                 "synthetic_num_videos": 4, "num_workers": 2,
                 "limit_val_batches": 0},
        "optim": {"optimizer": "adamw", "lr": 3e-3, "num_epochs": 1},
        "mixed_precision": "fp32",
    },
}

_COUNT_DRIVER = """
import json, sys
sys.path.insert(0, {root!r})
import jax

events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, fun_name="?", **_: events.append((name, fun_name)))

from pytorchvideo_accelerate_tpu.config import config_from_dict
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer

cfg = config_from_dict(dict({cfg!r}, checkpoint={{"output_dir": {out!r}}}))
fit = Trainer(cfg).fit()
stage = "/jax/core/compile/{{}}_duration".format
print(json.dumps({{
    "steps": fit["steps"], "train_recompiles": fit["train_recompiles"],
    "traced": events.count((stage("jaxpr_trace"), "step")),
    "lowered": events.count((stage("jaxpr_to_mlir_module"), "jit(step)")),
    "compiled": events.count((stage("backend_compile"), "jit(step)"))}}))
"""


@pytest.mark.parametrize("kind", sorted(_CONFIGS))
def test_fit_lowers_its_step_once(kind, tmp_path):
    """Over a `fit()` of two steps the train step (`step` of
    trainer/steps.py, whatever the task) is traced, lowered and compiled
    once each, counted from `jax.monitoring`'s events as
    `benchmarks/lib/compile_counters.py` counts compiles; in a process of
    its own (one CPU device, as a one-chip run has)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, "-c", _COUNT_DRIVER.format(
            root=ROOT, cfg=_CONFIGS[kind], out=str(tmp_path))],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"steps": 2, "train_recompiles": 0, "traced": 1,
                   "lowered": 1, "compiled": 1}

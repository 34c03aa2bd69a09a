"""`correct` has to be able to come out false.

* the control: the plain reference put in the program's place and computed in
  the nearest precision below the one the (toy) configuration states, judged
  exactly as the program is, comes out not correct; the reference itself, in
  the same seat, comes out correct;
* (slow) the whole command at the toy geometry, the chip check lifted, with the
  timed path broken underneath: a step that returns its state unchanged, and
  half of the batch left out with the mean taken over the rest.
"""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import compare
from benchmarks.lib.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "slowfast_r50.train"


def _toy():
    spec = Spec(ROOT)
    cell = spec.cell(CELL)
    config = spec.config(cell["config"])
    arch = {**config["arch"], **config["rehearse"]["arch"]}
    return cell, config, arch


def test_control_in_lower_precision_is_not_correct():
    import jax

    cell, config, arch = _toy()
    limits = cell["rehearse"]["limits"]
    assert config["rehearse"]["control_dtype"] == "bfloat16"  # below float32
    r = np.random.default_rng(0)
    batches = [{"fast": r.standard_normal((4, 8, 64, 64, 3)).astype(np.float32),
                "label": r.integers(0, 5, 4).astype(np.int32)} for _ in range(3)]
    optim = {"lr": 0.001, "momentum": 0.9, "weight_decay": 1e-4, "total_steps": 2048}
    device = jax.devices()[0]
    for seed in (1,):  # the chip runs of PERF.md cover three seeds at the cell's size
        ref = compare.follow_reference(config["family"], arch, optim, seed,
                                       batches, device)
        control = compare.stand_in_numbers(
            config["family"], arch, optim, seed, batches, ref, limits, device,
            q=config["rehearse"]["control_dtype"])
        assert not all(n["ok"] for n in control), control
        half = compare.stand_in_numbers(
            config["family"], arch, optim, seed, batches, ref, limits, device,
            fault="half_batch")
        assert not all(n["ok"] for n in half), half
    same = compare.judge(ref["losses"], ref["grad_norms"], ref["delta_norms"],
                         ref, limits, {"duplicate_rows": 0})
    assert all(n["ok"] for n in same)


def test_judge_catches_each_reading():
    ref = {"losses": [2.0, 2.0], "grad_norms": {"a": 1.0, "b": 1e-9, "c": 2.0},
           "delta_norms": {"a": 0.1, "b": 0.5, "c": 0.2},
           "sizes": {"a": 8, "b": 8, "c": compare.WIDE}}
    limits = {"loss_rel_step2": 0.01, "grad_gap_median": 0.1,
              "delta_gap_median": 0.1, "delta_gap_worst_wide": 0.3}

    def verdict(losses, grad, delta, **structure):
        return {n["name"]: n for n in
                compare.judge(losses, grad, delta, ref, limits, structure)}

    sound = verdict([2.5, 2.001], dict(ref["grad_norms"]),
                    {"a": 0.1, "b": 0.0, "c": 0.2}, recompiles=0)
    # leaf b's gradient is nought in the reference: its change is not compared;
    # step 1's loss has no limit here: it is shown and decides nothing
    assert all(n["ok"] for n in sound.values())
    assert sound["loss_rel_step1"]["limit"] is None
    assert sound["delta_gap_median"]["note"] == "1 leaves left out"
    bad = lambda *a, **k: {k2 for k2, n in verdict(*a, **k).items() if not n["ok"]}
    assert bad([2.0, 2.1], ref["grad_norms"], ref["delta_norms"]) == {"loss_rel_step2"}
    assert bad([2.0, None], ref["grad_norms"], ref["delta_norms"]) == {"loss_rel_step2"}
    # a state returned unchanged: no leaf moved; the worst leaf reads 1 and so
    # does every leaf at or above the median leaf (here a reads 0.1/0.15)
    unchanged = verdict([2.0, 2.0], ref["grad_norms"], {"a": 0, "b": 0, "c": 0})
    assert unchanged["delta_gap_worst"]["value"] == 1.0
    assert unchanged["delta_gap_median"]["value"] == pytest.approx((1 + 2 / 3) / 2)
    assert not unchanged["delta_gap_median"]["ok"]
    # every leaf moved double
    assert bad([2.0, 2.0], ref["grad_norms"], {"a": 0.2, "b": 1.0, "c": 0.4}) == {
        "delta_gap_median", "delta_gap_worst_wide"}
    assert bad([2.0, 2.0], {"a": 1.5, "b": 0.5, "c": 3.0}, ref["delta_norms"]) == {"grad_gap_median"}
    # one leaf far off moves the worst gap, which is shown, not the median
    one = verdict([2.0, 2.0], {"a": 1.0, "b": 1e-9, "c": 4.0}, ref["delta_norms"])
    assert one["grad_gap_worst"]["value"] == 1.0
    assert one["grad_gap_worst"]["note"].startswith("c (")
    assert all(n["ok"] for n in one.values())
    # a small leaf left unmoved is the small leaves' noise to the wide number;
    # the one wide leaf left unmoved, or moved double, is caught by it alone
    wide = "delta_gap_worst_wide"
    assert wide not in bad([2.0, 2.0], ref["grad_norms"], {"a": 0.0, "b": 0.5, "c": 0.2})
    assert wide in bad([2.0, 2.0], ref["grad_norms"], {"a": 0.1, "b": 0.5, "c": 0.0})
    assert wide in bad([2.0, 2.0], ref["grad_norms"], {"a": 0.1, "b": 0.5, "c": 0.4})
    assert bad([2.0, 2.0], ref["grad_norms"], ref["delta_norms"], recompiles=1) == {"recompiles"}
    assert bad([2.0, 2.0], ref["grad_norms"], ref["delta_norms"], recompiles=None) == {"recompiles"}


def test_gaps_by_size_class():
    sizes = {"a": 8, "b": 100, "c": compare.WIDE, "d": 10 ** 6}
    ref = {"a": 1.0, "b": 1.0, "c": 1.0, "d": 1.0}
    rows = compare.gaps_by_size({"a": 2.0, "b": 1.2, "c": 1.05, "d": 1.0}, ref, sizes)
    assert [(n, round(w, 3)) for _lo, _hi, n, w, _m in rows] == [
        (1, 1.0), (1, 0.2), (1, 0.05), (1, 0.0)]


def _placed(fast, alpha=4):
    from benchmarks.reference import slowfast

    return {"fast": fast, "slow": slowfast.slow_frames(fast, alpha),
            "label": np.arange(fast.shape[0], dtype=np.int32)}


def _expect(shape, alpha=4):
    from benchmarks.reference import slowfast

    b, t, s = shape[0], shape[1], shape[2]
    return {"shapes": slowfast.expected_inputs({"alpha": alpha}, b, t, s),
            "dtype": "float32", "num_classes": 5, "low": -2.0, "high": -1.0,
            "mean": -1.5,
            "derive": lambda batch: slowfast.derived_inputs(batch, {"alpha": alpha})}


def test_placed_batch_is_checked():
    r = np.random.default_rng(0)
    fast = r.uniform(-2.0, -1.0, (2, 8, 16, 16, 3)).astype(np.float32)
    sound = compare.input_numbers([_placed(fast)], _expect(fast.shape))
    assert sound["input_shape_gap"] == 0 and sound["input_range_out"] == 0
    assert sound["input_derived_gap"] == 0.0 and sound["input_mean_gap"] < 0.03
    # the slow pathway cut at other frames than the reference's own cut
    wrong = _placed(fast)
    wrong["slow"] = fast[:, ::4]
    assert compare.input_numbers([wrong], _expect(fast.shape))["input_derived_gap"] > 0
    # clips that were not normalised: outside the range, and the mean far off
    raw = compare.input_numbers([_placed(fast + 1.6)], _expect(fast.shape))
    assert raw["input_range_out"] > 0 and raw["input_mean_gap"] > 1.0
    # another type, another crop, a label outside the classes
    assert compare.input_numbers([_placed(fast.astype(np.float16))],
                                 _expect(fast.shape))["input_shape_gap"] == 2
    assert compare.input_numbers([_placed(fast[:, :, :8])],
                                 _expect(fast.shape))["input_shape_gap"] == 2
    off = _placed(fast)
    off["label"] = np.array([0, 5], np.int32)
    assert compare.input_numbers([off], _expect(fast.shape))["input_range_out"] == 1


def test_slow_pathway_is_the_truncated_linspace():
    from benchmarks.reference import slowfast

    fast = np.arange(32, dtype=np.float32).reshape(1, 32, 1, 1, 1)
    assert slowfast.slow_frames(fast, 4).ravel().tolist() == [
        0, 4, 8, 13, 17, 22, 26, 31]


def test_duplicate_rows_are_counted():
    a = np.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 3, 4)
    assert compare.duplicate_rows([{"video": a}]) == 0
    assert compare.duplicate_rows([{"video": a}, {"video": a[:1]}]) == 1


_DRIVER = """
import sys
sys.path.insert(0, {root!r})
import jax
from pytorchvideo_accelerate_tpu.trainer import loop

def state_unchanged(step):
    def broken(state, batch, key):
        new, metrics = step(state, batch, key)
        return state.replace(step=new.step), metrics
    return jax.jit(broken)

def half_batch(step):
    def broken(state, batch, key):
        half = {{k: v[: v.shape[0] // 2] for k, v in batch.items()}}
        return step(state, half, key)
    return jax.jit(broken)

fault = {fault!r}
if fault:
    real = loop.make_train_step
    loop.make_train_step = lambda *a, **k: globals()[fault](real(*a, **k))
from benchmarks import run
run.main(["--workload", {cell!r}, "--seed", "5", "--seconds", "1",
          "--trace", "0", "--rehearse"])
"""


def _rehearse(fault=None, cell=CELL):
    """The whole command at the toy geometry in a process of its own (one CPU
    device, as the cell's mesh wants), the look for a chip lifted, with the
    trainer's step builder wrapped so that the timed path is broken."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER.format(root=ROOT, fault=fault, cell=cell)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CELLS = [CELL, "slowfast_r50.train_resident"]  # one job, two feeds of the window


@pytest.mark.slow
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_a_sound_run_is_correct(cell):
    line = _rehearse(cell=cell)
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"


@pytest.mark.slow
@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(fault, cell):
    line = _rehearse(fault, cell)
    assert line["correct"] is False, line["compared"]
    if fault == "state_unchanged":
        # no leaf moved: the change reads 1 by the measure, on any seed
        assert line["compared"]["delta_gap_median"]["value"] > 0.9

"""The harness is driven by data: a cell, a configuration, a job kind and a
per-layer metric added as NEW files (plus appended `BENCHMARK.json` entries)
run through the one command without an edit to any file that was there. Also
the contract's shape of `BENCHMARK.json` and of the result line."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib.spec import Spec, quantity

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _tree_hashes(root):
    out = {}
    for base, _dirs, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_config_job_and_metric_are_found_by_name(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "pytorchvideo_accelerate_tpu"),
               copy / "pytorchvideo_accelerate_tpu")
    before = _tree_hashes(copy / "benchmarks")

    # what a later PR brings: four new files ...
    (copy / "benchmarks" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "source": "none", "answer": 42}))
    (copy / "benchmarks" / "workloads" / "toy.echo.json").write_text(json.dumps(
        {"config": "toy", "job": "echo", "chips": 1, "repeat": 3}))
    (copy / "benchmarks" / "jobs" / "echo.py").write_text(
        "def run(ctx):\n"
        "    n = ctx['cell']['repeat'] * ctx['config']['answer']\n"
        "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
        "            'end_to_end': {'setup_s': 1.5, 'echo_per_s': 7.0},\n"
        "            'results': {'n': n}, 'memory_peak_bytes': 0,\n"
        "            'device_extra': {}, 'breakdown': None,\n"
        "            'compared': [{'name': 'echo_gap', 'value': 0.0, 'limit': 0,\n"
        "                          'ok': True, 'note': ''}]}\n")
    (copy / "benchmarks" / "metrics" / "echo_count.py").write_text(
        "def read(results):\n    return results['n']\n")
    (copy / "benchmarks" / "metrics" / "echo_nothing.py").write_text(
        "def read(results):\n    return None\n")
    # ... and appended entries
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "toy", "source": "none",
                           "file": "benchmarks/configs/toy.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "toy.echo", "config": "toy",
                             "traffic": "echo", "chips": 1, "why": "test"})
    doc["end_to_end"].append({"name": "echo_per_s", "unit": "1/s", "better": "higher",
                              "bound": 0.05, "source": "host_clock",
                              "workloads": ["toy.echo"]})
    for name in ("echo_count", "echo_nothing"):
        doc["per_layer"].append({"name": name, "unit": "1", "better": "higher",
                                 "source": "program_counter", "layer": "device",
                                 "moves": "echo_per_s", "workloads": ["toy.echo"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)

    def run(trace):
        proc = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", "toy.echo",
             "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", str(trace),
             "--rehearse"], cwd=copy, env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr

    line, err = run(0)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["attempted"] == 126 and line["failed"] == 0
    # only this cell's end-to-end metrics, with their units
    assert line["metrics"] == {"setup_s": {"value": 1.5, "unit": "s"},
                               "echo_per_s": {"value": 7.0, "unit": "1/s"}}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["rehearsal"] is True
    assert line["compared"] == {"echo_gap": {"value": 0.0, "limit": 0}}
    assert "compared echo_gap value 0.0 limit 0 ok" in err.strip().splitlines()[-1]

    line, _ = run(1)
    # the reader that found nothing is left out, not reported as 0
    assert line["metrics"] == {"echo_count": {"value": 126, "unit": "1"}}

    after = _tree_hashes(copy / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "x3d_s.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_benchmark_alone_in_a_directory_gives_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "x3d_s.train",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and "{" not in proc.stdout


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    # a full check with 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [c["name"] for c in doc["configs"]]
    assert len(set(names)) == len(names)
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith(tuple(doc["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    cells = [w["name"] for w in doc["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in doc["workloads"]}) == len(cells)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "workloads",
                                           w["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # a roofline or mfu share is a percentage
    for m in doc["per_layer"]:
        if quantity(m["name"]).endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # one quantity, several metrics: each moves the end-to-end metric of its
    # own cells, and every cell reports what its per-layer metrics move
    for m in doc["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


def test_every_per_layer_metric_has_a_reader(doc):
    from benchmarks.lib.spec import SpecError, metric_module

    for m in doc["per_layer"]:
        assert callable(metric_module(m["name"]).read)
    # `<quantity>.<regime>` is read by its quantity's file
    assert metric_module("step_mfu.some_later_regime") is metric_module("step_mfu")
    with pytest.raises(SpecError):
        metric_module("no_such_quantity.device_paced")


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from benchmarks.lib.spec import metric_module

    empty = {"trace": None, "work": None, "peaks": None, "spans": {},
             "window_wait_s": None, "window_s": 1.0, "steps": 0, "chips": 1,
             "memory_peak_bytes": 0,
             "global_batch": 8, "compile": {"compile_s": 0.0}}
    for name in ("input_wait_share", "dispatch_ms_per_step", "step_gap_p95_ms",
                 "device_step_ms", "step_mfu", "conv_roofline",
                 "depthwise_roofline", "device_idle_share", "peak_hbm_bytes"):
        assert metric_module(name).read(empty) is None, name


def _regime_cells(doc):
    """{regime: cells} from the end-to-end `clips_per_s_per_chip.<regime>`
    lists: the suffix names the bound a cell's measured spread needs."""
    return {m["name"].split(".", 1)[1]: m["workloads"] for m in doc["end_to_end"]
            if quantity(m["name"]) == "clips_per_s_per_chip"}


def _one_regime_a_cell(doc, regimes, cells):
    for cell in cells:
        assert [r for r, listed in regimes.items() if cell in listed] \
            in (["device_paced"], ["host_paced"]), cell


def _per_layer_in_its_regime(doc, regimes, cells):
    for m in doc["per_layer"]:
        if "." in m["name"]:
            regime = m["name"].split(".", 1)[1]
            assert m["moves"] == f"clips_per_s_per_chip.{regime}"
            assert set(m["workloads"]) <= set(regimes[regime]), m["name"]


def _no_empty_list(doc, regimes, cells):
    for m in doc["end_to_end"] + doc["per_layer"]:
        listed = m.get("workloads", cells)
        assert listed and len(set(listed)) == len(listed), m["name"]


def _moved_cell_keeps_its_readers(doc, regimes, cells):
    # no quantity is read twice in a cell, and the two SlowFast cells share
    # every quantity but the two that read the prefetcher
    spec = Spec(ROOT)
    read = {}
    for cell in cells:
        names = spec.metric_names("per_layer", cell)
        read[cell] = {quantity(n) for n in names}
        assert len(read[cell]) == len(names), cell
    loader, resident = read["slowfast_r50.train"], read["slowfast_r50.train_resident"]
    assert loader - resident == {"input_wait_share", "prefetch_ready_at_pop"}
    assert resident <= loader


def _resident_cell_loads(doc, regimes, cells):
    cell = Spec(ROOT).cell("slowfast_r50.train_resident")
    assert cell["job"] == "train_fit" and cell["resident_batches"] == 8
    assert cell["chips"] == 1 and cell["config"] == "slowfast_r50"
    # the loader's cell, but for the key: same stream, steps and limits
    other = Spec(ROOT).cell("slowfast_r50.train")
    for key in ("train_config", "clips", "check_steps", "warmup_steps",
                "trace_seconds", "limits"):
        assert cell[key] == other[key], key
    assert "resident_batches" not in other
    assert cell["resident_batches"] <= cell["check_steps"] + cell["warmup_steps"]


@pytest.mark.parametrize("rule", [
    _one_regime_a_cell, _per_layer_in_its_regime, _no_empty_list,
    _moved_cell_keeps_its_readers, _resident_cell_loads],
    ids=lambda rule: rule.__name__.lstrip("_"))
def test_regimes_and_their_cells(doc, rule):
    rule(doc, _regime_cells(doc), [w["name"] for w in doc["workloads"]])


class _Prefetcher:
    """Stands where the trainer's `DevicePrefetcher` stands under the tap."""

    wait_s = 0.0

    def __init__(self):
        self.made = 0
        self.closed_at = None

    def epoch(self, epoch=None, from_start=False):
        try:
            while True:
                self.made += 1
                yield f"batch{self.made - 1}"
        finally:
            self.closed_at = self.made


def _feed(resident_batches, steps, window_start=4):
    """What `FitTap.epoch` hands `fit()` over `steps` steps, the boundaries'
    own work (copies, the window's clock, the profiler) left out."""
    import types

    from benchmarks.jobs.train_fit import FitTap

    class Tap(FitTap):
        def on_boundary(self, index, batch):
            self.seen.append((index, batch, self.inner.closed_at))

    inner = _Prefetcher()
    plan = {"window_start": window_start}
    if resident_batches is not None:
        plan["resident_batches"] = resident_batches
    tap = Tap(types.SimpleNamespace(train_prefetch=inner), plan)
    tap.seen = []
    it = tap.epoch()
    fed = [next(it) for _ in range(steps)]
    it.close()
    return tap, inner, fed


@pytest.mark.parametrize("resident_batches", [None, 0])
def test_tap_without_the_key_feeds_the_loaders_batches(resident_batches):
    tap, inner, fed = _feed(resident_batches, 9)
    assert fed == [f"batch{i}" for i in range(9)]
    assert tap.pulled == 9 and tap.worker_ended is None
    # the epoch stayed open until `fit()` closed it
    assert [c for _i, _b, c in tap.seen] == [None] * 9 and inner.closed_at == 9


@pytest.mark.parametrize("keep,expected", [
    (2, ["batch2", "batch3"]), (3, ["batch1", "batch2", "batch3"]),
    (4, ["batch0", "batch1", "batch2", "batch3"]),
    (8, ["batch0", "batch1", "batch2", "batch3"]),  # no more than were placed
])
def test_tap_feeds_the_window_the_batches_placed_last(keep, expected):
    tap, inner, fed = _feed(keep, 4 + 7)
    assert fed[:4] == ["batch0", "batch1", "batch2", "batch3"]
    assert fed[4:] == [expected[i % len(expected)] for i in range(7)]
    # nothing was taken from the loader after the window's start, and its
    # epoch was closed before the window's first boundary was reached
    assert tap.pulled == 4 and inner.made == 4
    assert [c for i, _b, c in tap.seen if i >= 4] == [4] * 7
    assert [c for i, _b, c in tap.seen if i < 4] == [None] * 4


@pytest.mark.parametrize("stats,live,expected", [
    # the token cell (ledger and chip runs, PR 28): the live arrays' peak falls
    # while the weights are made, the scratch's while the step runs
    ({"peak_bytes_in_use": 10.05e9, "peak_bytes_reserved": 8.09e9}, 7.65e9, 15.74e9),
    # live arrays alone above what the window held
    ({"peak_bytes_in_use": 9e9, "peak_bytes_reserved": 1e9}, 2e9, 9e9),
    ({"peak_bytes_in_use": 3e9}, 0, 3e9),  # a backend with no reserved peak
    ({}, 0, 0),  # the CPU: nothing to read, so `peak_hbm_bytes` is left out
])
def test_memory_peak_is_one_peak_not_the_sum_of_two(stats, live, expected):
    import types

    from benchmarks.jobs.train_fit import memory_peak_bytes
    from benchmarks.lib.spec import metric_module

    full = types.SimpleNamespace(memory_stats=lambda: stats)
    emptier = types.SimpleNamespace(memory_stats=lambda: None)
    peak = memory_peak_bytes([emptier, full], live)
    assert peak == int(expected)
    assert peak <= stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
    read = metric_module("peak_hbm_bytes.device_paced").read({"memory_peak_bytes": peak})
    assert read == (peak or None)


_RESIDENT = """
import json, sys, threading
sys.path.insert(0, {root!r})
from benchmarks.jobs import train_fit

seen = {{}}
real = train_fit.FitTap.pop_wait

def pop_wait(self):
    out = real(self)
    if self.pop_wait_calls == 2:  # the window has just closed
        seen.update(pulled=self.pulled, steps=self.steps_in_window,
                    start=self.plan["window_start"], keep=self.plan["resident_batches"],
                    prefetch_threads=sum(t.name == "device-prefetch" and t.is_alive()
                                         for t in threading.enumerate()))
    return out

train_fit.FitTap.pop_wait = pop_wait
from benchmarks import run
run.main(["--workload", "slowfast_r50.train_resident", "--seed", str(2 ** 31 + 11),
          "--seconds", "1", "--trace", "0", "--rehearse"])
print(json.dumps(seen))
"""


def test_rehearsal_of_the_resident_cell(tmp_path):
    """The whole command at the toy geometry (`resident_batches` 2), the look
    for a chip lifted: the window is fed the kept batches only."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "-c", _RESIDENT.format(root=ROOT)], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line, seen = json.loads(lines[-2]), json.loads(lines[-1])
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    for name in ("window_batches_from_loader", "loader_worker_left",
                 "step_count_gap", "recompiles", "duplicate_rows"):
        assert line["compared"][name] == {"value": 0.0, "limit": 0}, name
    assert set(line["metrics"]) == {"clips_per_s_per_chip.device_paced", "setup_s"}
    # the loader gave the batches up to the window's start and none after,
    # though the window ran more steps than were kept; its worker had ended
    assert seen["keep"] == 2 and seen["pulled"] == seen["start"]
    assert seen["steps"] > seen["keep"] and line["attempted"] == seen["steps"]
    assert seen["prefetch_threads"] == 0

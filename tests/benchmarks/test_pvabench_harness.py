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

from benchmarks.lib.spec import quantity

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

"""Finding a cell's files by name.

`BENCHMARK.json` (at the root of the checkout) names the cells, the
configurations and the metrics. Everything that belongs to one of them is a
file of its own under `benchmarks/`, found by that name:

    workloads/<cell>.json      the cell: config, job, chips, traffic parameters
    configs/<config>.json      the configuration as it is run
    jobs/<job>.py              the driver of one kind of job
    metrics/<metric>.py        the reader of one per-layer metric
    reference/<family>.py      the plain reference of one model family

so a later PR adds files and appends entries, and edits none.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's own files disagree or are missing."""


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}") from None


class Spec:
    """`BENCHMARK.json` of the checkout at `root`, with its cells' files."""

    def __init__(self, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name):
        """The cell's `BENCHMARK.json` entry merged over its workload file."""
        for entry in self.doc["workloads"]:
            if entry["name"] == name:
                break
        else:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                            f"{[w['name'] for w in self.doc['workloads']]}")
        cell = _read_json(os.path.join(self.bench_dir, "workloads", f"{name}.json"))
        for key in ("config", "chips"):
            if key in cell and cell[key] != entry[key]:
                raise SpecError(f"workloads/{name}.json says {key}="
                                f"{cell[key]!r}, BENCHMARK.json {entry[key]!r}")
        return {**cell, **entry}

    def config(self, name):
        for entry in self.doc["configs"]:
            if entry["name"] == name:
                return _read_json(os.path.join(self.root, entry["file"]))
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def metric_names(self, group, cell_name):
        """Names of the `end_to_end` / `per_layer` metrics this cell reports."""
        return [m["name"] for m in self.doc[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def metric(self, name):
        for group in ("end_to_end", "per_layer"):
            for m in self.doc[group]:
                if m["name"] == name:
                    return m
        raise SpecError(f"no metric {name!r} in BENCHMARK.json")

    def peaks(self, device_kind):
        """The chip's peaks; a kind that is not in the table is an error."""
        table = _read_json(os.path.join(self.bench_dir, "peaks.json"))["chips"]
        if device_kind not in table:
            raise SpecError(f"no peaks on record for device_kind "
                            f"{device_kind!r}; add it to benchmarks/peaks.json "
                            "with its source")
        return table[device_kind]


def job_module(name):
    return importlib.import_module(f"benchmarks.jobs.{name}")


def quantity(name):
    """`<quantity>.<regime>` -> `<quantity>`: one quantity is several metrics
    of `BENCHMARK.json` where its cells report different end-to-end metrics
    (`step_mfu.device_paced` moves `clips_per_s_per_chip.device_paced`)."""
    return name.split(".")[0]


def metric_module(name):
    """The reader of one per-layer metric: a file named after the metric
    (dots become `__`), else the file of its quantity."""
    for stem in (name.replace(".", "__"), quantity(name)):
        try:
            return importlib.import_module(
                "benchmarks.metrics." + stem.replace("-", "_"))
        except ModuleNotFoundError as e:
            if e.name != "benchmarks.metrics." + stem.replace("-", "_"):
                raise
    raise SpecError(f"no reader benchmarks/metrics/{quantity(name)}.py for "
                    f"the metric {name!r}")

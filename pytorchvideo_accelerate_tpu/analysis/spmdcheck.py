"""`pva-tpu-spmdcheck` — collective-schedule divergence: static + dynamic.

ROADMAP item 4 turns the single-process forced-host emulation into a
real pod: N processes that must issue IDENTICAL ordered collective
schedules, where one host skipping a `psum` behind a
`process_index()==0` branch deadlocks everyone with no evidence. This
module is the pair of tools that proves divergence-freedom BEFORE that
PR lands, and gates it forever:

- **Static pass** (`run_spmdcheck`): the `rules_spmd` rules — four
  `spmd-divergence` finding kinds (divergent-predicate,
  branch-asymmetry, skip-path, ckpt-discipline) plus the
  `spmd-coverage` audit (every raw collective primitive inside a
  hangcheck `collective_section`) — over the hot modules. Pure
  stdlib-ast, runs with no jax anywhere.
- **Dynamic counterpart** (`parallel/schedule_recorder.py`): the
  installed recorder logs every `collective_section` entry per host;
  `diff_schedules` reports the first cross-host divergence with both
  hosts' trailing windows (tests/test_zspmd.py records + diffs
  emulated hosts: `test_recorder_clean_run_non_vacuous`,
  `test_seeded_divergence_detected_with_evidence`).

CLI: `pva-tpu-spmdcheck [paths...]` — exit 0 clean, 1 findings, 2
usage/crash. `--selftest` seeds one violation per static kind, one
covered/uncovered primitive pair, and one injected schedule divergence
through the REAL armed `collective_section`; every seed MUST be
detected and every clean twin MUST stay clean.

Gates: `spmdcheck_findings == 0` in `scripts/analyze.sh` and
tests/test_zspmd.py::test_full_tree_clean; `pva_spmd_findings` /
`pva_spmd_schedule_divergence` gauges + flight-ring events;
`pva-tpu-doctor diagnose()` carries `spmd_snapshot()`. See
docs/STATIC_ANALYSIS.md § spmdcheck.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

from pytorchvideo_accelerate_tpu.analysis.core import lint_source, run_lint
from pytorchvideo_accelerate_tpu.analysis.rules_spmd import (
    DIVERGENCE_KINDS,
    spmd_rules,
)

# the hot-module surface the rules gate on lives entirely inside the
# package tree; linting the whole package keeps the entrypoint stable as
# hot modules are added
DEFAULT_PATHS = ("pytorchvideo_accelerate_tpu",)

_LAST_REPORT: Optional[dict] = None


def run_spmdcheck(paths: Optional[Sequence[str]] = None,
                  log=None) -> dict:
    """Run the static pass; returns the report dict (stashed for
    `spmd_snapshot`, published to obs)."""
    global _LAST_REPORT
    t0 = time.perf_counter()
    paths = list(paths or DEFAULT_PATHS)
    findings = run_lint(paths, spmd_rules())
    by_rule: Dict[str, int] = {}
    by_kind: Dict[str, int] = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        if f.rule == "spmd-divergence":
            kind = f.message.split(":", 1)[0]
            if kind in DIVERGENCE_KINDS:
                by_kind[kind] = by_kind.get(kind, 0) + 1
    report: Dict[str, Any] = {
        "paths": paths,
        "findings_total": len(findings),
        "by_rule": by_rule,
        "by_kind": by_kind,
        "findings": [
            {"path": f.path, "line": f.line, "col": f.col,
             "rule": f.rule, "message": f.message} for f in findings],
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }
    if log:
        log(f"[spmdcheck] {len(findings)} finding(s) over {paths} "
            f"in {report['elapsed_s']}s")
    _LAST_REPORT = report
    publish(report)
    return report


def finding_count(report: dict) -> int:
    return int(report.get("findings_total", 0))


def format_report(report: dict, max_findings: int = 20) -> str:
    lines = [f"pva-tpu-spmdcheck: {report.get('findings_total', 0)} "
             f"finding(s) over {', '.join(report.get('paths', []))} "
             f"in {report.get('elapsed_s')}s "
             f"(by_rule={report.get('by_rule') or {}})"]
    for i, f in enumerate(report.get("findings", ())):
        if i >= max_findings:
            lines.append("  ... (truncated)")
            break
        lines.append(f"  {f['path']}:{f['line']}:{f['col']}: "
                     f"[{f['rule']}] {f['message']}")
    return "\n".join(lines)


def publish(report: dict) -> None:
    """`pva_spmd_findings` gauge + a flight-ring event (the
    graphcheck/tsan publish discipline; telemetry stays optional). The
    dynamic half's `pva_spmd_schedule_divergence` gauge is published by
    `schedule_recorder.publish_schedule_report`."""
    try:
        from pytorchvideo_accelerate_tpu import obs

        obs.get_registry().gauge(
            "pva_spmd_findings",
            "total findings of the last pva-tpu-spmdcheck static pass "
            "(spmd-divergence kinds + spmd-coverage)",
        ).set(report.get("findings_total", 0))
        obs.get_recorder().record(
            "spmd", "static pass",
            findings=report.get("findings_total", 0),
            by_rule=report.get("by_rule") or {},
            elapsed_s=report.get("elapsed_s"))
    except Exception:  # telemetry must never fail the pass
        pass


def spmd_snapshot() -> dict:
    """Doctor view (utils/device_doctor.diagnose): the last in-process
    static pass + the live recorder's schedule counts, or ran=False."""
    rec_snap = None
    try:
        from pytorchvideo_accelerate_tpu.parallel.schedule_recorder import (
            current_recorder,
        )

        rec = current_recorder()
        if rec is not None:
            rec_snap = rec.snapshot()
    except Exception:  # pragma: no cover - snapshot must never raise
        pass
    if _LAST_REPORT is None:
        return {"ran": False, "recorder": rec_snap}
    rep = _LAST_REPORT
    return {
        "ran": True,
        "findings_total": rep.get("findings_total", 0),
        "by_rule": rep.get("by_rule") or {},
        "by_kind": rep.get("by_kind") or {},
        "elapsed_s": rep.get("elapsed_s"),
        "finding_heads": [f["message"][:160]
                          for f in rep.get("findings", ())][:10],
        "recorder": rec_snap,
    }


# --- selftest fixtures ------------------------------------------------------
# All anchored at a hot-module path so the rules engage; each positive
# seed has a clean twin (and the suppression syntax is exercised once
# per rule name).

_FIXTURE_PATH = "pytorchvideo_accelerate_tpu/trainer/_spmd_fixture.py"

_SEED_DIVERGENT = """\
import jax
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def resume(x):
    if jax.process_index() == 0:
        host_broadcast(x)
"""

_CLEAN_DIVERGENT = """\
import jax
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def resume(x):
    if jax.process_count() > 1:
        host_broadcast(x)
"""

_SUPPRESSED_DIVERGENT = """\
import jax
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def resume(x):
    if jax.process_index() == 0:
        host_broadcast(x)  # pva: disable=spmd-divergence -- selftest seed
"""

_SEED_ASYMMETRY = """\
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def maybe(x, manifest):
    if load_manifest(manifest):
        host_broadcast(x)
    else:
        log_skip(manifest)
"""

_CLEAN_ASYMMETRY = """\
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def maybe(x, manifest):
    if load_manifest(manifest):
        host_broadcast(x)
    else:
        host_broadcast(x)
"""

_SEED_SKIP = """\
import os
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def sync(x):
    if not os.path.exists("/tmp/marker"):
        return None
    host_broadcast(x)
"""

_CLEAN_SKIP = """\
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def sync(x, ready):
    if not ready:
        return None
    host_broadcast(x)
"""

_SEED_CKPT = """\
from pytorchvideo_accelerate_tpu.reliability.atomic import atomic_write_json

def export(tree, path):
    atomic_write_json(path, tree)
"""

_CLEAN_CKPT = """\
from pytorchvideo_accelerate_tpu.parallel.distributed import is_main_process
from pytorchvideo_accelerate_tpu.reliability.atomic import atomic_write_json

def export(tree, path):
    if is_main_process():
        atomic_write_json(path, tree)
"""

_SEED_DERIVED = """\
import jax
from pytorchvideo_accelerate_tpu.parallel.collectives import host_broadcast

def _bcast_helper(x):
    host_broadcast(x)

def run(x):
    if jax.process_index() == 0:
        _bcast_helper(x)
"""

_SEED_COVERAGE = """\
from jax.experimental import multihost_utils

def barrier():
    multihost_utils.sync_global_devices("fence")
"""

_CLEAN_COVERAGE = """\
from jax.experimental import multihost_utils
from pytorchvideo_accelerate_tpu.parallel.hangcheck import collective_section

def barrier():
    with collective_section("barrier", name="fence"):
        multihost_utils.sync_global_devices("fence")
"""

_SUPPRESSED_COVERAGE = """\
from jax.experimental import multihost_utils

def barrier():
    multihost_utils.sync_global_devices("fence")  # pva: disable=spmd-coverage -- selftest seed
"""


def _lint_fixture(source: str):
    return lint_source(source, _FIXTURE_PATH, spmd_rules())


def selftest(log=print) -> int:
    """Seed one violation per static kind + the coverage audit + one
    injected schedule divergence through the REAL armed
    `collective_section`; every seed MUST be detected and every clean
    twin MUST stay clean. Returns failure count."""
    failures = 0

    def expect(cond: bool, what: str):
        nonlocal failures
        if cond:
            log(f"[selftest] PASS {what}")
        else:
            failures += 1
            log(f"[selftest] FAIL {what}")

    def kinds(findings):
        return [f.message.split(":", 1)[0] for f in findings
                if f.rule == "spmd-divergence"]

    # (1) divergent-predicate
    f = _lint_fixture(_SEED_DIVERGENT)
    expect("divergent-predicate" in kinds(f),
           "static: collective under process_index() branch detected")
    expect(not _lint_fixture(_CLEAN_DIVERGENT),
           "static: uniform process_count() guard stays clean")
    expect(not _lint_fixture(_SUPPRESSED_DIVERGENT),
           "static: spmd-divergence suppression silences the seed")

    # (2) branch-asymmetry
    f = _lint_fixture(_SEED_ASYMMETRY)
    expect("branch-asymmetry" in kinds(f),
           "static: one-armed collective under dynamic test detected")
    expect(not _lint_fixture(_CLEAN_ASYMMETRY),
           "static: collective-symmetric arms stay clean")

    # (3) skip-path
    f = _lint_fixture(_SEED_SKIP)
    expect("skip-path" in kinds(f),
           "static: early return under fs probe skipping a collective "
           "detected")
    expect(not _lint_fixture(_CLEAN_SKIP),
           "static: uniform early return stays clean")

    # (4) ckpt-discipline
    f = _lint_fixture(_SEED_CKPT)
    expect("ckpt-discipline" in kinds(f),
           "static: unguarded checkpoint-artifact write detected")
    expect(not _lint_fixture(_CLEAN_CKPT),
           "static: is_main_process()-guarded write stays clean")

    # one-level interprocedural carrier
    f = _lint_fixture(_SEED_DERIVED)
    expect(any("_bcast_helper" in x.message for x in f),
           "static: helper that issues collectives carries the site one "
           "call level up")

    # coverage audit
    f = _lint_fixture(_SEED_COVERAGE)
    expect(any(x.rule == "spmd-coverage" for x in f),
           "static: raw primitive outside collective_section detected")
    expect(not _lint_fixture(_CLEAN_COVERAGE),
           "static: collective_section-wrapped primitive stays clean")
    expect(not _lint_fixture(_SUPPRESSED_COVERAGE),
           "static: spmd-coverage suppression silences the seed")

    # dynamic: identical emulated schedules clean; an injected skip MUST
    # be caught at the exact op, through the real collective_section hook
    from pytorchvideo_accelerate_tpu.parallel.hangcheck import (
        collective_section,
    )
    from pytorchvideo_accelerate_tpu.parallel.schedule_recorder import (
        CollectiveScheduleRecorder,
        diff_schedules,
        install_schedule_recorder,
        uninstall_schedule_recorder,
    )

    rec = CollectiveScheduleRecorder()
    install_schedule_recorder(rec)
    try:
        for h in range(2):
            with rec.as_host(f"host={h}/2"):
                for i in range(3):
                    with collective_section("step_dispatch", step=i):
                        pass
                with collective_section("epoch_sync"):
                    pass
        clean = diff_schedules(rec.schedules())
        expect(not clean["diverged"]
               and clean["lengths"] == {"host=0/2": 4, "host=1/2": 4},
               "dynamic: identical emulated schedules diff clean")

        rec.clear()
        for h in range(2):
            with rec.as_host(f"host={h}/2"):
                with collective_section("step_dispatch", step=0):
                    pass
                if h == 0:  # host 1 SKIPS the epoch_sync — the bug shape
                    with collective_section("epoch_sync"):
                        pass
                with collective_section("ckpt_save", step=0):
                    pass
        bad = diff_schedules(rec.schedules())
        first = bad.get("first_divergence") or {}
        hosts = first.get("hosts") or {}
        expect(bad["diverged"] and first.get("tick") == 1
               and (hosts.get("host=0/2") or [None, None])[1] == "epoch_sync"
               and (hosts.get("host=1/2") or [None, None])[1] == "ckpt_save",
               "dynamic: injected skipped-collective divergence detected "
               "at the exact op")
        expect(len((first.get("window") or {}).get("host=0/2", ())) >= 2,
               "dynamic: divergence report carries trailing windows")
    finally:
        uninstall_schedule_recorder()

    # disarmed = structurally silent: no recorder, no records
    before = rec.counts()
    with collective_section("step_dispatch", step=99):
        pass
    expect(rec.counts() == before,
           "dynamic: disarmed collective_section records nothing")

    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pva-tpu-spmdcheck",
        description="collective-schedule divergence analysis over the "
                    "hot modules: divergent predicates, asymmetric "
                    "branches, skip paths, checkpoint-write discipline, "
                    "collective_section coverage "
                    "(docs/STATIC_ANALYSIS.md § spmdcheck)")
    ap.add_argument("paths", nargs="*", default=list(DEFAULT_PATHS),
                    help="files/trees to analyze (default: the package)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--selftest", action="store_true",
                    help="seed one violation per rule kind plus an "
                         "injected schedule divergence; exit 0 only when "
                         "every one is detected")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    if args.selftest:
        failures = selftest(log=log)
        if failures:
            log(f"pva-tpu-spmdcheck --selftest: {failures} seeded "
                "violation(s) NOT detected")
            return 1
        log("pva-tpu-spmdcheck --selftest: all seeded violations "
            "detected; clean constructions clean")
        return 0

    try:
        report = run_spmdcheck(paths=args.paths, log=log)
    except Exception as e:
        log(f"pva-tpu-spmdcheck: analysis failed: "
            f"{type(e).__name__}: {e}")
        return 2
    if args.format == "json":
        print(json.dumps(report, default=str))
    else:
        print(format_report(report))
    return 1 if report["findings_total"] else 0


if __name__ == "__main__":
    sys.exit(main())

"""Device reachability doctor: probe, diagnose, and guard TPU backend init
(SURVEY §5 failure detection — the device-attachment analogue of
`data/verify.py`'s dataset doctor).

A PJRT client-create can hang rather than fail, and a chip held by another
process makes a second client wait. The reference stack (torch + NCCL)
fails loudly on a bad device; a jax job just sits there. This module gives
the framework the same loud-failure property:

- `quick_probe(timeout)`: can a DISPOSABLE subprocess enumerate devices
  and run one op within the deadline? It runs BEFORE the caller touches
  JAX: the chip belongs to one process at a time, so a parent that already
  holds it would make the probe itself hang.
- `assert_device_reachable(timeout)`: Trainer guard (config
  `device_init_timeout`) — raises RuntimeError with the diagnosis recipe
  instead of letting the training job hang in backend init, and refuses a
  probe that landed on the CPU when nobody asked for the CPU.
- `diagnose(...)`: evidence capture — device-related env vars, the libtpu
  file, a verbose init attempt whose stderr tail survives the kill, and
  the obs/analysis/reliability snapshots.
- `main()`: the `pva-tpu-doctor` CLI.

Every subprocess redirects stderr to a file first: a hung child gets
SIGKILLed, and a pipe would discard exactly the init logs the diagnosis
needs.
"""

from __future__ import annotations

import datetime
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, Optional

ENV_PREFIXES = ("TPU", "PJRT", "JAX", "XLA", "PALLAS", "LIBTPU")

PROBE_CODE = ("import jax, numpy as np\n"
              "d = jax.devices()[0]\n"
              "x = jax.device_put(np.ones((128, 128), np.float32), d)\n"
              "jax.jit(lambda a: a @ a)(x).block_until_ready()\n"
              "print(d.platform, d.device_kind)\n")
DEVICES_CODE = ("import jax\n"
                "ds = jax.devices()\n"
                "print('DEVICES:', [(d.platform, d.device_kind) "
                "for d in ds])\n")


def _utcnow() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime("%FT%TZ")


def env_snapshot() -> Dict[str, str]:
    return {k: v for k, v in sorted(os.environ.items())
            if any(k.upper().startswith(p) or f"_{p}" in k.upper()
                   for p in ENV_PREFIXES)}


def file_facts() -> dict:
    path = os.environ.get("TPU_LIBRARY_PATH", "")
    if not path:
        return {"libtpu": "env var unset"}
    if not os.path.exists(path):
        return {"libtpu": {"path": path, "missing": True}}
    st = os.stat(path)
    return {"libtpu": {"path": path, "bytes": st.st_size,
                       "mtime": datetime.datetime.fromtimestamp(
                           st.st_mtime).strftime("%FT%T")}}


def _attempt(code: str, env: dict, timeout_s: int,
             err_path: Optional[str] = None,
             tail_bytes: int = 4000) -> dict:
    """Run `code` in a disposable subprocess (own process group, killed
    wholesale on timeout) with stderr redirected to a FILE so the tail
    survives the kill. Default: a fresh mkstemp file, removed after
    reading — fixed shared names would collide across concurrent probes
    (two launch ranks both running the init guard) and across users of a
    shared /tmp."""
    import tempfile

    own_file = err_path is None
    if own_file:
        fd, err_path = tempfile.mkstemp(prefix="pva_doctor_", suffix=".txt")
        os.close(fd)
    rec: dict = {"timeout_s": timeout_s}
    t0 = time.time()
    try:
        with open(err_path, "wb") as errf:
            p = subprocess.Popen([sys.executable, "-c", code], env=env,
                                 stdout=subprocess.PIPE, stderr=errf,
                                 text=True, start_new_session=True)
            try:
                out, _ = p.communicate(timeout=timeout_s)
                rec.update(ok=p.returncode == 0, returncode=p.returncode,
                           stdout=(out or "").strip()[-300:])
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
                p.wait()
                rec.update(ok=False, error="timeout (killed)")
        rec["elapsed_s"] = round(time.time() - t0, 1)
        try:
            with open(err_path, "rb") as f:
                data = f.read()
            rec["stderr_bytes"] = len(data)
            rec["stderr_tail"] = data[-tail_bytes:].decode("utf-8", "replace")
        except OSError:
            pass
    finally:
        if own_file:
            try:
                os.unlink(err_path)
            except OSError:
                pass
    return rec


def quick_probe(timeout_s: int = 240) -> dict:
    """Enumerate devices + run one op in a disposable subprocess, default
    init path (whatever the job itself would get). Returns the attempt
    record; `ok` means the main process can safely init its backend."""
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    rec = _attempt(PROBE_CODE, env, timeout_s, tail_bytes=1000)
    rec["ts"] = _utcnow()
    return rec


def assert_device_reachable(timeout_s: int, log=None) -> dict:
    """Trainer guard: fail LOUDLY (RuntimeError) when backend init would
    hang, instead of wedging the training job in jax.devices().

    A probe that lands on the CPU backend is a failure too, unless
    `JAX_PLATFORMS=cpu` asked for it (the Trainer skips the guard under
    `--cpu`): the job would get the same backend and train on the host
    while its operator believes it holds a chip."""
    log = log or (lambda msg: print(msg, file=sys.stderr))
    log(f"[device_doctor] probing device init ({timeout_s}s cap) ...")
    rec = quick_probe(timeout_s)
    platform = (rec.get("stdout") or "").split(" ", 1)[0]
    if (rec.get("ok") and platform == "cpu"
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        raise RuntimeError(
            "device probe landed on the CPU backend although neither "
            "--cpu nor JAX_PLATFORMS=cpu asked for it: JAX found no "
            "accelerator. Refusing to start a training job on the host "
            "by accident; pass --cpu to mean it.")
    if rec.get("ok"):
        log(f"[device_doctor] device ok in {rec['elapsed_s']}s: "
            f"{rec.get('stdout', '')}")
        return rec
    raise RuntimeError(
        "device backend init did not complete within "
        f"{timeout_s}s (probe: {rec.get('error') or rec.get('stderr_tail', '')[-200:]}). "
        "Refusing to start a training job that would hang in "
        "jax.devices(). Diagnose with `pva-tpu-doctor` (device env, "
        "verbose init attempt), run on CPU with --cpu, or raise/disable "
        "the guard via --device_init_timeout (0 disables)."
    )


def verbose_init_attempt(timeout_s: int = 120,
                         tail_bytes: int = 4000) -> dict:
    """Default init path under maximum libtpu/jax verbosity — whatever
    the runtime logs before hanging is the diagnosis."""
    env = dict(os.environ)
    env.update(
        TPU_STDERR_LOG_LEVEL="0",   # INFO and up to stderr
        TPU_MIN_LOG_LEVEL="0",
        TPU_VMODULE="*=1",
        JAX_LOGGING_LEVEL="DEBUG",
        PYTHONUNBUFFERED="1",
    )
    return _attempt(DEVICES_CODE, env, timeout_s,
                    tail_bytes=tail_bytes)


def obs_snapshot(output_dir: str = "", last: int = 30) -> dict:
    """Telemetry snapshot for diagnosing a wedged run (obs/ spine):

    - in-process: every thread's OPEN span stack (who is inside what right
      now) + the last flight-recorder events — the live view when the
      doctor runs inside the stuck process (the Trainer init guard path);
    - cross-process: the tail of `<output_dir>/flight_record.json` — the
      file the watchdog / excepthook / SIGTERM handler dumps, i.e. the
      evidence a SECOND shell reads while (or after) the run is wedged:
      `pva-tpu-doctor --obs-dir <output_dir> --skip-init`.
    """
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu import obs

        out["span_stacks"] = obs.current_stacks()
        out["recent_events"] = obs.get_recorder().snapshot(last)
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    if output_dir:
        path = os.path.join(output_dir, "flight_record.json")
        try:
            with open(path) as f:
                data = json.load(f)
            out["flight_record_file"] = {
                "path": path,
                "dumped_at": data.get("dumped_at"),
                "pid": data.get("pid"),
                "events": data.get("events", [])[-last:],
            }
        except (OSError, ValueError) as e:
            out["flight_record_file"] = {
                "path": path, "error": f"{type(e).__name__}: {e}"}
    return out


def lint_snapshot(root: str = "", max_items: int = 40) -> dict:
    """Static-analysis health of the installed package (analysis/ —
    docs/STATIC_ANALYSIS.md): a fresh `pva-tpu-lint` pass (finding count
    + heads) and every outstanding `# pva: disable=... -- reason`
    suppression with its file, rules, and reason. Suppressions are
    DEBT the linter is carrying on purpose; surfacing them here keeps
    them auditable instead of letting reasons rot in comments."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.analysis import (
            iter_suppressions,
            lint_source,
        )
        from pytorchvideo_accelerate_tpu.analysis.core import iter_py_files

        if not root:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # one read per file feeds BOTH the lint pass and the suppression
        # audit (run_lint would re-read the tree this loop already reads)
        findings, sups = [], []
        for fp in iter_py_files([root]):
            try:
                with open(fp, encoding="utf-8") as f:
                    source = f.read()
            except OSError:
                continue
            findings.extend(lint_source(source, path=fp))
            rel = os.path.relpath(fp, os.path.dirname(root))
            for s in iter_suppressions(source):
                sups.append({"file": rel, "line": s.line,
                             "rules": list(s.rules), "reason": s.reason})
        out["findings"] = len(findings)
        out["finding_heads"] = [f.format() for f in findings[:max_items]]
        out["suppressions"] = len(sups)
        out["suppression_list"] = sups[:max_items]
        # a suppression without a reason defeats the audit trail — count
        # them so the doctor's reader sees the debt explicitly
        out["suppressions_without_reason"] = sum(
            1 for s in sups if not s["reason"])
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def trace_snapshot() -> dict:
    """Distributed-tracing health (obs/trace.py — docs/OBSERVABILITY.md
    § distributed tracing): whether the tracer is armed, ring occupancy
    vs capacity, how many heads were sampled, the tracer's self-measured
    bookkeeping overhead, the slowest root spans still in the ring, and
    the last export path (`trace_ring.json`) a second shell can merge
    with `pva-tpu-trace`."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.obs import trace

        out.update(trace.snapshot())
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def tsan_snapshot() -> dict:
    """Dynamic-sanitizer health (analysis/tsan.py — docs/STATIC_ANALYSIS.md
    § dynamic sanitizer): whether a pva-tpu-tsan run happened in this
    process, the current lock-order graph, live held locks per thread, and
    recent finding counts. For a wedged ARMED process this is the "who
    holds what right now" view the stall dump can't always reach."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.analysis.tsan_report import (
            tsan_snapshot as _snap,
        )

        out.update(_snap())
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def reliability_snapshot(output_dir: str = "") -> dict:
    """Resilience health (reliability/ — docs/RELIABILITY.md): the armed
    fault plan if a chaos run is live (production must read `None`), the
    fired-fault history length, the retry/fault counters from the default
    registry, and — given the run's output_dir — the last emergency-
    checkpoint record (where a preempted run stopped, and the directory
    `resume=auto` will pick up)."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.obs import get_registry
        from pytorchvideo_accelerate_tpu.reliability import faults
        from pytorchvideo_accelerate_tpu.reliability.preemption import (
            read_emergency_record,
        )

        plan = faults.current_plan()
        out["fault_plan_armed"] = plan is not None
        if plan is not None:
            out["fault_plan"] = plan.to_dict()
        out["fault_fires"] = len(faults.fault_history())
        reg = get_registry()
        counters: dict = {}
        for name in ("pva_retry_attempts_total", "pva_retry_giveups_total",
                     "pva_retry_recoveries_total",
                     "pva_fault_injected_total"):
            m = reg.get(name)
            if m is None:
                continue
            counters[name] = {
                ",".join(f"{k}={v}" for k, v in labels.items()) or "total":
                value for labels, value in m.samples()}
        out["retry_counters"] = counters
        if output_dir:
            out["emergency_checkpoint"] = read_emergency_record(output_dir)
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def guard_snapshot(output_dir: str = "") -> dict:
    """Self-healing-guard health (reliability/guard.py TrainGuard —
    docs/RELIABILITY.md § divergence runbook): LKG step + ring contents,
    rollback/skip counts, the last anomaly verdict, the quarantine list,
    and — given the run's output_dir — the on-disk replay bundles and
    quarantine sidecar a second shell reads for a wedged/dead run."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.reliability.guard import (
            guard_snapshot as _snap,
        )

        out.update(_snap(output_dir))
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def graphcheck_snapshot() -> dict:
    """Compiled-graph analysis health (analysis/graphcheck.py —
    docs/STATIC_ANALYSIS.md § graphcheck): the last in-process
    pva-tpu-graphcheck run's per-pass finding counts and the
    donation-verified verdict. ran=False in a fresh process — the doctor
    reports the absence rather than paying a multi-second trace of the
    step functions on every diagnosis."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.analysis.graphcheck import (
            graphcheck_snapshot as _snap,
        )

        out.update(_snap())
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def spmd_snapshot() -> dict:
    """Collective-schedule divergence health (analysis/spmdcheck.py —
    docs/STATIC_ANALYSIS.md § spmdcheck): the last in-process static
    pass's finding counts by rule/kind plus the live schedule recorder's
    per-host record counts (recorder=None when disarmed). ran=False in a
    fresh process — the static pass is cheap but the doctor reports
    state, it doesn't mint it."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.analysis.spmdcheck import (
            spmd_snapshot as _snap,
        )

        out.update(_snap())
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def memory_snapshot() -> dict:
    """Device-memory ledger health (obs/memory.py — docs/OBSERVABILITY.md
    § memory ledger): per-component registered bytes, the unattributed
    residual against the backend's `bytes_in_use`, the declared-vs-
    measured drift per component, and the measurement source ("measured"
    on a backend with memory_stats, "estimate" elsewhere — a CPU doctor
    run must say so, never fake device bytes). armed=False when no run
    in this process configured the ledger."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.obs import memory as obs_memory

        led = obs_memory.get_ledger()
        out["armed"] = led is not None
        if led is not None:
            out.update(led.snapshot())
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def alerts_snapshot() -> dict:
    """Metrics-history / burn-rate alert health (obs/history.py +
    obs/alerts.py): history ring occupancy and span, per-rule state
    (active, fire count, last fast/slow burn factors, last clear) and
    the currently-firing set. armed=False when neither the history nor
    the alert engine was configured in this process."""
    out: dict = {"ts": _utcnow()}
    try:
        from pytorchvideo_accelerate_tpu.obs import alerts as obs_alerts
        from pytorchvideo_accelerate_tpu.obs import history as obs_history

        engine = obs_alerts.get_engine()
        hist = obs_history.get_history()
        out["armed"] = engine is not None or hist is not None
        if engine is not None:
            out.update(engine.snapshot())
        elif hist is not None:
            out["history"] = hist.snapshot()
    except Exception as e:  # the doctor must never die of its own probes
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def diagnose(timeout_s: int = 120, skip_init: bool = False,
             obs_dir: str = "") -> dict:
    rec = {
        "probe": "diagnostics",
        "ts": _utcnow(),
        "env": env_snapshot(),
        "files": file_facts(),
        "obs": obs_snapshot(obs_dir),
        "trace": trace_snapshot(),
        "lint": lint_snapshot(),
        "graphcheck": graphcheck_snapshot(),
        "spmd": spmd_snapshot(),
        "tsan": tsan_snapshot(),
        "reliability": reliability_snapshot(obs_dir),
        "guard": guard_snapshot(obs_dir),
        "memory": memory_snapshot(),
        "alerts": alerts_snapshot(),
    }
    if not skip_init:
        rec["verbose_init"] = verbose_init_attempt(timeout_s)
        rec["ok"] = bool(rec["verbose_init"].get("ok"))
    return rec


def main(argv: Optional[list] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--timeout", type=int, default=120,
                    help="seconds for the verbose init attempt")
    ap.add_argument("--skip-init", action="store_true",
                    help="environment + snapshots only (no init attempt)")
    ap.add_argument("--obs-dir", default="",
                    help="training run's output_dir: include the tail of "
                         "its dumped flight_record.json (watchdog/"
                         "excepthook evidence) in the obs snapshot — the "
                         "second-shell diagnosis path for a wedged run")
    ap.add_argument("--log", default="",
                    help="append the JSON record to this jsonl file")
    args = ap.parse_args(argv)

    rec = diagnose(args.timeout, args.skip_init, obs_dir=args.obs_dir)
    print(json.dumps(rec, indent=1))
    if args.log:
        with open(args.log, "a") as f:
            f.write(json.dumps(rec) + "\n")
    ok = rec.get("ok")
    return 0 if (ok or args.skip_init) else 1


if __name__ == "__main__":
    sys.exit(main())

"""Per-host collective-schedule recorder + cross-host differ.

The multi-host failure mode `pva-tpu-spmdcheck` exists for: N processes
must execute IDENTICAL ordered collective schedules, and the host that
skips one `psum` behind a `process_index()==0` branch (or a per-host
file-existence check, or an exception path) deadlocks the whole pod with
no evidence beyond "everything is wedged". hangcheck (PR 9) attributes
the wedge AFTER it happens; this module records the evidence that says
WHY: every `hangcheck.collective_section` entry appends one
`(tick, op, detail)` record to the installed recorder under the current
host label, and `diff_schedules` compares the per-host streams and
reports the FIRST divergence with both hosts' trailing windows — the
exact op one host issued that the other never did.

Arm/disarm follows the watchdog discipline in `parallel/hangcheck.py`:
disarmed (the default) costs ONE module-global read inside
`collective_section` and records nothing; `install_schedule_recorder`
routes every section entry here. Single-process runs (chaos legs, the
spmdcheck selftest, tests/test_zspmd.py) record several
EMULATED hosts by replaying the same deterministic segment under
`recorder.as_host(...)` labels — run-to-run schedule determinism is the
property a real pod needs from every host, so the emulation diffs the
real thing, it just manufactures the host axis sequentially.

See docs/STATIC_ANALYSIS.md § spmdcheck and docs/PARALLELISM.md
§ multi-host readiness.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from pytorchvideo_accelerate_tpu.utils.sync import make_lock

# entries shown on each side of a first-divergence report: enough trailing
# context to see the schedule drift without dumping whole epochs
DEFAULT_WINDOW = 5


class CollectiveScheduleRecorder:
    """Ordered (tick, op, detail) records per host label.

    One instance records one experiment; hosts are keyed by label
    (`host=i/n`, the `hangcheck.host_tag()` format). In a real pod every
    process records exactly one host; emulated lanes switch labels with
    `as_host` between replays of the same segment.
    """

    def __init__(self, host: str = "host=0/1"):
        self._lock = make_lock("CollectiveScheduleRecorder._lock")
        self._host = host
        self._schedules: Dict[str, List[Tuple[int, str, str]]] = {}

    # --- recording --------------------------------------------------------

    def record(self, op: str, detail: str = "") -> None:
        """Append one schedule point under the current host label (called
        by `hangcheck.collective_section` at section ENTRY — issue order,
        not completion order, is the schedule)."""
        with self._lock:
            sched = self._schedules.setdefault(self._host, [])
            sched.append((len(sched), str(op), str(detail)))

    def set_host(self, host: str) -> None:
        with self._lock:
            self._host = str(host)

    @contextmanager
    def as_host(self, host: str):
        """Record the enclosed segment under an emulated host label (the
        forced-host MULTICHIP lane / selftest replay mechanism)."""
        with self._lock:
            prev, self._host = self._host, str(host)
        try:
            yield self
        finally:
            with self._lock:
                self._host = prev

    # --- reading ----------------------------------------------------------

    def hosts(self) -> List[str]:
        with self._lock:
            return sorted(self._schedules)

    def schedule(self, host: Optional[str] = None) -> List[Tuple[int, str, str]]:
        with self._lock:
            return list(self._schedules.get(host or self._host, ()))

    def schedules(self) -> Dict[str, List[Tuple[int, str, str]]]:
        with self._lock:
            return {h: list(s) for h, s in self._schedules.items()}

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {h: len(s) for h, s in self._schedules.items()}

    def clear(self) -> None:
        with self._lock:
            self._schedules.clear()

    def snapshot(self) -> dict:
        """Doctor view: per-host record counts + each host's last entry."""
        with self._lock:
            return {
                "hosts": sorted(self._schedules),
                "counts": {h: len(s) for h, s in self._schedules.items()},
                "last": {h: list(s[-1]) for h, s in self._schedules.items()
                         if s},
            }


def install_schedule_recorder(recorder: CollectiveScheduleRecorder) -> None:
    """Route every `collective_section` entry to `recorder` (arm)."""
    from pytorchvideo_accelerate_tpu.parallel import hangcheck

    hangcheck._set_schedule_recorder(recorder)


def uninstall_schedule_recorder() -> None:
    from pytorchvideo_accelerate_tpu.parallel import hangcheck

    hangcheck._set_schedule_recorder(None)


def current_recorder() -> Optional[CollectiveScheduleRecorder]:
    from pytorchvideo_accelerate_tpu.parallel import hangcheck

    return hangcheck._schedule_recorder()


# --- cross-host differ ------------------------------------------------------

def diff_schedules(schedules: Dict[str, List[Tuple[int, str, str]]],
                   window: int = DEFAULT_WINDOW) -> dict:
    """Compare per-host ordered schedules; report the first divergence.

    The lexicographically-first host is the reference (every host is
    equally authoritative on a healthy pod — identical schedules make the
    choice irrelevant, and a deterministic choice keeps the report
    stable). Returns::

        {"diverged": bool,
         "divergence_count": int,        # hosts that drifted from the ref
         "hosts": [...], "lengths": {host: n},
         "first_divergence": None | {
             "tick": int,                # first disagreeing position
             "hosts": {host: [tick, op, detail] | None},  # None = missing
             "window": {host: trailing entries up to the tick},
         }}

    A host whose schedule simply ENDS early counts as divergent at the
    first missing tick (`hosts[h] is None`) — that is the skipped-
    collective deadlock shape, not a benign short run.
    """
    hosts = sorted(schedules)
    report: dict = {
        "diverged": False,
        "divergence_count": 0,
        "hosts": hosts,
        "lengths": {h: len(schedules[h]) for h in hosts},
        "first_divergence": None,
    }
    if len(hosts) < 2:
        return report
    ref_host, others = hosts[0], hosts[1:]
    ref = schedules[ref_host]
    diverged_hosts = set()
    first: Optional[dict] = None
    for h in others:
        sched = schedules[h]
        n = max(len(ref), len(sched))
        for i in range(n):
            a = ref[i] if i < len(ref) else None
            b = sched[i] if i < len(sched) else None
            # compare (op, detail) — the tick is positional by construction
            if (a and a[1:]) == (b and b[1:]):
                continue
            diverged_hosts.add(h)
            if first is None or i < first["tick"]:
                first = {
                    "tick": i,
                    "hosts": {ref_host: list(a) if a else None,
                              h: list(b) if b else None},
                    "window": {
                        ref_host: [list(e) for e in ref[max(0, i - window):i + 1]],
                        h: [list(e) for e in sched[max(0, i - window):i + 1]],
                    },
                }
            break
    if diverged_hosts:
        report["diverged"] = True
        report["divergence_count"] = len(diverged_hosts)
        report["first_divergence"] = first
    return report


def publish_schedule_report(report: dict) -> None:
    """`pva_spmd_schedule_divergence` gauge + a flight-ring event on any
    divergence (the graphcheck/tsan publish discipline; telemetry stays
    optional)."""
    try:
        from pytorchvideo_accelerate_tpu import obs

        obs.get_registry().gauge(
            "pva_spmd_schedule_divergence",
            "hosts whose recorded collective schedule diverged from the "
            "reference in the last diff (0 == identical schedules)",
        ).set(float(report.get("divergence_count", 0)))
        if report.get("diverged"):
            first = report.get("first_divergence") or {}
            obs.get_recorder().record(
                "spmd", "schedule divergence",
                divergence_count=report.get("divergence_count", 0),
                tick=first.get("tick"),
                hosts={h: (e[1] if e else None)
                       for h, e in (first.get("hosts") or {}).items()})
    except Exception:  # telemetry must never fail the differ
        pass


def format_divergence(report: dict) -> str:
    """One readable paragraph per divergence report (chaos legs / CLI)."""
    if not report.get("diverged"):
        return (f"schedules identical across {len(report.get('hosts', []))} "
                f"host(s): {report.get('lengths')}")
    first = report.get("first_divergence") or {}
    lines = [f"collective-schedule divergence across "
             f"{report.get('divergence_count')} host(s) at tick "
             f"{first.get('tick')}:"]
    for h, entry in sorted((first.get("hosts") or {}).items()):
        if entry is None:
            lines.append(f"  {h}: <no collective issued — schedule ended>")
        else:
            lines.append(f"  {h}: op={entry[1]!r} detail={entry[2]!r}")
    for h, win in sorted((first.get("window") or {}).items()):
        tail = ", ".join(f"{t}:{op}" for t, op, _ in win)
        lines.append(f"  {h} trailing window: [{tail}]")
    return "\n".join(lines)

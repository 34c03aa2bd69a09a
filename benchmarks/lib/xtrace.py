"""Reading the profiler's trace and reducing it to numbers.

`load(path)` turns an `.xplane.pb` (read with `jax.profiler.ProfileData`,
nothing but JAX) into plain data:

    [{"name": plane, "lines": [{"name": line, "events": [Event, ...]}]}]

with `Event = (name, start_ns, dur_ns, stats)`; only the stats in `KEEP` are
kept. The same structure, as JSON, is the recorded fixture the tests reduce
(`benchmarks/tests/fixtures/`), so every function below works on plain data
and is checked without a chip.

What is read from a TPU trace (see PERF.md, "Layers", for what was found by
looking at one by hand):

* planes named `/device:TPU:<n>` are the chips;
* their line `XLA Ops` holds one event per executed HLO op, nested where an
  op contains others. On a v5e with jax 0.9 the event's name is the HLO
  instruction's text (`%fusion.9 = ...`) and its stats hold only the device
  offset and duration: no name stack. The scope comes from the compiled
  step's own text instead (`benchmarks/lib/hlo.py`), by instruction name;
* their line `XLA Modules` holds one event per execution of a compiled
  program, named after the jitted function (`jit_step(...)`);
* the plane `/host:CPU` holds the host threads with the `TraceAnnotation`s.
"""

from __future__ import annotations

import re
from collections import namedtuple

from benchmarks.lib import hlo

Event = namedtuple("Event", "name start_ns dur_ns stats")

KEEP = ("tf_op", "long_name", "hlo_category", "hlo_op", "hlo_module",
        "step_num", "name", "program_id", "run_id", "group_id")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


HOST_NAMES = ("bench/prefetch_next", "train")  # the annotations we read


def load(path):
    """Only what the reduction reads is kept: the chips' op and module lines,
    and the host's events called `HOST_NAMES` (a host plane can hold millions
    of runtime events)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if not is_device and ev.name not in HOST_NAMES:
                    continue
                stats = {}
                for key, value in ev.stats:
                    if key in KEEP:
                        stats[key] = value if isinstance(value, (int, float)) \
                            else str(value)
                events.append(Event(ev.name, float(ev.start_ns),
                                    float(ev.duration_ns), stats))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def from_json(doc):
    """The fixture's form back into planes of `Event`s."""
    return [{"name": p["name"],
             "lines": [{"name": ln["name"],
                        "events": [Event(e[0], float(e[1]), float(e[2]), e[3])
                                   for e in ln["events"]]}
                       for ln in p["lines"]]}
            for p in doc]


# --- interval arithmetic ---------------------------------------------------


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(a, b):
    """The parts of the merged intervals `a` that no interval of `b` covers."""
    out = []
    b = list(b)
    for start, end in a:
        cur = start
        for bs, be in b:
            if be <= cur or bs >= end:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= end:
                break
        if cur < end:
            out.append((cur, end))
    return out


def overlap(a_start, a_end, b_start, b_end):
    return max(0.0, min(a_end, b_end) - max(a_start, b_start))


def self_times(events):
    """[(event, self_ns)]: each event's duration less the time its children
    (events nested inside it on the same line) cover."""
    out = []
    stack = []  # [event, end, children_ns]
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        end = ev.start_ns + ev.dur_ns
        # an event is a child only where it lies wholly inside the one before
        while stack and (ev.start_ns >= stack[-1][1] or end > stack[-1][1]):
            done = stack.pop()
            out.append((done[0], max(done[0].dur_ns - done[2], 0.0)))
        if stack:
            stack[-1][2] += ev.dur_ns
        stack.append([ev, end, 0.0])
    while stack:
        done = stack.pop()
        out.append((done[0], max(done[0].dur_ns - done[2], 0.0)))
    return out


def scope_of(ev, scopes=None):
    """The op's place in the program: the compiled program's name stack for
    this instruction (benchmarks/lib/hlo.py) where we have it, else the one
    the trace carries, else nothing."""
    if scopes:
        found = scopes.get(hlo.instruction_name(ev.name))
        if found:
            return found
    return ev.stats.get("tf_op") or ev.stats.get("long_name") or ""


def percentile(values, q):
    """The q-th percentile (0..100), linear between the ranks."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)


# --- the reduction ---------------------------------------------------------


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def device_planes(planes):
    found = []
    for p in planes:
        m = DEVICE_PLANE.match(p["name"])
        if m:
            found.append((int(m.group(1)), p))
    return [p for _, p in sorted(found, key=lambda x: x[0])]


def host_annotations(planes, names):
    """Intervals of the host's `TraceAnnotation`s called `names`, by name."""
    out = {n: [] for n in names}
    for p in planes:
        if p["name"] != HOST_PLANE:
            continue
        for ln in p["lines"]:
            for ev in ln["events"]:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns, ev.start_ns + ev.dur_ns))
    return out


ARMING_GAP_NS = 1e9  # a gap this long between two executions of the step


def armed_steps(steps):
    """The step executions after the profiler has armed. On this runtime
    (v5e, jax 0.9) arming stalls the device's queue once in every trace, for
    3-6 s, seconds AFTER `start_trace` has returned (PERF.md, Findings: 12 of
    12 traced runs; it overlaps no host call of the harness, and tracing on
    until the host has seen it pass costs more host memory than the machine
    has). So the FIRST gap of `ARMING_GAP_NS` or more between two executions
    is taken for it, and the executions up to it are left out, with the
    trace's first execution, which may be cut (where there are four or
    more). Every later gap, however long, is inside the window and counts as
    idle."""
    if len(steps) > 3:
        steps = steps[1:]
    for i, (a, b) in enumerate(zip(steps, steps[1:])):
        if b.start_ns - (a.start_ns + a.dur_ns) >= ARMING_GAP_NS:
            return steps[i + 1:]
    return steps


def reduce_device(plane, step_name):
    """One chip's numbers: window, busy union, the step program's executions,
    op self times.

    The window runs from the start of the first execution of the step program
    after the profiler has armed (`armed_steps`) to the end of the last one.
    Where the trace holds fewer than two such executions the window is every
    event."""
    ops = _line(plane, OPS_LINE)
    modules = _line(plane, MODULES_LINE)
    if not ops and not modules:
        return None
    steps = armed_steps(sorted((e for e in modules if step_name in e.name),
                               key=lambda e: e.start_ns))
    if len(steps) >= 2:
        w0, w1 = steps[0].start_ns, steps[-1].start_ns + steps[-1].dur_ns
    else:
        spans = [(e.start_ns, e.start_ns + e.dur_ns) for e in ops + modules]
        w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    ops = [e for e in ops if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]
    busy = union([(e.start_ns, e.start_ns + e.dur_ns) for e in ops])
    return {
        "programs": union([(e.start_ns, e.start_ns + e.dur_ns) for e in modules
                           if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]),
        "busy": busy, "busy_ns": total(busy), "window": (w0, w1),
        "steps": steps, "ops_self": self_times(ops),
    }


def reduce(planes, step_name="jit_step", scopes=None):
    """The numbers the per-layer readers and the result line take from one
    traced window. Durations are seconds; `ops` keeps (scope, name, category,
    self seconds) of the first chip for the readers that select by scope."""
    devices = [d for d in (reduce_device(p, step_name)
                           for p in device_planes(planes)) if d]
    if not devices:
        return {"busy_s": None, "window_s": None, "devices": 0, "ops": [],
                "step_ms": [], "step_gap_ms": [], "breakdown": None,
                "traced_steps": 0}
    first = devices[0]
    busy_s = sum(d["busy_ns"] for d in devices) / len(devices) / 1e9
    window_s = max(d["window"][1] - d["window"][0] for d in devices) / 1e9
    steps = first["steps"]
    step_ms = [e.dur_ns / 1e6 for e in steps]
    gaps = [(b.start_ns - (a.start_ns + a.dur_ns)) / 1e6
            for a, b in zip(steps, steps[1:])]
    ops = [(scope_of(e, scopes), hlo.instruction_name(e.name),
            str(e.stats.get("hlo_category", "")), s / 1e9)
           for e, s in first["ops_self"] if s > 0]

    by_name = {}
    for scope, name, _cat, s in ops:
        key = _short(scope, name)
        by_name[key] = by_name.get(key, 0.0) + s
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]

    w0, w1 = first["window"]
    idle = subtract([(w0, w1)], first["busy"])
    host = host_annotations(planes, HOST_NAMES)
    gaps_by_cause = {}
    running = first["programs"]
    cursor = 0
    for a, b in idle:
        while cursor < len(running) and running[cursor][1] <= a:
            cursor += 1
        if cursor < len(running) and running[cursor][0] <= a and b <= running[cursor][1]:
            cause = "between the ops of a running program"
        else:
            cause = _cause(a, b, host)
        gaps_by_cause.setdefault(cause, []).append((b - a) / 1e9)
    idle_gaps = sorted(((f"{cause} (longest of {len(v)}, {sum(v):.6f}s in all)",
                         max(v)) for cause, v in gaps_by_cause.items()),
                       key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s, "window_s": window_s, "devices": len(devices),
        "ops": ops, "step_ms": step_ms, "step_gap_ms": gaps,
        "traced_steps": len(steps),
        "breakdown": {"device_ops": [[k, v] for k, v in device_ops],
                      "idle_gaps": [[k, v] for k, v in idle_gaps]},
    }


def _short(scope, name):
    """A readable key for an op: its HLO name without the instance number and
    the tail of (the first of) its scopes."""
    first = str(scope).split(" | ")[0]
    tail = "/".join(first.split("/")[-3:])
    base = re.sub(r"(\.clone)*(\.\d+)*$", "", name.lstrip("%"))
    return f"{base} @ {tail}" if tail else base


def _cause(a, b, host):
    """What the host was doing during the device's idle gap (a, b): the
    annotation that covers most of it, or `unattributed`."""
    best, best_ns = "unattributed", 0.0
    for name, spans in host.items():
        covered = sum(overlap(a, b, s, e) for s, e in spans)
        if covered > best_ns:
            best, best_ns = name, covered
    if best_ns < 0.5 * (b - a):
        return "unattributed"
    return {"bench/prefetch_next": "waiting for the next batch",
            "train": "dispatching the step"}.get(best, best)


def scope_seconds(ops, pattern):
    """Self seconds of the ops whose scope matches `pattern` (a regex)."""
    rx = re.compile(pattern)
    return sum(s for scope, _n, _c, s in ops if rx.search(str(scope)))

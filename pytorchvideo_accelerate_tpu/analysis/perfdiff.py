"""`pva-tpu-perfdiff`: compare two bench rounds, gate on regressions.

The bench emits one headline JSON line per round (bench.py finalize);
the driver archives them as `BENCH_r*.json` (either the bare headline
dict, a driver record whose `tail` holds the line, or a
`bench_partial.json` with a `headline` key — all three load here). This
tool diffs two rounds key by key, with DIRECTION awareness (clips/s up is
good, p99 down is good), and exits 1 when any watched key regressed past
the threshold — the perf-diff gate every later perf PR reads.

The ROADMAP standing constraint is enforced, not advised: a round flagged
`suspect: true` has no trustworthy device numbers (a CPU smoke run, a
sync that returned early), so diffing it would manufacture fake regressions or fake wins —
the tool REFUSES (exit 2) unless `--allow-suspect` explicitly overrides
(useful only for comparing two smoke rounds' plumbing).

Exit codes: 0 no regression, 1 regression past threshold, 2 usage error
or suspect-round refusal. Wired into scripts/analyze.sh as a NON-fatal
report over the two newest rounds (perf trends inform, gates live in
bench --smoke); CI that wants it fatal calls it directly.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, Optional, Sequence

# headline keys worth diffing, by direction. Keys absent from either
# round are skipped (lanes come and go across rounds).
HIGHER_BETTER = (
    "value",                    # flagship clips/s/chip
    "trainer_cps_chip",
    "trainer_vs_rawstep",
    "tflops_per_sec",
    "mfu",
    "mfu_analytic",
    "trainer_mfu",
    "multichip_mfu",
    "multichip_mfu_analytic",
    "serve_rps",
    "serve_fill_ratio",
    # per-kernel fused-vs-reference speedups (pva-tpu-kbench): the keys
    # that make a bench-trajectory move attributable to ONE kernel —
    # same-backend ratios, only comparable when kbench_platform matches
    # across the two rounds (the suspect-refusal rule keeps CPU-fallback
    # rounds from headlining device claims in the first place)
    "kbench_dw_x3d_res3_speedup",
    "kbench_pw_x3d_res3_speedup",
    "kbench_conv133_sf_res4_speedup",
    "kbench_conv311_sf_res4_speedup",
    # PIPELINE lane: pipelined clips/s/chip at the lane's P-stage point
    "pipeline_cps_per_chip",
    # STREAM lane: per-label cost ratio, full-recompute / incremental
    # (streaming/; docs/SERVING.md § streaming)
    "stream_incremental_speedup",
    # STREAM lane trunk reuse: per-label advance cost ratio, full-trunk
    # token ring / KV-ring incremental trunk (docs/SERVING.md
    # § trunk-reuse) — only headlined when the top-1 quality gate holds
    "stream_trunk_speedup",
    # incremental banded attention vs full-recompute attention at the
    # videomae_b stream shape (ops/attention.incremental_band_attention)
    "kbench_attn_causal_inc_speedup",
    "kbench_attn_windowed_inc_speedup",
    # FLEET_AUTO lane: model families served off ONE pool under the
    # shared budget (fleet/control/multimodel.py) — a drop means a
    # family fell off the fleet
    "fleet_models_served",
)
LOWER_BETTER = (
    "step_ms_blocked",
    "serve_p50_ms",
    "serve_p99_ms",
    "serve_p99_ms_under_load",
    "swap_blackout_ms",
    "fleet_shed_frac",
    "trainer_input_wait_frac",
    "obs_input_wait_frac",
    "trace_overhead_frac",
    # PIPELINE lane: realized fill/drain idle fraction (two-point fit)
    "pipeline_bubble_frac",
    # STREAM lane: label-latency tail under open-loop stream load, and
    # the exact per-advance H2D payload fraction (s/T)
    "stream_p99_ms",
    "stream_h2d_bytes_frac",
    # trunk-reuse quality gate: |top-1(full) - top-1(banded)| on the
    # fixed-seed synthetic eval — the gate that decides whether
    # stream_trunk_speedup may headline at all
    "stream_trunk_top1_delta",
    # FLEET_AUTO lane (fleet/control/): seconds from the traffic step to
    # the autoscaler's last scaling action, advances shed across the
    # scale-down drain, and rollbacks the seeded-regression canary took
    # (a rise past 1 means the ladder needed extra strikes — the canary
    # verdict got less decisive)
    "autoscale_converge_s",
    "fleet_scaledown_shed_frac",
    "canary_rollback",
    # pva-tpu-hbm: device high-water mark from the memory ledger (backend
    # peak_bytes_in_use where measured, peak attributed bytes elsewhere);
    # null -> number is the metric APPEARING on the first measured round
    "hbm_peak_bytes",
)


def load_round(path: str) -> dict:
    """Load one round in any of its archived shapes; raises ValueError
    with the path when no headline dict can be found."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        if "metric" in data and "value" in data:
            return data
        if isinstance(data.get("headline"), dict):
            return data["headline"]
        if isinstance(data.get("parsed"), dict) and "value" in data["parsed"]:
            return data["parsed"]  # driver record with a pre-parsed line
        tail = data.get("tail")
        if isinstance(tail, str):
            # the child-output protocol's one parser (utils/forcehost):
            # the headline is the LAST JSON line of the captured tail
            from pytorchvideo_accelerate_tpu.utils.forcehost import (
                last_json_line,
            )

            parsed = last_json_line(tail)
            if isinstance(parsed, dict) and "value" in parsed:
                return parsed
    raise ValueError(f"{path}: no bench headline found "
                     "(expected a finalize() dict, a driver record with a "
                     "JSON line in 'tail', or bench_partial.json)")


def _pct(old: float, new: float) -> Optional[float]:
    if old == 0:
        return None
    return (new - old) / abs(old)


def diff_rounds(old: dict, new: dict, threshold: float = 0.05) -> dict:
    """Key-by-key comparison; a REGRESSION is a watched key moving in its
    bad direction by more than `threshold` (fractional)."""
    keys: Dict[str, dict] = {}
    regressions = []
    improvements = []
    appeared = []
    for key in HIGHER_BETTER + LOWER_BETTER:
        ov, nv = old.get(key), new.get(key)
        if ov is None and isinstance(nv, (int, float)) \
                and not isinstance(nv, bool):
            # null -> number is a metric APPEARING (a lane started
            # measuring something it couldn't before — e.g. mfu_analytic
            # landing on a round after an r02-shaped round whose mfu was
            # null), never a regression-from-zero or a divide-by-zero:
            # "wasn't measured" and "measured zero" are different facts
            keys[key] = {"old": None, "new": float(nv), "pct": None}
            appeared.append(key)
            continue
        if not isinstance(ov, (int, float)) or not isinstance(nv, (int, float)):
            continue
        ov, nv = float(ov), float(nv)
        pct = _pct(ov, nv)
        rec = {"old": ov, "new": nv,
               "pct": None if pct is None else round(pct, 4)}
        keys[key] = rec
        if pct is None:
            # zero baseline: no finite pct, but the DIRECTION still
            # classifies — a shed_frac/input_wait_frac that APPEARS is a
            # regression the gate must not skip. `threshold` doubles as
            # the absolute movement floor (these keys are fractions/ms,
            # so sub-threshold appearances are noise, not a verdict).
            if abs(nv - ov) <= threshold:
                continue
            worse = (nv > ov) == (key in LOWER_BETTER)
            (regressions if worse else improvements).append(key)
            continue
        bad = -pct if key in HIGHER_BETTER else pct
        if bad > threshold:
            regressions.append(key)
        elif bad < -threshold:
            improvements.append(key)
    # per-model clips/s/chip deltas (error strings skipped)
    models: Dict[str, dict] = {}
    om, nm = old.get("models") or {}, new.get("models") or {}
    for name in sorted(set(om) & set(nm)):
        ov, nv = om[name], nm[name]
        if not isinstance(ov, (int, float)) or not isinstance(nv, (int, float)):
            continue
        pct = _pct(float(ov), float(nv))
        models[name] = {"old": ov, "new": nv,
                        "pct": None if pct is None else round(pct, 4)}
        if pct is not None and -pct > threshold:
            regressions.append(f"models.{name}")
        elif pct is not None and pct > threshold:
            improvements.append(f"models.{name}")
    return {
        "threshold": threshold,
        "old_metric": old.get("metric"),
        "new_metric": new.get("metric"),
        "keys": keys,
        "models": models,
        "regressions": sorted(regressions),
        "improvements": sorted(improvements),
        "appeared": sorted(appeared),
        "ok": not regressions,
    }


def latest_rounds(directory: str, n: int = 2) -> list:
    """The n newest LOADABLE BENCH_r*.json rounds, oldest-first (round
    number == name order: BENCH_r03 < BENCH_r04 by construction).
    Headline-less rounds — a timeout round whose captured tail truncated
    mid-line is a shape the driver produces routinely — are skipped with
    a stderr note, so one broken round cannot starve the report while
    older readable rounds exist."""
    picked: list = []
    for path in sorted(glob.glob(os.path.join(directory, "BENCH_r*.json")),
                       reverse=True):
        try:
            load_round(path)
        except (OSError, ValueError) as e:
            print(f"pva-tpu-perfdiff: skipping {path}: {e}",
                  file=sys.stderr)
            continue
        picked.append(path)
        if len(picked) >= n:
            break
    return picked[::-1]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pva-tpu-perfdiff",
        description="diff two bench rounds' headline keys; exit 1 on a "
                    "regression past --threshold, 2 on a suspect round "
                    "(no trustworthy device numbers — refused)")
    ap.add_argument("old", nargs="?", default="",
                    help="older round (BENCH_rNN.json / headline JSON / "
                         "bench_partial.json); omit BOTH paths to diff "
                         "the two newest BENCH_r*.json under --dir")
    ap.add_argument("new", nargs="?", default="", help="newer round")
    ap.add_argument("--dir", default=".",
                    help="round directory for the no-path mode")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="fractional regression tolerance (default 5%%)")
    ap.add_argument("--allow-suspect", action="store_true",
                    help="diff suspect rounds anyway (plumbing "
                         "comparisons only; the numbers are NOT device "
                         "numbers)")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0
    if bool(args.old) != bool(args.new):
        print("pva-tpu-perfdiff: pass two rounds, or none (newest two "
              "under --dir)", file=sys.stderr)
        return 2
    if not args.old:
        rounds = latest_rounds(args.dir)
        if len(rounds) < 2:
            print(f"pva-tpu-perfdiff: fewer than 2 BENCH_r*.json rounds "
                  f"in {args.dir!r}; nothing to diff", file=sys.stderr)
            return 2
        args.old, args.new = rounds
    try:
        old, new = load_round(args.old), load_round(args.new)
    except (OSError, ValueError) as e:
        print(f"pva-tpu-perfdiff: {e}", file=sys.stderr)
        return 2
    if not args.allow_suspect:
        for label, rnd, path in (("old", old, args.old),
                                 ("new", new, args.new)):
            if rnd.get("suspect"):
                # the ROADMAP standing constraint: suspect rounds carry no
                # trustworthy device numbers; diffing them manufactures
                # fiction in either direction
                print(f"pva-tpu-perfdiff: REFUSED — {label} round {path} "
                      "is flagged suspect: true (no trustworthy device "
                      "numbers; --allow-suspect to compare plumbing "
                      "anyway)", file=sys.stderr)
                return 2
    report = diff_rounds(old, new, threshold=args.threshold)
    report["old_path"], report["new_path"] = args.old, args.new
    print(json.dumps(report))
    if report["regressions"]:
        print("pva-tpu-perfdiff: REGRESSION past "
              f"{args.threshold:.0%}: {', '.join(report['regressions'])}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

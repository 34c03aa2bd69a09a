"""`pva-tpu-lint`: the console front of the analysis package.

Exit code contract (scripts/lint.sh relies on
it): 0 = clean tree, 1 = findings, 2 = usage error. Output is one
`path:line:col: [rule] message` line per finding (the shape every
editor/CI annotator parses), or a JSON list with `--format json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from pytorchvideo_accelerate_tpu.analysis.core import (
    default_rules,
    run_lint,
)


def _package_dir() -> str:
    """Default lint target: the installed package tree itself."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pva-tpu-lint",
        description="AST-based JAX/TPU hazard linter (host-sync, recompile, "
                    "lock-discipline, tracer-leak, span-discipline); see "
                    "docs/STATIC_ANALYSIS.md")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: the "
                         "pytorchvideo_accelerate_tpu package tree)")
    ap.add_argument("--select", default="",
                    help="comma-list of rule names to run (default: all)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule taxonomy and exit")
    args = ap.parse_args(argv)

    rules = default_rules()
    if args.list_rules:
        for r in rules:
            print(f"{r.name}: {r.description}")
        return 0
    if args.select:
        wanted = {s.strip() for s in args.select.split(",") if s.strip()}
        unknown = wanted - {r.name for r in rules}
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))} "
                  f"(--list-rules shows the taxonomy)", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.name in wanted]

    paths = args.paths or [_package_dir()]
    for p in paths:
        if not os.path.exists(p):
            print(f"no such path: {p}", file=sys.stderr)
            return 2

    findings = run_lint(paths, rules=rules)
    if args.format == "json":
        print(json.dumps([f.__dict__ for f in findings], indent=1))
    else:
        for f in findings:
            print(f.format())
        print(f"pva-tpu-lint: {len(findings)} finding(s) over "
              f"{', '.join(paths)}", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

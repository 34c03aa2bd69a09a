"""Rule engine for the pva-tpu-lint static-analysis pass.

Everything here is stdlib-`ast` + `tokenize`: the linter must run in CI
and inside `pva-tpu-doctor` without importing jax
(or the package under analysis — a module with a broken import must still
be lintable).

The moving parts:

- `Finding`: one violation (path, line, col, rule, message).
- `Rule`: a named check over one parsed module (`ModuleInfo`), yielding
  findings. Concrete rules live in the `rules_*` siblings and register
  through `default_rules()`.
- Suppressions: `# pva: disable=<rule>[,<rule>...][ -- reason]` on the
  FIRST line of the flagged statement silences those rules for that line
  (`all` silences everything). The reason text after ` -- ` is surfaced
  by `utils/device_doctor.lint_snapshot()` so outstanding suppressions
  stay auditable instead of rotting silently.
- `run_lint(paths)`: walk files/trees, parse once, run every rule,
  filter suppressed findings, return the rest sorted.

Why `tokenize` for suppressions: a regex over raw lines would match the
marker inside string literals (this file itself would self-flag); comment
TOKENS cannot lie about being comments.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

SUPPRESS_RE = re.compile(
    r"#\s*pva:\s*disable=([A-Za-z0-9_,\- ]+?)(?:\s+--\s+(.*))?\s*$")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    path: str
    line: int
    col: int
    rule: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"


@dataclass
class Suppression:
    """One `# pva: disable=...` comment (line-scoped)."""

    line: int
    rules: Tuple[str, ...]  # ("all",) silences every rule on the line
    reason: str = ""

    def covers(self, rule: str) -> bool:
        return "all" in self.rules or rule in self.rules


@dataclass
class ModuleInfo:
    """One parsed module handed to every rule."""

    path: str  # display path (as given / walked)
    tree: ast.AST
    source: str
    suppressions: Dict[int, Suppression] = field(default_factory=dict)

    @property
    def posix_path(self) -> str:
        return self.path.replace(os.sep, "/")

    def matches(self, suffixes: Sequence[str]) -> bool:
        """Does this module's path end with any of the given suffixes
        (posix-style, e.g. "trainer/loop.py")?"""
        p = self.posix_path
        return any(p.endswith(s) for s in suffixes)


class Rule:
    """A named static check. Subclasses yield `Finding`s from `check`."""

    name: str = ""
    description: str = ""

    def check(self, module: ModuleInfo) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, module: ModuleInfo, node: ast.AST, message: str) -> Finding:
        return Finding(module.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), self.name, message)


# --- shared AST helpers (used by every rules_* sibling) ---------------------

def dotted_name(node: ast.AST) -> str:
    """"jax.jit" for Attribute/Name chains; "" for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def call_name(node: ast.Call) -> str:
    return dotted_name(node.func)


def walk_pruned(node: ast.AST, prune=()) -> Iterator[ast.AST]:
    """ast.walk that does NOT descend into child nodes of the `prune`
    types (the pruned node itself is still yielded). `ast.walk` + an
    `isinstance` skip does not do this — it yields the skipped node's
    descendants anyway, which is exactly wrong for scope analysis."""
    for child in ast.iter_child_nodes(node):
        yield child
        if not isinstance(child, prune):
            yield from walk_pruned(child, prune)


def walk_with_qualname(tree: ast.AST) -> Iterator[Tuple[ast.AST, str]]:
    """Yield (node, qualname-of-enclosing-scope) for every node; qualname
    is the "Class.method" chain of ClassDef/FunctionDef ancestors ("" at
    module level)."""

    def rec(node: ast.AST, scope: Tuple[str, ...]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                yield child, ".".join(scope)
                yield from rec(child, scope + (child.name,))
            else:
                yield child, ".".join(scope)
                yield from rec(child, scope)

    yield from rec(tree, ())


# --- suppression parsing ----------------------------------------------------

def iter_suppressions(source: str) -> Iterator[Suppression]:
    """Every `# pva: disable=...` comment in `source`, by line."""
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
            yield Suppression(tok.start[0], rules, (m.group(2) or "").strip())
    except tokenize.TokenError:
        # unterminated something: the ast parse will report it properly
        return


# --- runner -----------------------------------------------------------------

def default_rules() -> List[Rule]:
    """The shipped rule set (one import site so the CLI, the tests and
    the doctor all lint with identical rules)."""
    from pytorchvideo_accelerate_tpu.analysis.rules_dtype import DtypeLiteralRule
    from pytorchvideo_accelerate_tpu.analysis.rules_host_sync import HostSyncRule
    from pytorchvideo_accelerate_tpu.analysis.rules_knob import KnobReadRule
    from pytorchvideo_accelerate_tpu.analysis.rules_ledger import (
        LedgerDisciplineRule,
    )
    from pytorchvideo_accelerate_tpu.analysis.rules_lock import LockDisciplineRule
    from pytorchvideo_accelerate_tpu.analysis.rules_mesh import MeshDisciplineRule
    from pytorchvideo_accelerate_tpu.analysis.rules_recompile import RecompileHazardRule
    from pytorchvideo_accelerate_tpu.analysis.rules_span import SpanDisciplineRule
    from pytorchvideo_accelerate_tpu.analysis.rules_thread import (
        ThreadFactoryRule,
        ThreadJoinRule,
    )
    from pytorchvideo_accelerate_tpu.analysis.rules_trace import (
        TracePropagationRule,
    )
    from pytorchvideo_accelerate_tpu.analysis.rules_tracer import TracerLeakRule

    return [HostSyncRule(), RecompileHazardRule(), LockDisciplineRule(),
            TracerLeakRule(), SpanDisciplineRule(), ThreadFactoryRule(),
            ThreadJoinRule(), MeshDisciplineRule(), TracePropagationRule(),
            DtypeLiteralRule(), LedgerDisciplineRule(), KnobReadRule()]


def parse_module(source: str, path: str) -> ModuleInfo:
    tree = ast.parse(source, filename=path)
    sup = {s.line: s for s in iter_suppressions(source)}
    # a suppression on the FIRST line of a multi-line statement covers the
    # statement's own lines: findings anchor at sub-nodes (a wrapped call
    # arg lands on a continuation line), and the documented placement must
    # still silence them. Compound statements (def/for/with/if/...) extend
    # only across their HEADER — a comment on a block opener must never
    # silently disable a rule for the whole body (that would break the
    # line-scoped contract). Exact-line comments keep priority.
    if sup:
        covered = dict(sup)
        for node in ast.walk(tree):
            if not isinstance(node, ast.stmt):
                continue
            s = sup.get(node.lineno)
            end = getattr(node, "end_lineno", None)
            if s is None or end is None:
                continue
            body = getattr(node, "body", None)
            if isinstance(body, list) and body:
                end = body[0].lineno - 1  # header lines only
            for line in range(node.lineno + 1, end + 1):
                covered.setdefault(line, s)
        sup = covered
    return ModuleInfo(path=path, tree=tree, source=source, suppressions=sup)


def lint_source(source: str, path: str = "<string>",
                rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    """Lint one source string (the fixture-test entry point). `path` drives
    the hot-module matching, so fixtures fake a package-relative path."""
    rules = list(rules) if rules is not None else default_rules()
    try:
        module = parse_module(source, path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, e.offset or 0, "parse-error",
                        f"not parseable: {e.msg}")]
    findings: List[Finding] = []
    for rule in rules:
        for f in rule.check(module):
            sup = module.suppressions.get(f.line)
            if sup is not None and sup.covers(f.rule):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def iter_py_files(paths: Sequence[str]) -> Iterator[str]:
    """Expand files/directories into .py files (sorted, pycache skipped)."""
    for path in paths:
        if os.path.isdir(path):
            for root, dirs, files in os.walk(path):
                dirs[:] = sorted(d for d in dirs
                                 if d != "__pycache__"
                                 and not d.startswith("."))
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(root, fn)
        else:
            yield path


def run_lint(paths: Sequence[str],
             rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    """Lint every .py file under `paths`; returns all unsuppressed findings
    (empty list == clean tree, the CI gate)."""
    rules = list(rules) if rules is not None else default_rules()
    findings: List[Finding] = []
    for fp in iter_py_files(paths):
        try:
            with open(fp, encoding="utf-8") as f:
                source = f.read()
        except OSError as e:
            findings.append(Finding(fp, 1, 0, "parse-error",
                                    f"unreadable: {e}"))
            continue
        findings.extend(lint_source(source, path=fp, rules=rules))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings

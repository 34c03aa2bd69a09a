"""Rule `recompile`: call patterns that retrigger XLA compilation.

XLA compiles one executable per (function, static args, input shapes).
Two legal-Python patterns silently turn that into a compile per call:

1. **Unmarked static-looking arguments.** Calling a jitted function with
   a Python literal, `len(...)`, or a `.shape`-derived value as a
   positional argument traces a fresh executable every time the value
   changes (and weak-type churn can recompile even when it doesn't).
   Those arguments belong in `static_argnums`/`static_argnames` — or
   should be baked into the closure at build time, which is what
   `make_train_step` and friends do.

2. **jit-in-loop.** `jax.jit(f)` inside a `for`/`while` body constructs
   a FRESH jit wrapper per iteration — each with its own empty compile
   cache, so every iteration pays a full trace+compile (the classic
   "why is my serving loop 1000x slow" bug; the engine's keyed
   `self._fns` cache exists precisely to avoid this).

Static detection is heuristic by construction: it tracks names bound to
`jax.jit(...)` / `pjit(...)` results inside one module (`f = jax.jit(g)`
and `self.f = jax.jit(g)`) and inspects calls through those names. The
runtime counterpart (`analysis/recompile_guard.py` -> the
`pva_train_recompiles` gauge; the benchmark's `recompiles` must read 0) gives
the rule teeth beyond what syntax can prove.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from pytorchvideo_accelerate_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    Rule,
    call_name,
)

_JIT_NAMES = ("jit", "pjit")


def _is_jit_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and call_name(node).rsplit(".", 1)[-1] in _JIT_NAMES)


def _static_argnums(call: ast.Call) -> Tuple[Set[int], bool]:
    """(positions marked static, has_static_argnames) for a jit(...) call.
    Unparseable (computed) markings disable flagging for that callable —
    the rule must not guess."""
    nums: Set[int] = set()
    has_names = False
    parseable = True
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            vals = (kw.value.elts if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    nums.add(v.value)
                else:
                    parseable = False
        elif kw.arg == "static_argnames":
            has_names = True
    if not parseable:
        return nums, True  # treat as "anything may be static": stay quiet
    return nums, has_names


def _shape_derived(node: ast.AST) -> bool:
    """Does the expression read `.shape` / `.ndim` / call `len()` anywhere?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim"):
            return True
        if isinstance(sub, ast.Call) and call_name(sub) == "len":
            return True
    return False


class RecompileHazardRule(Rule):
    name = "recompile"
    description = ("jitted callables fed unmarked static-looking args, or "
                   "jax.jit constructed inside a loop")

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        # pass 1: names bound to jit(...) results, with their static markers
        jitted: Dict[str, Tuple[Set[int], bool]] = {}
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Assign) or not _is_jit_call(node.value):
                continue
            statics = _static_argnums(node.value)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    jitted[tgt.id] = statics
                elif (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    jitted["self." + tgt.attr] = statics

        yield from self._check_calls(module, jitted)
        yield from self._check_loops(module)

    def _check_calls(self, module: ModuleInfo,
                     jitted: Dict[str, Tuple[Set[int], bool]]
                     ) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            statics = jitted.get(call_name(node))
            if statics is None:
                continue
            static_nums, has_names = statics
            if has_names:
                continue  # named statics: positions unknowable, stay quiet
            for i, arg in enumerate(node.args):
                if i in static_nums:
                    continue
                if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, (int, float, bool)):
                    yield self.finding(
                        module, arg,
                        f"literal positional arg {i} to jitted "
                        f"`{call_name(node)}` is traced as a weak-typed "
                        "array — mark it static_argnums or close over it "
                        "at build time")
                elif _shape_derived(arg):
                    yield self.finding(
                        module, arg,
                        f"shape/len-derived positional arg {i} to jitted "
                        f"`{call_name(node)}` recompiles on every new "
                        "geometry — mark it static_argnums (intended) or "
                        "derive it inside the traced function")

    def _check_loops(self, module: ModuleInfo) -> Iterable[Finding]:
        loop_bodies: List[ast.AST] = []
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.For, ast.While)):
                loop_bodies.append(node)
        seen: Set[int] = set()
        for loop in loop_bodies:
            for node in ast.walk(loop):
                if node is loop or id(node) in seen:
                    continue
                # a nested def/lambda inside the loop body runs per CALL,
                # not per iteration — jit there is the cached-factory
                # pattern, not the hazard
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                    for sub in ast.walk(node):
                        seen.add(id(sub))
                    continue
                if _is_jit_call(node):
                    seen.add(id(node))
                    yield self.finding(
                        module, node,
                        "`jit(...)` constructed inside a loop builds a "
                        "fresh wrapper (and empty compile cache) per "
                        "iteration — hoist it out or cache it by key "
                        "(serving/engine.py `_fns` is the pattern)")

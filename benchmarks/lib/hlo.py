"""Scopes of the compiled step's instructions, from its HLO text.

The TPU's trace names each device event by its HLO instruction
(`%fusion.9 = ...`) and carries no name stack. The compiled program's text
does: every instruction has `metadata={op_name="jit(step)/.../res2_block0/
conv_b/conv_general_dilated"}`, the JAX name stack with the flax module path.
A fusion's own metadata is only its root's, so an instruction's scope here is
the op_names of everything it contains, joined by " | ": a conv+BN+ReLU fusion
is found under the conv layer's scope as well as under the norm's.
"""

from __future__ import annotations

import re

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def instruction_name(event_name):
    """`%fusion.9 = (...) fusion(...)` -> `fusion.9` (a bare name stays)."""
    m = re.match(r"^%?([\w.\-]+)(?: = |$)", event_name)
    return m.group(1) if m else event_name


def scopes(hlo_text):
    """{instruction name: "op_name | op_name | ..."} for every instruction of
    every computation of the module."""
    own = {}        # instruction -> its own op_name
    calls = {}      # instruction -> computations it calls
    members = {}    # computation -> its instructions
    current = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line.split("(")[0]:
            current = m.group(1)
            members[current] = []
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m or current is None:
            continue
        name = m.group(1)
        members[current].append(name)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        calls[name] = _CALLS.findall(line)

    cache = {}

    def inside(computation, depth=0):
        if computation in cache:
            return cache[computation]
        found = []
        if depth < 8:
            for name in members.get(computation, ()):
                if own[name]:
                    found.append(own[name])
                for sub in calls[name]:
                    found.extend(inside(sub, depth + 1))
        cache[computation] = found
        return found

    out = {}
    for name, op in own.items():
        names = [op] if op else []
        for sub in calls[name]:
            names.extend(inside(sub))
        out[name] = " | ".join(dict.fromkeys(names))
    return out

"""Forced-host multi-device subprocess helper.

One CPU host can impersonate an N-chip slice: XLA's
``--xla_force_host_platform_device_count=N`` flag gives a fresh process N
fake CPU devices, which is how mesh semantics (sharded steps, collective
layouts, mesh-reshape restores) are tested without a TPU — tier-1's
conftest does it in-process, but the flag latches at backend init, so any
ALREADY-INITIALIZED process (a test, a chaos scenario, a user
REPL) can only get a differently-sized device set by spawning a fresh
interpreter. This module is that spawn, packaged:

- `forced_host_env(n)` — the env block (JAX_PLATFORMS=cpu + XLA_FLAGS)
  for a subprocess that should see `n` CPU devices;
- `run_forced_host(code, n)` — run a python snippet under that env and
  parse its LAST stdout line as JSON (the child convention: logs to
  stderr, one machine-readable line to stdout).

Used by tests/test_zmesh.py and the pva-tpu-chaos mesh-reshape
preemption leg. Stdlib-only on purpose: the
caller never needs jax imported (and must not let its own device count
leak into the child).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Optional

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def last_json_line(stdout: str) -> Optional[dict]:
    """The child-output protocol, in one place: logs go to stderr and
    exactly one machine-readable JSON object is the final stdout line —
    scan lines in reverse, return the first that parses, None if none do."""
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


def forced_host_env(n_devices: int, extra_env: Optional[dict] = None) -> dict:
    """Environment for a fresh process that sees `n_devices` CPU devices.

    Any inherited force-count flag is REPLACED, not appended — XLA honors
    the first occurrence, so tier-1's ambient 8-device flag would otherwise
    silently win over the requested count."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_FORCE_FLAG)]
    flags.append(f"{_FORCE_FLAG}={int(n_devices)}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return env


def run_forced_host(code: str, n_devices: int, timeout: float = 600.0,
                    extra_env: Optional[dict] = None) -> dict:
    """Run `code` (a python source string) in a subprocess with `n_devices`
    forced CPU devices; returns the last stdout line parsed as JSON.

    The snippet's contract: print exactly one JSON object as its final
    stdout line (everything else goes to stderr). Raises RuntimeError with
    the stderr tail on a nonzero exit, a timeout, or unparseable output —
    a mesh test must fail loudly, never return half a result."""
    env = forced_host_env(n_devices, extra_env)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, timeout=timeout,
            capture_output=True, text=True)
    except subprocess.TimeoutExpired as e:
        tail = ((e.stderr or b"").decode() if isinstance(e.stderr, bytes)
                else (e.stderr or ""))[-2000:]
        raise RuntimeError(
            f"forced-host({n_devices}) subprocess timed out after "
            f"{timeout}s; stderr tail:\n{tail}") from e
    if proc.returncode != 0:
        raise RuntimeError(
            f"forced-host({n_devices}) subprocess exited "
            f"{proc.returncode}; stderr tail:\n{proc.stderr[-2000:]}")
    out = last_json_line(proc.stdout)
    if out is None:
        raise RuntimeError(
            f"forced-host({n_devices}) subprocess produced no JSON line; "
            f"stdout tail:\n{(proc.stdout or '')[-500:]}")
    return out

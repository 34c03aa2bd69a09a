"""No hidden CPU on the chip path (CPU-side checks; the chip run itself is
`python chip_smoke.py` through the chip tool): the smoke script refuses a
host without a TPU and starts no child, the compile cache goes where the
environment or the checkout says, and serving replicas inherit the
environment as it is."""

import ast
import json
import os
import subprocess
import sys

import jax
import pytest

from pytorchvideo_accelerate_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def test_without_a_tpu_the_smoke_exits_nonzero_with_ok_false(tmp_path):
    r = subprocess.run([sys.executable, SMOKE], cwd=tmp_path, timeout=300,
                       capture_output=True, text=True)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]
    assert '"ok": true' not in r.stdout


def test_smoke_starts_no_child_process():
    """The chip belongs to one process: every phase runs in the process
    that holds it, so the script has no way to start another."""
    tree = ast.parse(open(SMOKE).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"subprocess", "multiprocessing", "concurrent"}
    spawners = {"fork", "forkpty", "system", "popen", "posix_spawn",
                "spawnv", "spawnl", "execv", "execl", "execvp"}
    called = {n.func.attr for n in ast.walk(tree)
              if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)}
    assert not called & spawners


@pytest.fixture()
def config_updates(monkeypatch):
    """Values the helper writes into jax's config (nothing really set)."""
    values = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: values.append(value))
    return values


def test_cache_helper_leaves_a_placed_cache_alone(monkeypatch,
                                                  config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    assert config_updates == []  # no directory set in code


def test_cache_helper_defaults_to_the_checkout_from_any_cwd(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    dirs = []
    for cwd in (tmp_path, ROOT):
        monkeypatch.chdir(cwd)
        dirs.append(compile_cache.enable_compile_cache())
    assert dirs == [os.path.join(ROOT, ".jax_cache")] * 2
    assert config_updates == dirs


def test_spawn_serving_process_passes_no_platform_of_its_own(monkeypatch):
    from pytorchvideo_accelerate_tpu.fleet import pool

    seen = {}

    class FakeProc:
        pid = 4242

    def fake_popen(cmd, env=None, **kw):
        seen["cmd"], seen["env"] = cmd, env
        return FakeProc()

    monkeypatch.setattr(pool.subprocess, "Popen", fake_popen)
    monkeypatch.setattr(
        pool, "read_line_with_deadline",
        lambda *a, **k: ("pva-tpu-serve: http://127.0.0.1:1 model=x", False))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    pool.spawn_serving_process("/artifact")
    # env=None: the child inherits the environment exactly as it is
    assert seen["env"] is None
    assert "--serve.checkpoint" in seen["cmd"]

"""The repo's outward surface says what is: every console script of
`pyproject.toml` resolves to a callable, and every file path a document
names in backticks exists."""

import functools
import importlib
import os
import re
import tomllib

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "pytorchvideo_accelerate_tpu")

with open(os.path.join(ROOT, "pyproject.toml"), "rb") as _f:
    SCRIPTS = tomllib.load(_f)["project"]["scripts"]

DOCS = ["README.md", "MIGRATING.md", "PARITY.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(ROOT, "docs"))
    if name.endswith(".md"))

# a backticked token that is one path: letters, digits, `_-./{},*` only
# (a command line, a URL or prose in backticks is not a path)
_PATH = re.compile(r"`([A-Za-z0-9_\-./{},*<>]+\.(?:py|sh|json|md))`")


def test_sixteen_console_scripts():
    assert len(SCRIPTS) == 16 and all(n.startswith("pva-tpu-")
                                      for n in SCRIPTS)


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_console_script_resolves(name):
    module, _, attr = SCRIPTS[name].partition(":")
    assert callable(getattr(importlib.import_module(module), attr))


def _expand(token):
    """`a/{b,c}.py` names two files; anything else names itself."""
    m = re.fullmatch(r"(.*)\{([^{}]*)\}(.*)", token)
    if not m:
        return [token]
    return [m.group(1) + alt + m.group(3) for alt in m.group(2).split(",")]


@functools.lru_cache(maxsize=None)
def _repo_files():
    """Every file of the checkout as `/<path from the root>`, but what a
    run leaves behind (hidden, underscore and `chiprun_out` directories)."""
    out = []
    for d, subdirs, files in os.walk(ROOT):
        subdirs[:] = [s for s in subdirs
                      if not s.startswith((".", "_")) and s != "chiprun_out"]
        out += ["/" + os.path.relpath(os.path.join(d, f), ROOT)
                for f in files]
    return out


def _resolves(path, doc_dir):
    """Against the document's own directory, or by its tail anywhere in
    the checkout (`trainer/loop.py` and `loop.py` are how the documents
    write `pytorchvideo_accelerate_tpu/trainer/loop.py`); a made-up name
    (`<cell>.json`, `BENCH_r*.json`) is not a file of the repo."""
    if any(c in path for c in "<>*"):
        return True
    return (os.path.exists(os.path.join(doc_dir, path))
            or any(f.endswith("/" + path) for f in _repo_files()))


def _is_repo_file(path):
    """A bare `name.json` is what a run writes or a user supplies
    (`flight_record.json`, `cluster.json`), unless it is one of the root's
    records, which start with a capital (`BENCHMARK.json`)."""
    return ("/" in path or not path.endswith(".json")
            or path[0].isupper())


@pytest.mark.parametrize("doc", DOCS)
def test_doc_paths_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    doc_dir = os.path.dirname(os.path.join(ROOT, doc))
    missing = sorted({
        path for token in _PATH.findall(text) for path in _expand(token)
        if _is_repo_file(path) and not _resolves(path, doc_dir)})
    assert not missing, f"{doc} names files that do not exist: {missing}"

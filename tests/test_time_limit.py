"""tests/conftest.py's limit on each test: a test that runs past it fails
alone, named with its node id and the limit, and the run goes on."""

import os
import subprocess
import sys
import textwrap
import time

SLEEPER = """
import time

import conftest   # tests/conftest.py, loaded as the plugin `-p conftest`

conftest.LIMIT_S = 1


def test_sleeps_past_the_limit():
    time.sleep(60)


def test_passes_after_it():
    pass
"""


def test_a_test_past_its_limit_fails_alone_and_the_run_goes_on(tmp_path):
    (tmp_path / "test_sleeper.py").write_text(textwrap.dedent(SLEEPER))
    here = os.path.dirname(os.path.abspath(__file__))
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "test_sleeper.py"],
        cwd=str(tmp_path), env=dict(os.environ, PYTHONPATH=here),
        capture_output=True, text=True, timeout=120)
    out = proc.stdout
    assert proc.returncode == 1, out + proc.stderr
    assert time.monotonic() - start < 60, out
    assert "test_sleeper.py::test_sleeps_past_the_limit ran past its limit " \
        "of 1 s" in out, out
    assert "most recent call first" in out, out   # the threads' stacks
    assert "1 failed, 1 passed" in out, out

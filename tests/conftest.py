"""Test harness: 8 fake CPU devices.

The moral equivalent of accelerate's gloo-on-CPU subprocess trick (SURVEY §4):
`--xla_force_host_platform_device_count=8` gives JAX 8 CPU devices in one
process, so mesh sharding, implicit gradient psum, metric accumulation, and
checkpoint round-trips are tested with real (compiled) collectives and no TPU.

Must run before jax is imported, hence env mutation at import time. Children
the tests spawn inherit the same environment.

The budget: tier-1 runs as six xdist workers under `--dist loadfile` with a
limit on the whole run, so a file is one worker's from start to end and the
heaviest file bounds the run's end. Each test's setup and call get
`LIMIT_S` seconds of their own, about twice the slowest sound test (a
benchmark rehearsal that runs its job as subprocesses: 430-490 s among six
workers on an 8-core host). Past it the test fails with its node id, the
limit and every thread's stack, and the run goes on: a hang costs one test,
not the run. A change that adds tests states what they cost in seconds, and
keeps each file under the heaviest `tests/benchmarks/` file by sharing one
compile per module, toy shapes, or a split.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# tier-1 compiles hundreds of tiny programs once each: keep the persistent
# compile cache (utils/compile_cache.py) off for the session and its children
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

LIMIT_S = 900


@contextlib.contextmanager
def _limited(item):
    """Run a phase of `item` under SIGALRM: past `LIMIT_S` it fails with the
    node id, the limit and a `faulthandler` dump of every thread's stack."""
    def expired(signum, frame):
        with tempfile.TemporaryFile("w+") as dump:
            faulthandler.dump_traceback(dump, all_threads=True)
            dump.seek(0)
            stacks = dump.read()
        pytest.fail(f"{item.nodeid} ran past its limit of {LIMIT_S} s\n{stacks}",
                    pytrace=False)

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _limited(item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _limited(item):
        return (yield)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 fake CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def mesh8(devices8):
    from pytorchvideo_accelerate_tpu.config import MeshConfig
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_mesh

    return make_mesh(MeshConfig(data=8), devices=devices8)

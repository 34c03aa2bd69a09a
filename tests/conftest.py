"""Test harness: 8 fake CPU devices.

The moral equivalent of accelerate's gloo-on-CPU subprocess trick (SURVEY §4):
`--xla_force_host_platform_device_count=8` gives JAX 8 CPU devices in one
process, so mesh sharding, implicit gradient psum, metric accumulation, and
checkpoint round-trips are tested with real (compiled) collectives and no TPU.

Must run before jax is imported, hence env mutation at import time. Children
the tests spawn inherit the same environment.
"""

import os
import sys

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

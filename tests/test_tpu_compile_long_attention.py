"""The 8k and 16k causal and banded attention kernels of the token cells,
forward and gradient, bfloat16 and float32, compiled for a described TPU v5e
(the account of why is in described_v5e.py)."""

import pytest

from described_v5e import (  # noqa: F401 - v5e is a fixture
    DTYPES,
    LONG_ATTENTION,
    compiled_case,
    v5e,
)


@DTYPES
@pytest.mark.parametrize("name", LONG_ATTENTION)
def test_kernel_compiles_for_v5e(v5e, name, dtype):
    assert "tpu_custom_call" in compiled_case(v5e, name, dtype).as_text()

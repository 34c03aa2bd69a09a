"""Compute ops: attention backends (dense XLA, Pallas flash, ring/Ulysses
context-parallel), depthwise-conv lowerings, and the fused conv/norm/act
kernel tier for the slowfast/x3d hot paths (docs/KERNELS.md;
tests/test_zkernels.py holds each kernel to its XLA reference).

The fused kernels are NOT re-exported here on purpose: every in-tree
pallas import is lazy (function-local, the attention/depthwise
convention) so processes that never arm `fused_kernels` never pay the
pallas+mosaic import — reach them via
`pytorchvideo_accelerate_tpu.ops.pallas_fused`.
"""

from pytorchvideo_accelerate_tpu.ops.attention import dot_product_attention  # noqa: F401

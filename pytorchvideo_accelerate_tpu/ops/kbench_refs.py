"""XLA reference chains for the kernel parity tests and chip_smoke.py.

Each reference is the UNFUSED op sequence the model graph runs with
`model.fused_kernels=off` (conv, then the resolved norm affine as its
own pass, then the activation) — the baseline every fused kernel in
ops/pallas_fused.py is parity-checked against. They take the
same resolved (scale, bias) affine as the fused dispatchers so the two
sides compute the same function by construction, differing only in
lowering.

Kept out of pallas_fused.py so the reference can never accidentally
share code with the thing it is checking.
"""

from __future__ import annotations

from jax import lax

from pytorchvideo_accelerate_tpu.ops.pallas_fused import apply_act


def ref_conv_bn_act(x, w, scale, bias, *, act: str):
    """Dense stride-1 SAME conv -> per-channel affine -> act."""
    y = lax.conv_general_dilated(
        x, w, (1, 1, 1), [(k // 2, k // 2) for k in w.shape[:3]],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    return apply_act(y * scale + bias, act).astype(x.dtype)


def ref_pw_bn_act(x, w, scale, bias, *, act: str):
    """(1,1,1) conv -> affine -> act (the conv_a/conv_c chain)."""
    return ref_conv_bn_act(x, w, scale, bias, act=act)


def ref_dw_bn_act(x, k, scale, bias, *, act: str):
    """XLA grouped depthwise conv -> affine -> act (the conv_b chain)."""
    c = x.shape[-1]
    y = lax.conv_general_dilated(
        x, k, (1, 1, 1), [(d // 2, d // 2) for d in k.shape[:3]],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        feature_group_count=c)
    return apply_act(y * scale + bias, act).astype(x.dtype)

"""The FLOP and byte functions (benchmarks/lib/flops.py) against convs counted
by hand."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import flops
from benchmarks.reference import plain

PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _contractions(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)


def _conv(x, w, stride, groups):
    net = plain.Net({"params": {"c": {"kernel": w}}})
    return plain.conv3d(net, ("c",), x, w.shape[-1], w.shape[:3], stride, groups)


def test_pointwise_conv_by_hand():
    x = jnp.zeros((2, 4, 8, 8, 16))
    w = jnp.zeros((1, 1, 1, 16, 32))
    found = _contractions(lambda x, w: _conv(x, w, (1, 1, 1), 1), x, w)
    assert len(found) == 1
    cls, f, elems = found[0]
    assert cls == "conv_dense"
    assert f == 2 * (2 * 4 * 8 * 8) * 16 * 32
    assert elems == x.size + w.size + 2 * 4 * 8 * 8 * 32


def test_padded_strided_conv_counts_only_real_taps():
    # 1-D in effect: width 4, kernel 3, padding 1, stride 2 -> outputs at 0 and
    # 2; output 0 sees taps {-1,0,1} -> 2 real, output 1 sees {1,2,3} -> 3 real
    x = jnp.zeros((1, 1, 1, 4, 1))
    w = jnp.zeros((1, 1, 3, 1, 1))
    (cls, f, _), = _contractions(lambda x, w: _conv(x, w, (1, 1, 2), 1), x, w)
    assert f == 2 * (2 + 3)


def test_depthwise_forward_and_backward_classes():
    x = jnp.ones((1, 2, 4, 4, 8))
    w = jnp.ones((3, 3, 3, 1, 8))

    def loss(x, w):
        return jnp.sum(_conv(x, w, (1, 1, 1), 8))

    found = _contractions(jax.grad(loss, argnums=(0, 1)), x, w)
    # forward, data gradient and weight gradient, all of the depthwise class
    assert [c for c, *_ in found] == ["conv_depthwise"] * 3
    fwd = found[0][1]
    # valid taps: per dim sizes (2,4,4) with k=3,p=1 -> (4, 10, 10) real taps
    assert fwd == 2 * 8 * 1 * (4 * 10 * 10)
    # the three convs do the same multiply-adds
    assert found[1][1] == fwd and found[2][1] == fwd


def test_dot_and_least_time():
    a = jnp.zeros((4, 8))
    b = jnp.zeros((8, 16))
    found = _contractions(lambda a, b: jnp.dot(a, b), a, b)
    assert found == [("dot", 2.0 * 4 * 8 * 16, 4 * 8 + 8 * 16 + 4 * 16)]
    w = flops.work(found, PEAKS, bytes_per_element=2)["dot"]
    assert w["flops"] == 1024 and w["bytes"] == 2 * 224
    # memory bound: 448/10 s against 1024/100 s
    assert w["least_s"] == pytest.approx(44.8) and w["memory_bound"] == 1


def test_conv_outside_the_scopes_is_refused():
    x = jnp.zeros((1, 1, 4, 4, 1))
    w = jnp.zeros((1, 3, 3, 1, 1))
    jaxpr = jax.make_jaxpr(lambda x, w: jax.lax.conv_general_dilated(
        x, w, (1, 1, 1), "SAME", dimension_numbers=("NDHWC", "DHWIO", "NDHWC")))(x, w)
    with pytest.raises(ValueError):
        flops.contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)


def test_reference_work_x3d_toy():
    arch = {"depths": [1, 1, 1, 1], "stem_features": 24,
            "stage_features": [24, 48, 96, 192], "expansion": 2.25,
            "head_features": 64, "se_ratio": 0.0625, "num_classes": 5}
    w = flops.reference_work(
        "x3d", arch, {"video": ((2, 5, 32, 32, 3), "bfloat16"),
                      "label": ((2,), "int32")}, PEAKS)
    by = w["by_class"]
    assert by["conv_depthwise"]["n"] == 3 * (1 + 4)  # stem_t + 4 blocks, x3
    assert by["conv_dense"]["flops"] > by["conv_depthwise"]["flops"] > 0
    assert w["flops_per_step"] == sum(c["flops"] for c in by.values())

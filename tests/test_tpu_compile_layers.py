"""A layer's gradient at a cell's shapes, compiled for a described TPU v5e
(the account of why is in described_v5e.py): SlowFast's fast stem as the
lane-filling contraction, and the Gated-DeltaNet and attention kernels each
under the scope its device time is read by."""

import jax
import jax.numpy as jnp
import pytest

from described_v5e import F32, v5e  # noqa: F401 - v5e is a fixture
from pytorchvideo_accelerate_tpu.ops import attention, gated_delta


def test_fast_stem_lane_fold_compiles_lane_dense(v5e, monkeypatch):
    """SlowFast's fast stem ((5,7,7), stride (1,2,2), 3 -> 8) at
    `slowfast_r50.train`'s shape, forward and weight gradient, as the
    lane-filling contraction (ops/lane_fold.py): it compiles, the conv
    writes its output with the 128 folded channels minor (nn.Conv's is
    8 channels to a 128-lane tile, 16 times the bytes), and the program's
    temp stays under 1 GB (3.2 GB with nn.Conv). The described chip is not
    this process's default backend, so the rule's backend half is forced."""
    import re

    from pytorchvideo_accelerate_tpu.models.common import ConvBNAct
    from pytorchvideo_accelerate_tpu.ops import lane_fold

    monkeypatch.setattr(lane_fold, "takes_fold", lambda: True)
    stem = ConvBNAct(8, kernel=(5, 7, 7), stride=(1, 2, 2),
                     dtype=jnp.bfloat16)
    x_shape = (8, 32, 256, 256, 3)
    variables = jax.eval_shape(
        lambda: stem.init(jax.random.key(0), jnp.zeros(x_shape, jnp.bfloat16)))

    def loss(params, stats, x):
        with lane_fold.count_sites() as sites:
            y, _ = stem.apply({"params": params, "batch_stats": stats}, x,
                              train=True, mutable=["batch_stats"])
        assert len(sites) == 1
        return jnp.sum(y.astype(F32) ** 2)

    on_chip = jax.tree.map(lambda a: v5e(a.shape, a.dtype), variables)
    compiled = jax.jit(jax.grad(loss)).lower(
        on_chip["params"], on_chip["batch_stats"],
        v5e(x_shape, jnp.bfloat16)).compile()
    # the forward conv (fused with the batch sums) and the weight-gradient
    # conv: the last array of each result is the conv's own output
    convs = [line.split(" fusion(")[0]
             for line in compiled.as_text().splitlines()
             if " fusion(" in line and re.search(
                 r'op_name="[^"]*conv/conv_general_dilated"', line)]
    outputs = [re.findall(r"bf16\[([\d,]+)\]\{(\d+),", c)[-1] for c in convs]
    assert ("8,32,128,8,128", "4") in outputs  # B,T,H/2,W/32, 16 cols x 8 ch
    assert ("5,7,3,96,128", "4") in outputs    # kt,kh,blocks, 32 cols x 3, 128
    for dims, minor in outputs:
        assert dims.split(",")[int(minor)] == "128", convs
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_gated_delta_kernels_sit_under_the_scan_scope(v5e, monkeypatch):
    """A `GatedDeltaNet` layer's gradient at `qwen3_next_80b_a3b.train_8k`'s
    shapes, inside the model's rematerialised mixer unit, compiled for the chip
    with the rule's backend half forced: the forward, the rematerialised
    forward and the backward `pallas_call` are all there, and each sits under
    an `op_name` that contains `gdn/scan/`, the rule by which
    `gdn_scan_roofline` and `gdn_ms_per_step` select device time
    (benchmarks/lib/hlo.py): a `custom_vjp`'s backward keeps the call's name
    stack."""
    import re

    import flax.linen as nn

    from pytorchvideo_accelerate_tpu.models.qwen3_next import (
        Qwen3NextArch,
        _Mixer,
    )

    monkeypatch.setattr(gated_delta, "takes_kernel", lambda: True)
    monkeypatch.setattr(gated_delta, "_interpret", lambda: False)
    layer = nn.remat(_Mixer)(Qwen3NextArch(), jnp.bfloat16,
                             "linear_attention", name="mixer_0")
    x_shape = (2, 8192, 2048)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0),
                           jnp.zeros(x_shape, jnp.bfloat16)))["params"]

    def loss(params, x):
        with gated_delta.count_sites() as sites:
            y = layer.apply({"params": params}, x)
        assert len(sites) == 1
        return jnp.sum(y.astype(F32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(lambda a: v5e(a.shape, a.dtype), params),
        v5e(x_shape, jnp.bfloat16)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call" in line and "pva_gdn" in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert sorted(re.search(r"pva_gdn_\w+", n).group(0) for n in names) == [
        "pva_gdn_bwd", "pva_gdn_fwd_saving", "pva_gdn_fwd_saving"], names
    assert all("gdn/scan/" in n for n in names), names


@pytest.mark.parametrize("family,scope", [
    ("qwen3_next", "attn/core/"),
    ("smallthinker_full", "attn/core/"),
    ("smallthinker_window", "swa/core/"),
    ("ouro", "attn/core/"),
])
def test_attention_kernels_sit_under_the_core_scope(v5e, monkeypatch, family,
                                                    scope):
    """An attention layer's gradient at each token cell's shapes, inside the
    family's rematerialised mixer unit (which keeps the forward's `o` and
    `lse`), compiled for the chip with the rule's backend half forced: the
    forward (once: the unit's recomputation does not run it again), dq and
    dk/dv `pallas_call`s are all there and each sits under an `op_name` that
    contains `attn/core/` (`swa/core/` for a windowed layer), the rule by which
    `causal_attention_roofline` and `window_attention_roofline` select device
    time and raise over 100% (benchmarks/lib/scoped.py): a backward kernel
    outside the scope would turn a gain into `output_malformed`."""
    import re

    from pytorchvideo_accelerate_tpu.models import ouro, qwen3_next, smallthinker
    from pytorchvideo_accelerate_tpu.models.lm_common import (
        remat_keeping_attention,
    )

    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    if family == "qwen3_next":
        layer = remat_keeping_attention(qwen3_next._Mixer)(
            qwen3_next.Qwen3NextArch(), jnp.bfloat16, "full_attention",
            name="mixer_3")
        x_shape, window = (2, 8192, 2048), None
    elif family == "ouro":   # one layer execution: the loop's remat unit
        layer = remat_keeping_attention(ouro._Layer)(
            ouro.OuroArch(), jnp.bfloat16, name="layer_0")
        x_shape, window = (1, 4096, 2048), None
    else:
        arch = smallthinker.SmallThinkerArch()
        window = arch.sliding_window_size if family.endswith("window") else None
        layer = remat_keeping_attention(smallthinker._Mixer)(
            arch, jnp.bfloat16, rotary=window is not None, window=window,
            name="mixer_0")
        x_shape = (1, 16384, arch.hidden_size)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0),
                           jnp.zeros(x_shape, jnp.bfloat16)))["params"]

    def loss(params, x):
        with attention.count_kernel_sites() as sites:
            y = layer.apply({"params": params}, x)
        assert [(w, kept) for _shape, w, kept in sites] == [(window, True)]
        return sum(jnp.sum(leaf.astype(F32) ** 2)
                   for leaf in jax.tree.leaves(y)
                   if jnp.issubdtype(leaf.dtype, jnp.floating))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(lambda a: v5e(a.shape, a.dtype), params),
        v5e(x_shape, jnp.bfloat16)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call" in line and "pva_attn" in line]
    op_names = [re.search(r'op_name="([^"]*)"', line).group(1)
                for line in calls]
    assert sorted(re.search(r"pva_attn_\w+", n).group(0)
                  for n in op_names) == ["pva_attn_dkv", "pva_attn_dq",
                                         "pva_attn_fwd"], op_names
    assert all(scope in n for n in op_names), op_names

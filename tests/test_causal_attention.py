"""ops/attention.py `causal_gqa_attention` and `rotate_half`: causal softmax
attention with grouped queries, head size 256 and partial rotary, against the
dense masked product written out here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchvideo_accelerate_tpu.ops.attention import (
    causal_gqa_attention,
    rotate_half,
)


def _dense(q, k, v, scale):
    """Every query head against its key-value head, one masked product."""
    hq, hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _qkv(t, hq=4, hkv=2, d=256, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (2, t, hq, d)),
            jax.random.normal(ks[1], (2, t, hkv, d)),
            jax.random.normal(ks[2], (2, t, hkv, d)))


# float32 on the CPU: blocks change the order of the softmax's sums only
@pytest.mark.parametrize("t,block_q", [
    pytest.param(96, 512, id="one_dense_block"),
    pytest.param(96, 32, id="three_whole_blocks"),
    pytest.param(100, 32, id="ragged_last_block"),
])
def test_blocks_equal_the_dense_masked_product(t, block_q):
    q, k, v = _qkv(t)
    want = _dense(q, k, v, 256 ** -0.5)
    got = causal_gqa_attention(q, k, v, scale=256 ** -0.5, block_q=block_q)
    assert got.shape == q.shape
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_gradients_equal_the_dense_masked_product():
    q, k, v = _qkv(80, seed=1)
    cot = jax.random.normal(jax.random.key(9), q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    want = jax.grad(loss(lambda *a: _dense(*a, 256 ** -0.5)), (0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda *a: causal_gqa_attention(
        *a, scale=256 ** -0.5, block_q=32)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max() + 1)


def test_token_reads_no_later_key():
    q, k, v = _qkv(64, seed=2)
    base = causal_gqa_attention(q, k, v, block_q=16)
    k2 = k.at[:, 40:].add(3.0)
    v2 = v.at[:, 40:].add(3.0)
    moved = causal_gqa_attention(q, k2, v2, block_q=16)
    assert float(jnp.abs(moved[:, :40] - base[:, :40]).max()) == 0.0
    assert float(jnp.abs(moved[:, 40:] - base[:, 40:]).max()) > 0.1


def test_query_heads_must_divide_over_key_value_heads():
    q, k, v = _qkv(8, hq=3, hkv=2)
    with pytest.raises(ValueError, match="3 query heads over 2"):
        causal_gqa_attention(q, k, v)


def test_partial_rotary_rotates_the_first_dims_only():
    """64 of 256 dims, rotate-half pairing (i with i + 32), theta 1e7,
    against the angles written out in float64."""
    x = jax.random.normal(jax.random.key(3), (1, 50, 2, 256))
    out = rotate_half(x, jnp.arange(50), 1e7, 64)
    assert float(jnp.abs(out[..., 64:] - x[..., 64:]).max()) == 0.0
    xs = np.asarray(x, np.float64)
    freq = 1e7 ** (-np.arange(32) * 2.0 / 64)
    angle = np.arange(50)[:, None] * freq[None, :]
    cos, sin = np.cos(angle)[None, :, None, :], np.sin(angle)[None, :, None, :]
    a, b = xs[..., :32], xs[..., 32:64]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    assert float(np.abs(np.asarray(out[..., :64]) - want).max()) < 1e-4
    # position 0 is not rotated; norms are kept
    assert float(jnp.abs(out[:, 0] - x[:, 0]).max()) == 0.0
    assert float(jnp.abs(jnp.linalg.norm(out, axis=-1)
                         - jnp.linalg.norm(x, axis=-1)).max()) < 1e-4


# --- a trailing window: query t reads keys s with 0 <= t - s < window ---------

def _dense_banded(q, k, v, scale, window):
    """`_dense` under the band, as one masked product over all keys."""
    hq, hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    delta = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]
    mask = (delta >= 0) & (delta < window)
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


WINDOWS = [
    pytest.param(100, 16, 32, 4, 2, id="longer_than_the_window"),
    pytest.param(64, 16, 64, 4, 2, id="as_long_as_the_window"),
    pytest.param(48, 16, 64, 4, 2, id="shorter_than_the_window"),
    pytest.param(100, 16, 40, 4, 2, id="window_no_multiple_of_the_block"),
    pytest.param(100, 32, 7, 4, 2, id="window_shorter_than_a_block"),
    pytest.param(100, 16, 32, 7, 1, id="seven_query_heads_on_one"),
    pytest.param(100, 512, 32, 7, 1, id="seven_on_one_in_one_block"),
]


@pytest.mark.parametrize("t,block_q,window,hq,hkv", WINDOWS)
def test_window_equals_the_dense_banded_product(t, block_q, window, hq, hkv):
    q, k, v = _qkv(t, hq=hq, hkv=hkv, d=64, seed=4)
    want = _dense_banded(q, k, v, 0.125, window)
    got = causal_gqa_attention(q, k, v, scale=0.125, block_q=block_q,
                               window=window)
    assert got.shape == q.shape
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("t,block_q,window,hq,hkv",
                         [WINDOWS[i] for i in (0, 3, 4, 5)])
def test_window_gradients_equal_the_dense_banded_product(t, block_q, window,
                                                         hq, hkv):
    q, k, v = _qkv(t, hq=hq, hkv=hkv, d=64, seed=5)
    cot = jax.random.normal(jax.random.key(9), q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    want = jax.grad(loss(lambda *a: _dense_banded(*a, 0.125, window)),
                    (0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda *a: causal_gqa_attention(
        *a, scale=0.125, block_q=block_q, window=window)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max() + 1)


@pytest.mark.parametrize("block_q,window", [(16, 24), (16, 32), (512, 24)])
def test_query_reads_no_key_at_a_distance_of_the_window_or_more(block_q, window):
    """A huge value planted in the values up to position 20 reaches every
    query that may read one of them (t < 20 + window) and no other, though
    some of those keys lie in the key blocks the later queries' blocks read."""
    q, k, v = _qkv(96, d=64, seed=6)
    run = lambda v: causal_gqa_attention(  # noqa: E731
        q, k, v, block_q=block_q, window=window)
    base, moved = run(v), run(v.at[:, :21].add(1e4))
    reach = 20 + window
    assert float(jnp.abs(moved[:, reach:] - base[:, reach:]).max()) == 0.0
    assert float(jnp.abs(moved[:, :reach] - base[:, :reach])
                 .max(axis=(0, 2, 3)).min()) > 1.0


def _parent(q, k, v, scale=None, block_q=512):
    """`causal_gqa_attention` as it stood before it took a window, kept here
    word for word: `window=None` must trace to the same jaxpr."""
    from pytorchvideo_accelerate_tpu.precision import f32_island

    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    group = hq // hkv

    @jax.checkpoint
    def block(q_blk, k_seen, v_seen, start):
        n = q_blk.shape[1]
        rows = q_blk.reshape(b, n, hkv, group, d).transpose(0, 2, 3, 1, 4)
        rows = rows.reshape(b, hkv, group * n, d)
        logits = f32_island(jnp.einsum("bhrd,bkhd->bhrk", rows, k_seen)) * scale
        pos = start + jnp.arange(group * n) % n
        seen = jnp.arange(k_seen.shape[1])[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        out = jnp.einsum("bhrk,bkhd->bhrd", probs.astype(q.dtype), v_seen)
        out = out.reshape(b, hkv, group, n, d).transpose(0, 3, 1, 2, 4)
        return out.reshape(b, n, hq, d)

    outs = []
    for start in range(0, t, block_q):
        end = min(start + block_q, t)
        outs.append(block(q[:, start:end], k[:, :end], v[:, :end], start))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("window", [None, 100, 4096],
                         ids=["no_window", "window_of_the_length", "longer"])
def test_no_band_traces_to_the_jaxpr_it_had_before_the_window(window):
    """No window, and a window no shorter than the sequence, lower as the
    function did before: forward and gradient, jaxpr text for jaxpr text."""
    q, k, v = _qkv(100, d=64, seed=7)

    def text(fn):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), (0, 1, 2))
        return str(jax.make_jaxpr(fn)(q, k, v)), str(jax.make_jaxpr(grad)(q, k, v))

    assert text(lambda *a: causal_gqa_attention(
        *a, block_q=32, window=window)) == text(lambda *a: _parent(*a, block_q=32))


# --- the Pallas flash kernels (ops/pallas_attention.py), interpreted ----------
#
# `causal_gqa_attention`'s lowering on a TPU for heads a multiple of 128 wide,
# held here to the XLA form above and to `dense_attention` under the same
# mask. Blocks of 128 so that 300 tokens are three blocks, the last ragged.

def _mask(t, window):
    delta = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return (delta >= 0) & (delta < (t if window is None else window))


def _dense_attention(q, k, v, scale, window):
    """`dense_attention` under the band, the key heads repeated."""
    from pytorchvideo_accelerate_tpu.ops.attention import dense_attention

    k, v = (jnp.repeat(x, q.shape[2] // k.shape[2], axis=2) for x in (k, v))
    return dense_attention(q, k, v, scale=scale, mask=_mask(q.shape[1], window))


def _kernel(window, block_q=128, block_k=128):
    from pytorchvideo_accelerate_tpu.ops.pallas_attention import (
        causal_flash_attention,
    )

    return lambda q, k, v: causal_flash_attention(
        q, k, v, q.shape[-1] ** -0.5, window, True, block_q, block_k)


# t, query heads, key heads, head width, window, block_q, block_k
KERNEL = [
    pytest.param(300, 7, 1, 128, None, 128, 128, id="causal_groups_of_7"),
    pytest.param(300, 8, 1, 256, None, 128, 128, id="causal_groups_of_8_heads_of_256"),
    pytest.param(256, 2, 2, 128, None, 128, 128, id="causal_groups_of_1_whole_blocks"),
    pytest.param(300, 7, 1, 128, 128, 128, 128, id="window_of_a_block"),
    pytest.param(300, 14, 2, 128, 100, 128, 128, id="window_no_multiple_of_the_block"),
    pytest.param(300, 7, 1, 128, 7, 128, 128, id="window_shorter_than_a_block"),
    pytest.param(300, 8, 1, 256, 40, 128, 128, id="window_groups_of_8_heads_of_256"),
    pytest.param(300, 7, 1, 128, 300, 128, 128, id="window_of_the_length"),
    pytest.param(500, 7, 1, 128, 130, 256, 128, id="window_wide_query_blocks"),
    pytest.param(500, 7, 1, 128, 130, 128, 256, id="window_wide_key_blocks"),
    pytest.param(100, 7, 1, 128, 40, 512, 512, id="window_in_one_ragged_block"),
]
KERNEL_GRADS = [KERNEL[i] for i in (0, 1, 4, 5, 8, 9)]


@pytest.mark.parametrize("t,hq,hkv,d,window,block_q,block_k", KERNEL)
def test_kernel_equals_the_xla_form_and_the_dense_masked_product(
        t, hq, hkv, d, window, block_q, block_k):
    q, k, v = _qkv(t, hq=hq, hkv=hkv, d=d, seed=11)
    got = jax.jit(_kernel(window, block_q, block_k))(q, k, v)
    assert got.shape == q.shape and got.dtype == q.dtype
    xla = causal_gqa_attention(q, k, v, block_q=64, window=window)
    dense = _dense_attention(q, k, v, d ** -0.5, window)
    assert float(jnp.abs(got - xla).max()) < 2e-5
    assert float(jnp.abs(got - dense).max()) < 2e-5


@pytest.mark.parametrize("t,hq,hkv,d,window,block_q,block_k", KERNEL_GRADS)
def test_kernel_gradients_equal_the_xla_forms_and_the_dense_products(
        t, hq, hkv, d, window, block_q, block_k):
    q, k, v = _qkv(t, hq=hq, hkv=hkv, d=d, seed=12)
    cot = jax.random.normal(jax.random.key(9), q.shape)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * cot), (0, 1, 2)))(
            q, k, v)

    got = grads(_kernel(window, block_q, block_k))
    xla = grads(lambda *a: causal_gqa_attention(*a, block_q=64, window=window))
    dense = grads(lambda *a: _dense_attention(*a, d ** -0.5, window))
    for a, b, c in zip(got, xla, dense):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max() + 1)
        assert float(jnp.abs(a - c).max()) < 1e-4 * float(jnp.abs(c).max() + 1)


@pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
def test_kernel_in_bfloat16_rounds_where_the_xla_form_rounds(window):
    """bfloat16 operands, float32 scores and sums, the probabilities rounded
    to bfloat16 where they enter `p v` and the gradient products: forward and
    gradients within bfloat16's rounding (2^-8) of the XLA form's, which
    rounds at the same places in another order, and of the float32 product."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(300, hq=7, hkv=1, d=128,
                                                    seed=13))
    cot = jax.random.normal(jax.random.key(9), q.shape, jnp.bfloat16)

    def both(fn, *args):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        grads, out = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(*args)
        return (out,) + grads

    got = both(_kernel(window), q, k, v)
    xla = both(lambda *a: causal_gqa_attention(*a, block_q=64, window=window),
               q, k, v)
    f32 = both(lambda *a: _dense_attention(*a, 128 ** -0.5, window),
               *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, c in zip(got, xla, f32):
        assert a.dtype == jnp.bfloat16
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) < 2e-2 * float(jnp.abs(b).max())
        assert float(jnp.abs(a - c).max()) < 2e-2 * float(jnp.abs(c).max())


@pytest.fixture
def kernel_forced(monkeypatch):
    """The backend half of the rule as a TPU answers it; the kernels still
    run interpreted here."""
    from pytorchvideo_accelerate_tpu.ops import attention

    monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    return attention


@pytest.mark.parametrize("window", [None, 24, 150])
def test_kernel_reads_no_key_above_the_diagonal_or_behind_the_band(
        kernel_forced, window):
    """The moved-keys tests through the kernel (`causal_gqa_attention` with
    the rule forced; 600 tokens are two of its blocks): a huge value planted
    in the values up to position 20 reaches every query that may read one of
    them and no other, and keys moved from position 400 on reach no query
    before it."""
    q, k, v = _qkv(600, hq=7, hkv=1, d=128, seed=14)
    with kernel_forced.count_kernel_sites() as sites:
        run = jax.jit(lambda k, v: causal_gqa_attention(q, k, v, window=window))
        base = run(k, v)
    assert sites == [(q.shape, window, False)]  # no remat unit keeps it
    later = run(k.at[:, 400:].add(3.0), v.at[:, 400:].add(3.0))
    assert float(jnp.abs(later[:, :400] - base[:, :400]).max()) == 0.0
    assert float(jnp.abs(later[:, 400:] - base[:, 400:]).max()) > 0.1
    planted = run(k, v.at[:, :21].add(1e4))
    reach = 600 if window is None else 20 + window
    assert float(jnp.abs(planted[:, reach:] - base[:, reach:]).max(
        initial=0.0)) == 0.0
    assert float(jnp.abs(planted[:, :reach] - base[:, :reach])
                 .max(axis=(0, 2, 3)).min()) > 1.0


def test_window_no_shorter_than_the_sequence_lowers_as_none(kernel_forced):
    q, k, v = _qkv(600, hq=7, hkv=1, d=128, seed=15)

    def text(window):
        fn = lambda *a: causal_gqa_attention(*a, window=window)  # noqa: E731
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), (0, 1, 2))
        return str(jax.make_jaxpr(fn)(q, k, v)), str(jax.make_jaxpr(grad)(q, k, v))

    assert text(600) == text(None) == text(4096)
    assert "pva_attn_fwd" in text(None)[0] and "pva_attn_dkv" in text(None)[1]
    assert text(599) != text(None)


@pytest.mark.parametrize("backend_rule,shape,takes", [
    (False, (2, 8192, 16, 2, 256), False),      # the CPU's own rule
    (True, (2, 8192, 16, 2, 256), True),        # qwen3_next_80b_a3b.train_8k
    (True, (1, 16384, 28, 4, 128), True),       # smallthinker_21b_a3b.train_16k
    (True, (2, 128, 4, 2, 64), False),          # qwen3_next_t
    (True, (2, 128, 7, 1, 16), False),          # smallthinker_t
    (True, (1, 128, 28, 4, 128), False),        # the sample a model is
    (True, (1, 512, 16, 2, 256), False),        # initialised on; one block
    (True, (1, 513, 16, 2, 256), True),
], ids=["cpu", "qwen3_next_80b_a3b", "smallthinker_21b_a3b", "qwen3_next_t",
        "smallthinker_t", "init_sample", "one_block", "past_one_block"])
def test_the_rule_that_picks_the_lowering(monkeypatch, backend_rule, shape,
                                          takes):
    """Backend and head width, nothing a caller sets: traced at the cells'
    shapes (no product runs), the call is the kernels' or the XLA form's."""
    from pytorchvideo_accelerate_tpu.ops import attention

    if backend_rule:
        monkeypatch.setattr(attention, "takes_kernel", lambda: True)
    else:
        assert not attention.takes_kernel()     # this process runs on the CPU
    b, t, hq, hkv, d = shape
    args = [jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
            for h in (hq, hkv, hkv)]
    for window in (None, 32):
        with attention.count_kernel_sites() as sites:
            jaxpr = str(jax.make_jaxpr(lambda *a: causal_gqa_attention(
                *a, window=window))(*args))
        assert ("pallas_call" in jaxpr) == takes
        assert len(sites) == takes
    assert attention.kernel_shapes(t, d) == (d % 128 == 0 and t > 512)


@pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
def test_graphcheck_costs_the_kernels(kernel_forced, window):
    """graphcheck's flops pass has a hook for each `pva_attn_*` call (no
    finding), and the hooks count the products the kernels do: checked against
    the same count taken from the kernel bodies' own `dot_general`s, a grid
    step's times the grid (which walks only the tiles the mask lets through;
    `cond` takes the larger branch: the edge tile's and the inner tile's
    products are the same)."""
    from pytorchvideo_accelerate_tpu.analysis import gc_flops

    q, k, v = _qkv(2048, hq=7, hkv=1, d=128, seed=16)
    closed = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(causal_gqa_attention(*a, window=window)),
        (0, 1, 2)))(q, k, v)
    findings, summary = gc_flops.check_flops(closed)
    assert findings == [], findings
    assert summary["eqn_counts"]["pallas_call"] == 3

    def kernel_products(jaxpr):
        total = 0.0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grid = eqn.params["grid_mapping"].grid
                body = gc_flops.jaxpr_flops(
                    jax.extend.core.ClosedJaxpr(eqn.params["jaxpr"], ()))
                total += body["by_class"]["dot"] * gc_flops._prod(grid)
            for value in eqn.params.values():
                for sub in gc_flops._sub_closed(value):
                    total += kernel_products(sub.jaxpr)
        return total

    assert summary["by_class"]["pallas"] == pytest.approx(
        kernel_products(closed.jaxpr), rel=1e-6)
    # 2048 tokens are 4 blocks of 512: 10 causal tiles, 7 under the band,
    # for each of 2 sequences and 7 heads of 128
    tiles = 10 if window is None else 7
    assert summary["by_class"]["pallas"] == (2 + 3 + 4) * 2.0 * tiles \
        * 512 * 512 * 2 * 7 * 128


@pytest.mark.parametrize("window,tiles,edges", [(None, 528, 32), (4096, 252, 56),
                                                (4000, 252, 81), (512, 63, 63)])
def test_block_pairs_walk_only_what_the_mask_lets_through(window, tiles, edges):
    """The (query block, key block) table of a 16,384-token layer in tiles of
    512 x 512: the causal triangle's 528 tiles (138.4 M pairs a head for the
    algorithm's 134.2 M) and, under the 4096 band, 9 key blocks a query block
    (66.1 M for 58.7 M); the mask is computed in the tiles the diagonal or the
    band's far edge crosses and in no other; the dk/dv order holds the same
    pairs by key block, each block's run marked at both ends."""
    import numpy as np

    from pytorchvideo_accelerate_tpu.ops.pallas_attention import (
        EDGE, FIRST, LAST, Mask, block_pairs,
    )

    mask = Mask(causal=True, window=window)
    qb, kb, flags = block_pairs(32, 32, 512, 512, mask)
    assert len(qb) == tiles and int(np.sum(flags & EDGE != 0)) == edges
    t, s = np.arange(16384)[:, None], np.arange(16384)[None, :]
    allowed = (s <= t) & (t - s < (window or 16384))
    blocks = allowed.reshape(32, 512, 32, 512)
    some, every = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
    assert sorted(zip(qb, kb)) == sorted(zip(*np.nonzero(some)))
    assert all(every[q, k] == (f & EDGE == 0) for q, k, f in zip(qb, kb, flags))
    assert list(qb) == sorted(qb)
    for major, by_key in ((qb, False), (block_pairs(32, 32, 512, 512, mask,
                                                    by_key=True), True)):
        if by_key:
            qb2, kb2, flags = major
            assert sorted(zip(qb2, kb2)) == sorted(zip(qb, kb))
            major = kb2
        first, last = flags & FIRST != 0, flags & LAST != 0
        assert first.sum() == last.sum() == 32
        assert all(first[1:] == (major[1:] != major[:-1]))
        assert all(last[:-1] == (major[1:] != major[:-1]))

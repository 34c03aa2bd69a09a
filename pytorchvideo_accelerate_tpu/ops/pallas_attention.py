"""Flash attention as a Pallas TPU kernel family: forward, dq, dk/dv.

One online-softmax implementation for both callers (ops/attention.py):

- `flash_attention`: the ViT trunks' bidirectional attention (MViT, VideoMAE;
  `dot_product_attention(backend="pallas")`), heads of any width, queries and
  keys of different lengths, ragged lengths padded and the padded keys masked;
- `causal_flash_attention`: the token models' causal grouped-query attention,
  with or without a trailing window (`causal_gqa_attention`'s lowering on a
  TPU), heads a multiple of 128 wide read where the layer has them.

**Layout.** Operands are (B, T, heads * d) arrays: a key-value head is a
d-wide column block, and the `group` query heads that share it are the
`group * d` columns beside each other, so a grid step fetches one (block_q,
group * d) block of q and one (block_k, d) block each of k and v, with no
repeated key head and no (B, T, H, d) -> (B * H, T, d) pass through HBM. The
ViT caller has d < 128, which no column block can address: it folds its heads
into the batch (one head a "sequence", group 1) as it always did.

**Grid.** (batch, key-value head, pair): the last axis walks a
scalar-prefetched table of the (query block, key block) pairs the mask lets
through, in the order the kernel accumulates (`block_pairs`): by query block
for the forward and dq, by key block (the transposed range: from the diagonal
to `window` further) for dk/dv. A pair wholly above the diagonal, wholly
behind the band or wholly in the keys' padding is not in the table: neither
fetched nor computed. Each entry carries three flags: the first and the last
pair of its output block (zero the accumulators; write the block), and
whether the mask's edge crosses it. Only edge pairs compute the mask (iota,
compare, select); interior pairs run without.

**A grid step** works on the tile transposed, keys by queries: for each of
the group's heads in turn a float32 (block_k, block_q) tile of scores `k q^T`
in VMEM. The softmax's max and sum then run down the sublanes (elementwise
over vregs, no lane reduction a row) and are lane-dense (1, block_q) rows, the
form `lse` and `delta` are stored in; every product is plain, `x y^T` or
`x^T y`. Across the steps of one output block live the float32 accumulators:
the forward's output (group, d, block_q), running max and sum; dq's (group, d,
block_q); dk's and dv's (block_k, d), summed over the group's heads inside the
kernel. The forward and dq turn their (d, block_q) sums once, as they write.

**Backward** (`jax.custom_vjp`; Dao 2023 §3.2): the forward emits `lse = max +
log(sum)` a row, float32, (B, key heads, group, T); both backward kernels
recompute `p = exp(s - lse)` a tile; `delta = rowsum(dO * O)` is an XLA
reduction beside them. With `ds = p * (dO V^T - delta)`: dq += ds K, dk +=
ds^T Q, dv += p^T dO; the softmax's scale multiplies dq's and dk's sums once,
not every `ds`. The forward runs outside the `custom_vjp`, whose operands
are q, k, v and the forward's `o` and `lse`: the token models' remat units
keep those two by name and do not run the forward again (`KEPT_NAMES`,
docs/KERNELS.md).

**Precision.** Operands enter the MXU in their own dtype (bfloat16 under a
bfloat16 policy; float32 operands multiply at `Precision.HIGHEST`, so a
float32 model is float32 on the chip too), sums are float32; scores, max, sum,
`lse`, `dp`, `ds` and every accumulator are float32; `p` and `ds` are rounded
to the operands' dtype only where they enter a product, as the XLA form's
`probs.astype(q.dtype)` is. A masked score is -1e30, not -inf: a row whose
keys of one tile are all masked leaves garbage that the first tile with a real
key wipes (its rescale is exp(-1e30 - max) = 0), and every row has a real key
in the last tile it visits (a token reads itself; a padded key sits in a tile
that has real ones).

Off the TPU the kernels run interpreted, so the same code is what the CPU
tests hold against the dense product.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorchvideo_accelerate_tpu.precision import (
    ISLAND_DTYPE,
    end_island,
    f32_island,
)

NEG_INF = -1e30
LANES = 128
# queries and keys a tile, for the token models: the kernels' own constants
# (docs/KERNELS.md has the chip's readings), not a caller's parameter
BLOCK_Q = 512
BLOCK_K = 512
# the default 16 MiB holds neither token cell's tiles; 8 float32 heads of 256
# in tiles of 512 x 512 (the dq kernel) pass 32 MiB
_VMEM_LIMIT = 64 * 2 ** 20

FIRST, LAST, EDGE = 1, 2, 4  # a pair's flags

_NN = ((1,), (0,))  # x @ y
_NT = ((1,), (1,))  # x @ y^T
_TN = ((0,), (0,))  # x^T @ y


class Mask(NamedTuple):
    """Which keys s a query t reads; static."""

    causal: bool = False           # s <= t
    window: Optional[int] = None   # t - s < window
    keys: Optional[int] = None     # s < keys (the rest is padding)


class Spec(NamedTuple):
    """What a call is traced for; static."""

    key_heads: int   # column blocks of k and v; q has `group` times as many
    scale: float
    block_q: int
    block_k: int
    mask: Mask
    interpret: bool


def block_pairs(nq: int, nk: int, block_q: int, block_k: int, mask: Mask,
                by_key: bool = False) -> Tuple[np.ndarray, ...]:
    """The (query block, key block) pairs with a pair of (t, s) the mask lets
    through, as int32 arrays (query blocks, key blocks, flags), ordered by
    query block then key block, or by key block then query block. FIRST and
    LAST mark the ends of a run of equal major blocks; EDGE a pair with a
    masked (t, s) in it."""
    r0 = np.arange(nq)[:, None] * block_q
    c0 = np.arange(nk)[None, :] * block_k
    r1, c1 = r0 + block_q - 1, c0 + block_k - 1
    visit = np.ones((nq, nk), bool)
    whole = np.ones((nq, nk), bool)
    if mask.causal:
        visit &= c0 <= r1
        whole &= c1 <= r0
    if mask.window is not None:
        visit &= r0 - c1 < mask.window
        whole &= r1 - c0 < mask.window
    if mask.keys is not None:
        visit &= c0 < mask.keys
        whole &= c1 < mask.keys
    qb, kb = np.nonzero(visit.T)[::-1] if by_key else np.nonzero(visit)
    major = kb if by_key else qb
    if len(np.unique(major)) != (nk if by_key else nq):
        raise ValueError("a block that no pair writes")
    flags = np.where(whole[qb, kb], 0, EDGE)
    ends = np.flatnonzero(np.diff(major)) + 1
    flags[np.concatenate([[0], ends])] |= FIRST
    flags[np.concatenate([ends - 1, [len(major) - 1]])] |= LAST
    return tuple(np.asarray(x, np.int32) for x in (qb, kb, flags))


def _mm(x, y, dims):
    """One product: operands as they are, float32 sums."""
    precision = (lax.Precision.HIGHEST if x.dtype == ISLAND_DTYPE
                 else lax.Precision.DEFAULT)
    return lax.dot_general(x, y, (dims, ((), ())), precision=precision,
                           preferred_element_type=ISLAND_DTYPE)


def _head(i, d):
    """Head i's columns of a (rows, heads * d) block."""
    return slice(i * d, (i + 1) * d)


def _allowed(block_k, block_q, q0, k0, mask):
    """The mask of the (keys, queries) tile whose first query is q0 and first
    key k0."""
    k_idx = lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
    ok = None
    if mask.causal or mask.window is not None:
        delta = lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1) \
            - k_idx + (q0 - k0)
        if mask.causal:
            ok = delta >= 0
        if mask.window is not None:
            band = delta < mask.window
            ok = band if ok is None else jnp.logical_and(ok, band)
    if mask.keys is not None:
        real = k_idx < mask.keys - k0
        ok = real if ok is None else jnp.logical_and(ok, real)
    return ok


def _step(tables, q_ref, k_ref, spec, init, tile, write):
    """One grid step of any of the three kernels: `init()` at the first pair
    of an output block, `tile(scores)` for the pair, `write()` at the last.
    `scores(q)` gives a head's float32 (keys, queries) tile of scaled scores,
    masked where the mask's edge crosses the pair and only there."""
    qb_ref, kb_ref, flags_ref = tables
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    pair = pl.program_id(2)
    flags = flags_ref[pair]
    pl.when(flags & FIRST != 0)(init)

    def run(edge):
        ok = _allowed(block_k, block_q, qb_ref[pair] * block_q,
                      kb_ref[pair] * block_k, spec.mask) if edge else None
        k = k_ref[...]

        def scores(q):
            s = _mm(k, q, _NT) * spec.scale
            return s if ok is None else jnp.where(ok, s, NEG_INF)

        tile(scores)

    lax.cond(flags & EDGE != 0, lambda: run(True), lambda: run(False))
    pl.when(flags & LAST != 0)(write)


def _fwd_kernel(qb_ref, kb_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, acc_ref, m_ref, l_ref, *, spec, group, d):
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(scores):
        v = v_ref[...]
        for h in range(group):
            s = scores(q_ref[:, _head(h, d)])
            m_prev = m_ref[h]                                # (1, block_q)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * alpha + _mm(v, end_island(p, v.dtype),
                                                  _TN)     # (d, block_q)

    def write():
        for h in range(group):
            l = l_ref[h]
            o_ref[:, _head(h, d)] = end_island((acc_ref[h] / l).T, o_ref.dtype)
            lse_ref[h:h + 1, :] = m_ref[h] + jnp.log(l)

    _step((qb_ref, kb_ref, flags_ref), q_ref, k_ref, spec, init, tile, write)


def _dq_kernel(qb_ref, kb_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, acc_ref, *, spec, group, d):
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(scores):
        k, v = k_ref[...], v_ref[...]
        for h in range(group):
            p = jnp.exp(scores(q_ref[:, _head(h, d)]) - lse_ref[h:h + 1, :])
            dp = _mm(v, do_ref[:, _head(h, d)], _NT)
            ds = p * (dp - delta_ref[h:h + 1, :])  # the scale: on the sum
            acc_ref[h] += _mm(k, end_island(ds, k.dtype), _TN)

    def write():
        for h in range(group):
            dq_ref[:, _head(h, d)] = end_island(
                (acc_ref[h] * spec.scale).T, dq_ref.dtype)

    _step((qb_ref, kb_ref, flags_ref), q_ref, k_ref, spec, init, tile, write)


def _dkv_kernel(qb_ref, kb_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                spec, group, d):
    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(scores):
        v = v_ref[...]
        dk = dv = 0.0  # the group's heads sum into their key head's
        for h in range(group):
            q, do = q_ref[:, _head(h, d)], do_ref[:, _head(h, d)]
            p = jnp.exp(scores(q) - lse_ref[h:h + 1, :])
            dv = dv + _mm(end_island(p, do.dtype), do, _NN)
            ds = p * (_mm(v, do, _NT) - delta_ref[h:h + 1, :])
            dk = dk + _mm(end_island(ds, q.dtype), q, _NN)
        dk_acc[...] += dk
        dv_acc[...] += dv

    def write():
        dk_ref[...] = end_island(dk_acc[...] * spec.scale, dk_ref.dtype)
        dv_ref[...] = end_island(dv_acc[...], dv_ref.dtype)

    _step((qb_ref, kb_ref, flags_ref), q_ref, k_ref, spec, init, tile, write)


class _Calls:
    """The family's `pallas_call`s over one set of operands: q (and o, do, dq)
    (B, Tq, key_heads * group * d), k and v (B, Tk, key_heads * d), the
    lengths multiples of the blocks; `lse` and `delta` (B, key_heads, group,
    Tq) float32."""

    def __init__(self, q, k, spec: Spec):
        self.spec = spec
        self.b, self.tq, width = q.shape
        self.tk = k.shape[1]
        self.d = k.shape[2] // spec.key_heads
        self.group = width // k.shape[2]
        bq, bk = spec.block_q, spec.block_k
        if self.tq % bq or self.tk % bk:
            raise ValueError(f"{self.tq} queries and {self.tk} keys in "
                             f"blocks of {bq} and {bk}")
        g, d = self.group, self.d
        self.rows = pl.BlockSpec((None, bq, g * d),
                                 lambda b, h, p, qb, kb, f: (b, qb[p], h))
        self.keys = pl.BlockSpec((None, bk, d),
                                 lambda b, h, p, qb, kb, f: (b, kb[p], h))
        self.stats = pl.BlockSpec((None, None, g, bq),
                                  lambda b, h, p, qb, kb, f: (b, h, 0, qb[p]))
        self.stats_shape = jax.ShapeDtypeStruct(
            (self.b, spec.key_heads, g, self.tq), ISLAND_DTYPE)

    def __call__(self, kernel, name, by_key, in_specs, out_specs, out_shape,
                 scratch, operands):
        spec = self.spec
        pairs = block_pairs(self.tq // spec.block_q, self.tk // spec.block_k,
                            spec.block_q, spec.block_k, spec.mask, by_key)
        return pl.pallas_call(
            functools.partial(kernel, spec=spec, group=self.group, d=self.d),
            name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(self.b, spec.key_heads, len(pairs[0])),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=spec.interpret,
        )(*(jnp.asarray(x) for x in pairs), *operands)

    def forward(self, q, k, v):
        bq, g, d = self.spec.block_q, self.group, self.d
        return self(
            _fwd_kernel, "pva_attn_fwd", False,
            [self.rows, self.keys, self.keys], [self.rows, self.stats],
            [jax.ShapeDtypeStruct(q.shape, q.dtype), self.stats_shape],
            [pltpu.VMEM((g, d, bq), ISLAND_DTYPE),
             pltpu.VMEM((g, 1, bq), ISLAND_DTYPE),
             pltpu.VMEM((g, 1, bq), ISLAND_DTYPE)],
            (q, k, v))

    def backward(self, q, k, v, o, lse, do):
        bq, bk, g, d = (self.spec.block_q, self.spec.block_k, self.group,
                        self.d)
        # delta_t = sum_d dO_td O_td a head, as the rows `lse` is stored as
        delta = jnp.sum(
            (f32_island(do) * f32_island(o)).reshape(
                self.b, self.tq, self.spec.key_heads, g, d),
            axis=-1).transpose(0, 2, 3, 1)
        operands = (q, k, v, do, lse, delta)
        in_specs = [self.rows, self.keys, self.keys, self.rows, self.stats,
                    self.stats]
        dq = self(_dq_kernel, "pva_attn_dq", False, in_specs, self.rows,
                  jax.ShapeDtypeStruct(q.shape, q.dtype),
                  [pltpu.VMEM((g, d, bq), ISLAND_DTYPE)], operands)
        dk, dv = self(_dkv_kernel, "pva_attn_dkv", True, in_specs,
                      [self.keys, self.keys],
                      [jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
                      [pltpu.VMEM((bk, d), ISLAND_DTYPE)] * 2, operands)
        return dq, dk, dv


# the names a remat policy keeps the forward's `o` and `lse` by
# (models/lm_common.py `remat_keeping_attention`; docs/KERNELS.md)
KEPT_NAMES = ("pva_attn_o", "pva_attn_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _attended(q, k, v, o, lse, spec: Spec):
    """`o`, the forward kernel's output over q, k and v, as their function:
    the backward kernels are its rule. The forward runs outside, so its `o`
    and `lse` enter here as operands and the rule's residuals ARE the values
    a remat policy sees: returned from a rule that ran the forward itself,
    they would be copies the policy cannot keep (docs/KERNELS.md)."""
    return o


def _attended_fwd(q, k, v, o, lse, spec):
    return o, (q, k, v, o, lse)


def _attended_bwd(spec, residuals, do):
    q, k, v, o, lse = residuals
    # o and lse are functions of q, k and v, whose cotangents carry them
    return (*_Calls(q, k, spec).backward(q, k, v, o, lse, do), None, None)


_attended.defvjp(_attended_fwd, _attended_bwd)


def _flash(q, k, v, spec: Spec, named: bool):
    """The forward kernel once, differentiable through the backward pair;
    `named`: `o` and `lse` under KEPT_NAMES, for a remat policy to keep."""
    o, lse = _Calls(q, k, spec).forward(
        *(lax.stop_gradient(x) for x in (q, k, v)))
    if named:
        o, lse = (checkpoint_name(x, name) for x, name in zip((o, lse),
                                                              KEPT_NAMES))
    return _attended(q, k, v, o, lse, spec)


def _pad_rows(x, block):
    pad = -x.shape[1] % block
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def flash_attention(q, k, v, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Bidirectional flash attention, API-compatible with `dense_attention`;
    differentiable (custom VJP backed by the backward kernels).

    q: (B, Nq, H, D); k/v: (B, Nkv, H, D) -> (B, Nq, H, D). Sequence lengths
    need not be block multiples (padded + masked internally). `interpret`
    defaults to True off-TPU so tests run on CPU.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, nq, H, D = q.shape
    nkv = k.shape[1]
    block_q = min(block_q, _round_up(nq, 8))
    block_k = min(block_k, _round_up(nkv, 8))

    def fold(x, block):   # (B, N, H, D) -> (B*H, N padded, D)
        x = x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
        return _pad_rows(x, block)

    mask = Mask(keys=nkv if nkv % block_k else None)
    out = _flash(fold(q, block_q), fold(k, block_k), fold(v, block_k),
                 Spec(1, float(scale), block_q, block_k, mask, bool(interpret)),
                 named=False)
    return out[:, :nq].reshape(B, H, nq, D).transpose(0, 2, 1, 3)


def causal_flash_attention(q, k, v, scale: float, window: Optional[int],
                           interpret: bool, block_q: int = BLOCK_Q,
                           block_k: int = BLOCK_K):
    """Causal grouped-query attention, under a trailing `window` or not: q (B,
    T, Hq, D), k and v (B, T, Hkv, D), D a multiple of 128 -> (B, T, Hq, D).
    The blocks are multiples of 128, one a multiple of the other; a sequence
    shorter than a block is one block of 128 times a power of two; a T the
    blocks do not divide is padded inside (a padded key lies above every real
    query's diagonal; a padded query's row is cut off). The forward's `o` and
    `lse` carry `KEPT_NAMES`, for a remat policy to keep."""
    b, t, hq, d = q.shape
    # 128 times the least power of two that holds the sequence
    whole = LANES << max(-(-t // LANES) - 1, 0).bit_length()
    block_q, block_k = min(block_q, whole), min(block_k, whole)
    q, k, v = (_pad_rows(x.reshape(b, t, -1), max(block_q, block_k))
               for x in (q, k, v))
    out = _flash(q, k, v, Spec(k.shape[2] // d, float(scale), block_q, block_k,
                               Mask(causal=True, window=window), interpret),
                 named=True)
    return out[:, :t].reshape(b, t, hq, d)

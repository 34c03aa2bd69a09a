"""Attention backends.

The transformer models (MViT, VideoMAE) call one entry point —
`dot_product_attention(q, k, v, backend=...)` — so the attention
implementation is a deployment choice, not a model choice:

- "dense": `jax.nn.dot_product_attention` (XLA fuses QK^T -> softmax -> AV;
  on TPU this hits the MXU with flash-style chunking from the compiler).
- "pallas": hand-tiled flash attention kernel (ops/pallas_attention.py) for
  sizes where XLA's default schedule underperforms.
- "ring": context-parallel ring attention over the mesh "context" axis
  (parallel/ring_attention.py) — sequence sharded, K/V blocks rotate over
  ICI via ppermute (SURVEY §5 long-context plan).

Shapes: q (B, Nq, H, D), k/v (B, Nkv, H, D) — BNHD, heads separate, the
layout XLA:TPU prefers for attention (no pre-transpose of the token axis).

Masked variants (`mask=`): a boolean mask broadcastable to
(B, H, Nq, Nk), True = attend. Used by the causal/windowed trunk
variants (models/videomae.py `attn_mask`) and the streaming KV-ring
incremental step (streaming/engine.py); the banded-time helpers below
build the masks from temporal-slot indices, so every caller shares one
definition of "slot qi may read slot kj iff 0 <= qi - kj < window".
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp

from pytorchvideo_accelerate_tpu.ops import pallas_attention
from pytorchvideo_accelerate_tpu.precision import f32_island


def dense_attention(q, k, v, scale: Optional[float] = None, kmask=None,
                    mask=None):
    """Reference attention. `kmask`: optional (Nk,) bool — False keys are
    excluded from the softmax (used for padded keys by the CP wrappers).
    `mask`: optional bool broadcastable to (B, H, Nq, Nk), True = attend
    (the banded-trunk contract)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # f32 softmax logits: the designed island every attention impl shares
    logits = f32_island(jnp.einsum("bqhd,bkhd->bhqk", q, k)) * scale
    if kmask is not None:
        logits = jnp.where(kmask[None, None, None, :], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def fused_attention(q, k, v, scale: Optional[float] = None, kmask=None,
                    mask=None):
    """XLA's fused attention (flash-style chunking on TPU — no materialized
    N^2 score matrix) with the same key-mask contract as `dense_attention`.
    The CP wrappers use this for their local attention so peak memory stays
    O(N) at the long sequences that motivate context parallelism."""
    if kmask is not None:
        km = kmask[None, None, None, :]
        mask = km if mask is None else jnp.logical_and(mask, km)
    return jax.nn.dot_product_attention(q, k, v, mask=mask, scale=scale)


def rotate_half(x, positions, theta: float, rotary_dim: int):
    """Rotary embedding (rotate-half pairing) on the first `rotary_dim` of
    the head dimension of x (B, T, H, D), float32 angles; the other
    dimensions pass through (partial rotary)."""
    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    angle = f32_island(positions)[:, None] * freq[None, :]      # (T, half)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    rot, rest = f32_island(x[..., :rotary_dim]), x[..., rotary_dim:]
    a, b = rot[..., :half], rot[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)


_window_sites: contextvars.ContextVar = contextvars.ContextVar(
    "pva_attn_window_sites", default=None)
_kernel_sites: contextvars.ContextVar = contextvars.ContextVar(
    "pva_attn_kernel_sites", default=None)
_xla_sites: contextvars.ContextVar = contextvars.ContextVar(
    "pva_attn_xla_sites", default=None)
_keeping: contextvars.ContextVar = contextvars.ContextVar(
    "pva_attn_keeping", default=False)


@contextlib.contextmanager
def _collect(var: contextvars.ContextVar) -> Iterator[List[tuple]]:
    sites: List[tuple] = []
    token = var.set(sites)
    try:
        yield sites
    finally:
        var.reset(token)


def count_window_sites():
    """Collects, while a model is traced inside the block, one entry (T,
    window) for every `causal_gqa_attention` call lowered under a band (the
    `pva_attn_window_sites` gauge, trainer/steps.py)."""
    return _collect(_window_sites)


def count_kernel_sites():
    """The same for every `causal_gqa_attention` call that took the Pallas
    flash kernels, one entry (q's shape, window, kept): the lowering is
    static, so its engagement is a fact of the trace (the
    `pva_attn_kernel_sites` gauge). `kept`: traced inside a remat unit whose
    policy keeps the forward's `o` and `lse` (`keeping_kernel_results`; the
    `pva_attn_kept_sites` gauge)."""
    return _collect(_kernel_sites)


def count_xla_sites():
    """The same for every `causal_gqa_attention` call that took the XLA form
    on a TPU backend, one entry (q's shape, cause): cause "head_width" where
    the heads are not a multiple of 128 wide, else "length" (a sequence of
    one block). Off the TPU every call takes the XLA form and none is counted
    (the `pva_attn_xla_sites` gauge)."""
    return _collect(_xla_sites)


@contextlib.contextmanager
def keeping_kernel_results() -> Iterator[None]:
    """Around the trace of a remat unit whose policy keeps the names of
    `pallas_attention.KEPT_NAMES` (models/lm_common.py): the kernel sites
    traced inside are counted as kept."""
    token = _keeping.set(True)
    try:
        yield
    finally:
        _keeping.reset(token)


def takes_kernel() -> bool:
    """The backend half of the rule that picks `causal_gqa_attention`'s
    lowering (`lane_fold.takes_fold`'s, `gated_delta.takes_kernel`'s): off the
    TPU the kernels could only be interpreted."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_shapes(t: int, d: int) -> bool:
    """The shape half: the kernels read a head as a 128-lane column block of
    the layer's (B, T, H*d) arrays, and a sequence of one block has no key
    block to skip and no scores worth keeping out of HBM: it stays the XLA
    form's one dense masked product (so does the 128-token sample a model is
    initialised on, un-jitted: no Mosaic compile outside the jitted step)."""
    return d % 128 == 0 and t > pallas_attention.BLOCK_Q


def causal_gqa_attention(q, k, v, scale: Optional[float] = None,
                         block_q: int = 512, window: Optional[int] = None):
    """Causal softmax attention with grouped queries: q (B, T, Hq, D), k and v
    (B, T, Hkv, D), Hq a multiple of Hkv, each key-value head serving
    Hq / Hkv query heads. Token t reads keys 0..t; with `window`, the
    trailing `window` of them: keys s with 0 <= t - s < window.

    One algorithm, two lowerings, chosen by what the code can observe
    (`takes_kernel`: the backend; `kernel_shapes`: the heads' width and the
    sequence's length) and by nothing a caller sets. On a TPU, for heads a
    multiple of 128 wide and a sequence longer than one block, the Pallas flash
    kernels of ops/pallas_attention.py: a tile of float32 scores
    and the softmax's running max and sum stay in VMEM, and only the key
    blocks the diagonal and the band let through are fetched (docs/KERNELS.md).
    Everywhere else (the CPU, the toy models' narrow heads) the XLA form,
    `blocked_causal_attention`, in blocks of `block_q` queries: the definition
    the tests hold the kernels to. A window no shorter than the sequence is
    no band and lowers as none."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} key-value heads")
    if scale is None:
        scale = d ** -0.5
    if window is not None and window >= t:
        window = None
    if window is not None:
        if window < 1:
            raise ValueError(f"window={window}")
        sites = _window_sites.get()
        if sites is not None:
            sites.append((t, window))
    if takes_kernel() and kernel_shapes(t, d):
        sites = _kernel_sites.get()
        if sites is not None:
            sites.append((q.shape, window, _keeping.get()))
        return pallas_attention.causal_flash_attention(
            q, k, v, scale, window, _interpret())
    sites = _xla_sites.get()
    if sites is not None and takes_kernel():
        sites.append((q.shape, "head_width" if d % 128 else "length"))
    return blocked_causal_attention(q, k, v, scale, block_q, window)


def blocked_causal_attention(q, k, v, scale: float, block_q: int,
                             window: Optional[int]):
    """`causal_gqa_attention` as XLA products (`window`: None or shorter than
    the sequence), computed a block of `block_q` queries at a time against the
    keys up to that block's end, so the keys behind the diagonal cost nothing
    and the float32 scores of one block (B, Hq, block_q, keys so far) are the
    largest array alive; each block is rematerialised in the backward pass.
    A sequence no longer than `block_q` is one dense masked product. Under a
    band a block reads the keys from the first one its first query may read,
    rounded down to a block: at most `window + block_q` rounded up, so the
    key blocks wholly behind the window cost nothing either."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv

    @jax.checkpoint
    def block(q_blk, k_seen, v_seen, start):
        # a key-value head's `group` query heads become rows of one product:
        # scores (B, Hkv, group * block, keys), no axis of size `group` for
        # the compiler to lay out minor
        n = q_blk.shape[1]
        rows = q_blk.reshape(b, n, hkv, group, d).transpose(0, 2, 3, 1, 4)
        rows = rows.reshape(b, hkv, group * n, d)
        # f32 softmax logits: the island every attention impl shares
        logits = f32_island(jnp.einsum("bhrd,bkhd->bhrk", rows, k_seen)) * scale
        # `start`: the block's first query, counted from the first key read
        pos = start + jnp.arange(group * n) % n
        if window is None:
            seen = jnp.arange(k_seen.shape[1])[None, :] <= pos[:, None]
        else:
            seen = banded_time_mask(pos, jnp.arange(k_seen.shape[1]), window)
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        out = jnp.einsum("bhrk,bkhd->bhrd", probs.astype(q.dtype), v_seen)
        out = out.reshape(b, hkv, group, n, d).transpose(0, 3, 1, 2, 4)
        return out.reshape(b, n, hq, d)

    outs = []
    for start in range(0, t, block_q):
        end = min(start + block_q, t)
        # the first key the block's first query may read, down to a block
        lo = 0 if window is None else \
            max(start - window + 1, 0) // block_q * block_q
        outs.append(block(q[:, start:end], k[:, lo:end], v[:, lo:end],
                          start - lo))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def banded_time_mask(q_idx, k_idx, window: int):
    """Boolean band mask over ABSOLUTE temporal-slot indices: query slot
    qi may attend key slot kj iff ``0 <= qi - kj < window``.

    `q_idx` (..., Nq) / `k_idx` (..., Nk) int arrays (traced or static) ->
    (..., Nq, Nk) bool. Absolute indices are the wraparound-proof
    formulation the streaming KV rings rely on: a ring slot's position
    never aliases a future slot because the band is on the un-wrapped
    index, not the ring offset (docs/SERVING.md § trunk-reuse)."""
    delta = q_idx[..., :, None] - k_idx[..., None, :]
    return jnp.logical_and(delta >= 0, delta < window)


def temporal_band_mask(t: int, hw: int, window: int):
    """(t*hw, t*hw) bool mask for a full-clip trunk forward: token i at
    temporal slot i // hw attends token j iff its slot is within the
    trailing `window` slots (inclusive of its own). `window >= t` is plain
    temporal causality; smaller windows are the "windowed" variant. All
    hw spatial tokens of one slot share fate (space is never masked)."""
    slots = jnp.arange(t, dtype=jnp.int32)
    band = banded_time_mask(slots, slots, window)           # (t, t)
    return jnp.repeat(jnp.repeat(band, hw, axis=0), hw, axis=1)


def dot_product_attention(q, k, v, backend: str = "dense",
                          axis_name: Optional[str] = None, mesh=None,
                          mask=None):
    """Route to an attention implementation.

    For the context-parallel backends ("ring"/"ulysses") exactly one of two
    calling conventions applies:
    - `mesh=...` — caller is ordinary auto-sharded (jit) code: the router
      opens a `shard_map` region over the mesh's context-parallel axis
      (``axis_name`` when also given, else resolved from the mesh layout —
      ``context`` on the library mesh, ``model`` on the 2-D train mesh)
      around just this attention call (composable with auto sharding
      everywhere else);
    - `axis_name=...` and no mesh — caller is already inside a `shard_map`
      with that axis bound; q/k/v are local sequence shards.

    `mask`: optional bool broadcastable to (B, H, Nq, Nk), True = attend
    (the causal/windowed trunk variants). Dense backend only: the pallas
    flash kernel and the context-parallel backends have no masked
    lowering here — they refuse loudly rather than silently dropping the
    mask (a bidirectional answer under a causal contract is a
    correctness bug, not a fallback).
    """
    if backend == "dense":
        # XLA's fused attention (flash-style chunking on TPU) — measured ~4x
        # faster than the materialized-einsum path at MViT token counts on
        # v5e; `dense_attention` above stays as the numerics reference.
        return jax.nn.dot_product_attention(q, k, v, mask=mask)
    if mask is not None:
        raise NotImplementedError(
            f"attention backend {backend!r} has no masked lowering; "
            "causal/windowed trunks need backend='dense' "
            "(model.attention) — see docs/SERVING.md § trunk-reuse")
    if backend == "pallas":
        return pallas_attention.flash_attention(q, k, v)
    if backend == "ring":
        from pytorchvideo_accelerate_tpu.parallel.ring_attention import (
            make_ring_attention, ring_attention,
        )

        if mesh is not None:
            return make_ring_attention(mesh, axis_name)(q, k, v)
        if axis_name is None:
            raise ValueError("ring attention needs a mesh or the context-axis name")
        return ring_attention(q, k, v, axis_name=axis_name)
    if backend == "ulysses":
        from pytorchvideo_accelerate_tpu.parallel.ulysses import (
            make_ulysses_attention, ulysses_attention,
        )

        if mesh is not None:
            return make_ulysses_attention(mesh, axis_name)(q, k, v)
        if axis_name is None:
            raise ValueError("ulysses attention needs a mesh or the context-axis name")
        return ulysses_attention(q, k, v, axis_name=axis_name)
    raise ValueError(f"unknown attention backend {backend!r}")

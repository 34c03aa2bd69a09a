"""Ring attention: context-parallel attention over the mesh "context" axis.

Long-context plan from SURVEY §5: the reference stack only *hooks* context
parallelism (accelerate `_prepare_cp` accelerator.py:1658, `maybe_context_parallel`
accelerator.py:4111) and never exercises it; here it is a first-class backend.
For video transformers the token count is T·(H/p)·(W/p) (MViT-B at 32 frames /
224² is 8·14·14 ≈ 1.5k tokens; VideoMAE pretrain at 16·14·14 with longer clips
grows linearly in T), so sequence memory — activations and the O(N²) attention
— is the scaling wall. The TPU-native answer is blockwise ring attention:

- tokens sharded over the ``context`` mesh axis (each device holds N/cp tokens);
- K/V blocks rotate around the ring via ``lax.ppermute`` (XLA lowers this to
  neighbour-to-neighbour ICI transfers — no all-gather, no N² memory);
- each device accumulates its queries' attention with the *online softmax*
  (flash-attention style running max/sum), so the full score matrix never
  materializes.

Compute/communication overlap is XLA's job: the ppermute for step i+1 is
issued while step i's einsum runs (latency-hiding scheduler), matching the
hand-rolled double buffering in the published ring-attention kernels.

Sequences that don't divide the context axis are padded and masked (the mask
multiplies the softmax numerator as well as the logits: a *fully*-padded K
shard would otherwise contribute exp(logit - max) = exp(0) = 1 per column —
the classic streaming-softmax edge case).

Two entry points:
- `ring_attention(q, k, v, axis_name=...)` — call *inside* an active
  `shard_map` over the context axis (manual-SPMD region).
- `make_ring_attention(mesh)` — returns a drop-in attention fn for
  auto-sharded (jit) models: wraps the local kernel in `jax.shard_map`
  over ``context``, padding/masking ragged sequence lengths.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorchvideo_accelerate_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    batch_axes,
    cp_axis,
    mesh_memo,
)

NEG_INF = -1e30


def _online_block(q, k, v, kmask, o, l, m, scale):
    """One flash-attention accumulation block.

    q: (B, Nq, H, D); k/v: (B, Nk, H, D); kmask: (Nk,) bool or None;
    o: (B, Nq, H, D) f32 accumulator; l/m: (B, H, Nq) running sum/max.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32) * scale
    if kmask is not None:
        logits = jnp.where(kmask[None, None, None, :], logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])          # (B, H, Nq, Nk)
    if kmask is not None:
        # kill the exp(NEG_INF - NEG_INF) = 1 case when every key is masked
        p = p * kmask[None, None, None, :]
    alpha = jnp.exp(m - m_new)                       # (B, H, Nq)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return o_new, l_new, m_new


def ring_attention(q, k, v, axis_name: str = AXIS_CONTEXT,
                   scale: Optional[float] = None,
                   nk_valid: Optional[int] = None):
    """Blockwise ring attention. Must run inside `shard_map` with `axis_name`
    bound; q/k/v are the *local* sequence shards, shape (B, N_local, H, D).

    `nk_valid`: global number of real (unpadded) keys; when given, keys at
    global position >= nk_valid are masked out. Non-causal (video tokens are
    bidirectional — SlowFast/MViT classify, VideoMAE reconstructs).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    steps = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    blk = k.shape[1]

    B, Nq, H, D = q.shape
    o = jnp.zeros((B, Nq, H, D), jnp.float32)
    l = jnp.zeros((B, H, Nq), jnp.float32)
    m = jnp.full((B, H, Nq), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % steps) for i in range(steps)]

    def body(carry, s):
        o, l, m, k, v = carry
        if nk_valid is not None and nk_valid < steps * blk:
            # after s forward rotations this device holds the block that
            # started on device (my - s); mask its global key positions
            src = jnp.mod(my - s, steps)
            col = src * blk + jnp.arange(blk)
            kmask = col < nk_valid
        else:
            kmask = None
        o, l, m = _online_block(q, k, v, kmask, o, l, m, scale)
        # rotate K/V one hop around the ICI ring (neighbour-only transfer);
        # the last rotation is dead work but keeps the scan shape static
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (o, l, m, k, v), None

    (o, l, m, _, _), _ = lax.scan(body, (o, l, m, k, v), jnp.arange(steps))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _pad_tokens(x, mult: int):
    pad = (-x.shape[1]) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x


def make_cp_attention(mesh: Mesh, local_fn,
                      axis_name: Optional[str] = None):
    """Shared jit-side wrapper for context-parallel attention kernels.

    `local_fn(q, k, v, axis_name=..., nk_valid=...)` is a manual-SPMD kernel
    (ring_attention / ulysses_attention). Opens a `shard_map` region over the
    context-parallel axis — `axis_name` or, when None, resolved from the
    mesh layout (the library mesh's ``context`` axis / the 2-D train mesh's
    ``model`` axis, parallel/mesh.cp_axis): the token axis of q/k/v is
    sharded there and heads/features are replicated w.r.t. it. The batch
    axis additionally stays sharded over the mesh's DP axes when the global
    batch divides them (the normal training case) and is replicated
    otherwise (tiny eval batches). Ragged sequence lengths (e.g. MViT's
    pooled K/V grids) are padded to a multiple of the axis size and masked
    inside the kernel.
    """
    if axis_name is None:
        axis_name = cp_axis(mesh)
    cp = mesh.shape[axis_name]
    daxes = batch_axes(mesh)
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]

    # bounded: distinct (batch_divisible, lengths) combos are few per model
    @functools.lru_cache(maxsize=64)
    def build(batch_divisible: bool, nk_valid: int, nk_padded: int):
        spec = P(daxes if batch_divisible else None, axis_name, None, None)
        mask = None if nk_valid == nk_padded else nk_valid
        # replication checking off: the CP kernels do their own masking,
        # and the check rejects the padded-K path
        return jax.shard_map(
            lambda q, k, v: local_fn(q, k, v, axis_name=axis_name,
                                     nk_valid=mask),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )

    def attn(q, k, v):
        nq, nk = q.shape[1], k.shape[1]
        qp, kp, vp = _pad_tokens(q, cp), _pad_tokens(k, cp), _pad_tokens(v, cp)
        out = build(q.shape[0] % dp == 0, nk, kp.shape[1])(qp, kp, vp)
        return out[:, :nq]

    return attn


def make_ring_attention(mesh: Mesh, axis_name: Optional[str] = None):
    """Drop-in ring-attention `attn(q, k, v)` for auto-sharded models under
    `jit` (see `make_cp_attention`; `axis_name=None` resolves the CP axis
    from the mesh layout). Memoized on the mesh-identity store (an
    equality-keyed lru would serve a wrapper closed over a retired mesh
    after a mesh-reshape restore) so every attention layer / retrace
    reuses one wrapper and its shape cache."""
    memo = mesh_memo(mesh, "ring_attention")
    attn = memo.get(axis_name)
    if attn is None:
        attn = memo[axis_name] = make_cp_attention(mesh, ring_attention,
                                                   axis_name)
    return attn

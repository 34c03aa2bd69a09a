"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

The reference backbone ships this only as a DeepSpeed-Ulysses dataloader
adapter (accelerate accelerator.py:2486-2505) plus an external-deps test
(test_ds_alst_ulysses_sp.py) — never exercised by run.py. Here it is a real
attention backend, the complement of ring attention (SURVEY §5):

- ring: K/V blocks rotate hop-by-hop over ICI; comm volume ~ N·D per step,
  overlappable; works for any head count.
- ulysses: two `lax.all_to_all`s swap the sharded axis from tokens to heads,
  so each device runs *dense* attention for H/cp heads over the full
  sequence; comm is a single balanced all-to-all each way (great on a
  fully-connected ICI twisted torus), but requires H % cp == 0 and peak
  activation memory holds the full sequence for its head slice. When the
  head count doesn't divide the axis (MViT's 1-2-head early stages) it
  degrades to ring attention instead of failing.

Layouts (cp = context-axis size):
  local in : (B, N/cp, H,    D)   tokens sharded
  after a2a: (B, N,    H/cp, D)   heads sharded  -> dense attention
  after a2a: (B, N/cp, H,    D)   tokens sharded again
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from pytorchvideo_accelerate_tpu.ops.attention import fused_attention
from pytorchvideo_accelerate_tpu.parallel.mesh import AXIS_CONTEXT, mesh_memo


def ulysses_attention(q, k, v, axis_name: str = AXIS_CONTEXT,
                      scale: Optional[float] = None,
                      nk_valid: Optional[int] = None):
    """All-to-all attention. Must run inside `shard_map` with `axis_name`
    bound; q/k/v are local token shards (B, N/cp, H, D). `nk_valid`: global
    count of real (unpadded) keys. Falls back to ring when H % cp != 0."""
    cp = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % cp != 0:
        from pytorchvideo_accelerate_tpu.parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name=axis_name, scale=scale,
                              nk_valid=nk_valid)

    def to_heads(x):   # (B, N/cp, H, D) -> (B, N, H/cp, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_tokens(x):  # (B, N, H/cp, D) -> (B, N/cp, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    kg = to_heads(k)
    kmask = None
    if nk_valid is not None and nk_valid < kg.shape[1]:
        kmask = jnp.arange(kg.shape[1]) < nk_valid
    # fused (flash-chunked) local attention: peak memory O(N), not O(N^2) —
    # the whole point at the sequence lengths that motivate Ulysses
    out = fused_attention(to_heads(q), kg, to_heads(v), scale=scale, kmask=kmask)
    return to_tokens(out)


def make_ulysses_attention(mesh: Mesh, axis_name: Optional[str] = None):
    """Drop-in ulysses `attn(q, k, v)` for auto-sharded models under `jit` —
    same contract as `make_ring_attention` (token axis sharded over the
    mesh's CP axis, ragged lengths padded + masked, memoized on the
    mesh-identity store); see `make_cp_attention`."""
    from pytorchvideo_accelerate_tpu.parallel.ring_attention import make_cp_attention

    memo = mesh_memo(mesh, "ulysses_attention")
    attn = memo.get(axis_name)
    if attn is None:
        attn = memo[axis_name] = make_cp_attention(mesh, ulysses_attention,
                                                   axis_name)
    return attn

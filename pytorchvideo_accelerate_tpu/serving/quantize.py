"""int8 weight quantization for the serving tier (`serve.quantization`).

The Gemma-on-TPU fine-tune/serve comparison (PAPERS.md) quantifies what
full-precision serving leaves on the table: weights dominate a serving
replica's HBM residency, artifact size, and hot-swap transfer bytes.
This module implements the classic weight-only recipe — **int8 weights,
per-channel absmax scales, full-precision activations** (bf16 under the
default compute policy):

- `quantize_tree(params)` — every conv/dense "kernel" leaf above a size
  floor becomes `{"q8": int8, "q8_scale": f32}`: `scale[c] =
  absmax(w[..., c]) / 127` per OUTPUT channel (the last axis of every
  flax kernel layout in this zoo), `q = round(w / scale)` clipped to
  [-127, 127]. Biases, norm scales/biases, and BN running stats stay
  fp — they are a rounding error of the byte budget and quantizing
  norm statistics is where weight-only schemes actually lose accuracy.
- `dequantize_tree(tree, dtype)` — the in-graph inverse: `q * scale`
  in an f32 island, downcast once to the compute dtype. The engine
  calls it INSIDE the jitted forward, so the int8 tree is what lives
  pinned in HBM (4x smaller) and XLA is free to fuse the dequant into
  the weight read of each conv.

Quantization is applied at `export_inference` time (a baked int8
artifact — `meta.quantization` records it) or on the fly when a
full-precision artifact is loaded into an engine with
`serve.quantization=int8`. Both routes produce bit-identical quantized
weights (same absmax arithmetic in f32). The quality gate lives in
tests/test_zquant.py: int8-served top-1 within a stated tolerance of
full-precision serving on the tiny CPU-mesh e2e, padded rows and
multi-view folding unchanged. "off" leaves every byte of the engine's
behavior identical to the pre-quantization path.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

Q_KEY = "q8"
SCALE_KEY = "q8_scale"
QUANT_MODES = ("off", "int8")

# leaves below this many elements stay fp: biases/norm vectors are noise
# in the byte budget and carry outsized accuracy weight
MIN_QUANT_SIZE = 1024


def is_quant_leaf(x: Any) -> bool:
    """True for the {"q8": ..., "q8_scale": ...} marker dicts."""
    return isinstance(x, dict) and set(x.keys()) == {Q_KEY, SCALE_KEY}


def _eligible(name: str, arr) -> bool:
    # conv/dense weights are all named "kernel" in this zoo (flax layout,
    # output features on the LAST axis — including the depthwise
    # (kt,kh,kw,1,C) layout); everything else is norm/bias/stat state
    return (name == "kernel" and getattr(arr, "ndim", 0) >= 2
            and int(np.size(arr)) >= MIN_QUANT_SIZE)


def quantize_array(w) -> Dict[str, np.ndarray]:
    """Per-output-channel absmax int8 quantization of one weight array."""
    w32 = f32_island(np.asarray(w))
    axes = tuple(range(w32.ndim - 1))
    absmax = np.max(np.abs(w32), axis=axes)
    scale = f32_island(absmax / 127.0)
    # an all-zero channel must not divide by zero; its q rows are zero
    safe = f32_island(np.where(scale > 0, scale, 1.0))
    q = np.clip(np.rint(w32 / safe), -127, 127).astype(np.int8)
    return {Q_KEY: q, SCALE_KEY: safe}


def quantize_tree(params: Any) -> Tuple[Any, int]:
    """Walk a params dict-tree; returns (quantized tree, #leaves
    quantized). Already-quantized leaves pass through unchanged (the
    idempotence a hot-swap of a baked artifact relies on)."""
    n = 0

    def walk(node, name=""):
        nonlocal n
        if is_quant_leaf(node):
            return node
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if _eligible(name, node):
            n += 1
            return quantize_array(node)
        return node

    return walk(params), n


def dequantize_tree(tree: Any, dtype) -> Any:
    """In-graph inverse: q * scale in an f32 island, one downcast to the
    compute dtype (the int8-weight / bf16-activation contract). Works on
    jax arrays inside jit and on numpy trees alike."""
    import jax

    def deq(x):
        if is_quant_leaf(x):
            return end_island(f32_island(x[Q_KEY]) * x[SCALE_KEY], dtype)
        return x

    return jax.tree_util.tree_map(deq, tree, is_leaf=is_quant_leaf)


def quantize_kv(kv):
    """Per-token-row absmax int8 quantization of streaming KV-ring
    activations (streaming/engine.py KV rings, `serve.quantization=int8`):
    `kv` (..., dim) -> (q8 int8 same shape, scale f32 (...,)). Unlike the
    weight path this quantizes ACTIVATIONS — per-token scales (one per
    (layer, k/v, slot, spatial) row) keep the round-trip error bounded by
    each token's own magnitude, so one outlier token cannot flatten its
    neighbours' resolution. In-graph (jit) and numpy callers both work."""
    import jax.numpy as jnp

    kv32 = f32_island(kv)
    absmax = jnp.max(jnp.abs(kv32), axis=-1)
    scale = absmax / 127.0
    # an all-zero row must not divide by zero; its q entries are zero
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint(kv32 / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, safe


def dequantize_kv(q, scale, dtype):
    """In-graph inverse of `quantize_kv`: q * scale per token row in an
    f32 island, one downcast to the compute dtype (the int8-KV / fp-query
    contract the incremental attention step reads the ring through)."""
    return end_island(f32_island(q) * scale[..., None], dtype)


def quantized_leaf_count(tree: Any) -> int:
    import jax

    return sum(1 for leaf in jax.tree_util.tree_leaves(
        tree, is_leaf=is_quant_leaf) if is_quant_leaf(leaf))


def quant_bytes(tree: Any) -> Dict[str, int]:
    """{quantized, fp} payload bytes — the serving-memory win, reported
    by the engine log."""
    import jax

    q = fp = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_quant_leaf):
        if is_quant_leaf(leaf):
            q += int(np.size(leaf[Q_KEY])) + 4 * int(np.size(leaf[SCALE_KEY]))
        else:
            fp += int(np.asarray(leaf).nbytes)
    return {"quantized": q, "fp": fp}

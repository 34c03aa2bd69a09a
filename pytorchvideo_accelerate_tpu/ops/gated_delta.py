"""Gated delta rule: the recurrent-state mixer of a Gated-DeltaNet layer.

Per head, with a state S (dk x dv) that starts at zero, for every token t

    S <- exp(g_t) S
    d  = (v_t - S^T k_t) * beta_t
    S <- S + k_t d^T
    o_t = S^T q_t

`gated_delta_rule` computes this in chunks of tokens: inside a chunk
everything is matrix products, and only the state crosses from one chunk to
the next. `gated_delta_recurrence` is the per-token form, the definition the
chunked form has to equal (tests/test_gated_delta.py). One algorithm, two
lowerings, chosen by what the code can observe (`takes_kernel`: the backend;
`kernel_shapes`: the heads' width) and by nothing a caller sets:

* on a TPU, for dk and dv multiples of 128, the Pallas kernel pair of
  ops/pallas_gated_delta.py: a chunk's operands, its triangular inverse and
  the state stay in VMEM; q, k, v are read and o is written once, where the
  layer has them (a head is a 128-lane column block of (B, T, H*d)), g and
  beta go in as rows a chunk. The forward a gradient is taken through also
  writes each chunk's entering state (float32, (B, H, chunks, dk, dv): what
  the checkpointed scan below keeps too, 268 MB a layer at the 8k cell's
  shapes and the kernel's chunk of 128) and its (I + A)^-1 (compute dtype,
  134 MB), alive inside the layer's remat unit only; the backward kernel
  walks the chunks last to first and recomputes the rest;
* everywhere else (the CPU, the toy model's 16-wide heads) the XLA form
  below: a `lax.scan` over chunks of `chunk` tokens whose body is
  rematerialised, so the backward pass keeps one state a chunk and nothing
  else of the chunk; the tests' second witness beside the recurrence.

Inside one chunk, with gamma_i = g_1 + ... + g_i (so gamma <= 0) and
M_ij = exp(gamma_i - gamma_j) for j <= i:

    (I + A) D = beta*V - (beta*exp(gamma)*K) S0,   A_ij = beta_i M_ij k_i.k_j  (j < i)
    O  = (exp(gamma)*Q) S0 + ((Q K^T) * M) D
    S' = exp(gamma_C) S0 + (exp(gamma_C - gamma)*K)^T D

A is strictly lower triangular, so (I + A)^-1 = (I - A)(I + A^2)(I + A^4)...
ends after log2(chunk) factors: matrix products only, nothing sequential
inside a chunk. No exponent is ever positive, so strong decay underflows to
zero and never overflows. The decay, the state and every accumulation are
float32 (`precision.f32_island`). Under a bfloat16 policy the products take
the chip's default precision (operands rounded to bfloat16 once a product,
sums in float32, as the attention and expert products do); float32 inputs ask
for `Precision.HIGHEST`, so a float32 policy is float32 on the chip too.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List

import jax
import jax.numpy as jnp
from jax import lax

from pytorchvideo_accelerate_tpu.ops import pallas_gated_delta as kernel
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

CHUNK = 64
_MASKED = -1e30  # exponent of an entry above the diagonal: exp gives 0

_sites: contextvars.ContextVar = contextvars.ContextVar(
    "pva_gdn_scan_kernel_sites", default=None)


@contextlib.contextmanager
def count_sites() -> Iterator[List[tuple]]:
    """Collects, while a model is traced inside the block, one entry (the
    operands' shapes) for every `gated_delta_rule` call that took the
    kernel: the lowering is static, so its engagement is a fact of the trace
    (the `pva_gdn_scan_kernel_sites` gauge, trainer/steps.py)."""
    sites: List[tuple] = []
    token = _sites.set(sites)
    try:
        yield sites
    finally:
        _sites.reset(token)


def takes_kernel() -> bool:
    """The backend half of the rule (`lane_fold.takes_fold`'s): off the TPU
    the kernel could only be interpreted."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_shapes(dk: int, dv: int) -> bool:
    """The shape half: the kernel reads a head as a 128-lane column block of
    the layer's (B, T, H*d) arrays."""
    return dk % kernel.LANES == 0 and dv % kernel.LANES == 0


def _repeat_key_heads(q, k, hv):
    """q, k with hk key heads -> hv value heads (hk divides hv)."""
    rep = hv // q.shape[2]
    if rep == 1:
        return q, k
    return tuple(jnp.repeat(x, rep, axis=2) for x in (q, k))


def gated_delta_recurrence(q, k, v, g, beta):
    """The definition, token by token. q, k: (B, T, Hk, dk), Hk dividing H
    (value heads h*H/Hk .. share key head h); v: (B, T, H, dv); g (log
    decay, <= 0), beta: (B, T, H). Returns o (B, T, H, dv) in float32 and the
    last state (B, H, dk, dv)."""
    q, k, v, g, beta = (f32_island(x) for x in (q, k, v, g, beta))
    q, k = _repeat_key_heads(q, k, v.shape[2])
    b, _, h, dk = q.shape
    hi = lax.Precision.HIGHEST

    def token(state, xs):
        qt, kt, vt, gt, bt = xs  # (B, H, ...)
        state = state * jnp.exp(gt)[..., None, None]
        d = (vt - jnp.einsum("bhkv,bhk->bhv", state, kt, precision=hi)) \
            * bt[..., None]
        state = state + kt[..., :, None] * d[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=hi)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    state, o = lax.scan(token, state0, xs)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(a, mm):
    """(I + a)^-1 for strictly lower triangular `a` (..., C, C), C a power of
    two: the Neumann series as log2(C) products."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv = eye - a
    power = a
    for _ in range(max(c.bit_length() - 2, 0)):
        power = mm(power, power)
        inv = mm(inv, eye + power)
    return inv


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked form; arguments and results as `gated_delta_recurrence`,
    o in v's dtype. T need not be a multiple of the chunk: the tail is padded
    with tokens that leave the state alone (beta 0, no decay). On a TPU, for
    dk and dv multiples of 128, the Pallas kernel pair computes it
    (ops/pallas_gated_delta.py; its chunk length is its own); everywhere else
    the XLA form below, in chunks of `chunk`."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    if takes_kernel() and kernel_shapes(q.shape[-1], v.shape[-1]):
        return _kernel_rule(q, k, v, g, beta)
    q, k = _repeat_key_heads(q, k, v.shape[2])
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    out_dtype = v.dtype
    q, k, v, g, beta = _pad_tokens(-t % chunk, q, k, v, g, beta)
    n = q.shape[1] // chunk

    def chunks(x):  # (B, T, H, ...) -> (N, B, H, C, ...)
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gamma = jnp.cumsum(chunks(f32_island(g)), axis=-1)       # (N, B, H, C)
    beta_c = chunks(f32_island(beta))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], _MASKED))

    precision = (lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else lax.Precision.DEFAULT)

    def mm(x, y, eq="...ij,...jk->...ik"):
        return jnp.einsum(eq, x, y, precision=precision,
                          preferred_element_type=jnp.float32)

    # what does not depend on the state, for every chunk at once
    kk = mm(kc, kc, "...ik,...jk->...ij")
    a = kk * decay * beta_c[..., :, None] * jnp.tril(
        jnp.ones((chunk, chunk), jnp.float32), -1)
    inv = _unit_lower_inverse(a, mm)                          # (N, B, H, C, C)
    u = mm(inv, f32_island(vc) * beta_c[..., None])           # (N, B, H, C, dv)
    w = mm(inv, f32_island(kc) * (beta_c * jnp.exp(gamma))[..., None])
    qk = mm(qc, kc, "...ik,...jk->...ij") * decay             # (N, B, H, C, C)
    q_in = f32_island(qc) * jnp.exp(gamma)[..., None]         # reads S0
    gamma_end = gamma[..., -1:]                               # (N, B, H, 1)
    k_out = f32_island(kc) * jnp.exp(gamma_end - gamma)[..., None]
    # what the scan only ever multiplies goes in in the compute dtype: the
    # products round their operands to it anyway, and the scan reads each
    # array once a pass (u is added to, so it stays float32)
    w, qk, q_in, k_out = (end_island(x, q.dtype) for x in (w, qk, q_in, k_out))

    @jax.checkpoint
    def step(state, xs):
        u_i, w_i, qk_i, q_i, k_i, g_end = xs
        d = u_i - mm(w_i, state)                              # (B, H, C, dv)
        o = mm(q_i, state) + mm(qk_i, d)
        state = state * jnp.exp(g_end)[..., None] + mm(
            k_i, d, "...ck,...cv->...kv")
        return state, o

    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    state, o = lax.scan(step, state0, (u, w, qk, q_in, k_out, gamma_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)             # (B, N, C, H, dv)
    o = o.reshape(b, n * chunk, h, dv)[:, :t]
    return end_island(o, out_dtype), state


def _pad_tokens(pad, *arrays):
    if not pad:
        return arrays
    return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                 for x in arrays)


def _kernel_rule(q, k, v, g, beta):
    """`gated_delta_rule` through the kernel pair: heads stay where the layer
    has them (a head is a column block of the (B, T, H*d) view), the key
    heads are not repeated, and g and beta go in as rows a chunk."""
    sites = _sites.get()
    if sites is not None:
        sites.append((q.shape, v.shape))
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    c, out_dtype = kernel.CHUNK, v.dtype
    q, k, v, g, beta = _pad_tokens(-t % c, q, end_island(k, q.dtype),
                                   end_island(v, q.dtype), g, beta)
    tp = q.shape[1]

    def rows(x):  # (B, T, H) -> (B, H, N, C)
        return jnp.moveaxis(f32_island(x), 1, 2).reshape(b, hv, tp // c, c)

    o, state = kernel.gdn_chunks(
        q.reshape(b, tp, hk * dk), k.reshape(b, tp, hk * dk),
        v.reshape(b, tp, hv * dv), jnp.cumsum(rows(g), axis=-1), rows(beta),
        hk, _interpret())
    return end_island(o.reshape(b, tp, hv, dv)[:, :t], out_dtype), state

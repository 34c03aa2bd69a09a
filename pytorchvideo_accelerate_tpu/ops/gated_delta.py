"""Gated delta rule: the recurrent-state mixer of a Gated-DeltaNet layer.

Per head, with a state S (dk x dv) that starts at zero, for every token t

    S <- exp(g_t) S
    d  = (v_t - S^T k_t) * beta_t
    S <- S + k_t d^T
    o_t = S^T q_t

`gated_delta_rule` computes this in chunks of `chunk` tokens: inside a chunk
everything is matrix products, and only the state crosses from one chunk to
the next (a `lax.scan` over chunks whose body is rematerialised, so the
backward pass keeps one state a chunk and nothing else of the chunk).
`gated_delta_recurrence` is the per-token form, the definition the chunked
form has to equal (tests/test_gated_delta.py).

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

import jax
import jax.numpy as jnp
from jax import lax

from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

CHUNK = 64
_MASKED = -1e30  # exponent of an entry above the diagonal: exp gives 0


def gated_delta_recurrence(q, k, v, g, beta):
    """The definition, token by token. q, k: (B, T, H, dk); v: (B, T, H, dv);
    g (log decay, <= 0), beta: (B, T, H). Returns o (B, T, H, dv) in float32
    and the last state (B, H, dk, dv)."""
    q, k, v, g, beta = (f32_island(x) for x in (q, k, v, g, beta))
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
    o in v's dtype. T need not be a multiple of `chunk`: the tail is padded
    with tokens that leave the state alone (beta 0, no decay)."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    out_dtype = v.dtype
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (t + pad) // chunk

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

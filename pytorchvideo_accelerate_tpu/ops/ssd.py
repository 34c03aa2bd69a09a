"""Selective state space of a Mamba-2 mixer, in the chunked form of its
state-space duality (SSD).

Per head h, with a state S (P x N) that starts at zero, for every token t

    S   <- exp(dt_t a_h) S + dt_t x_t B_t^T
    y_t  = S C_t + d_h x_t

x: (B, T, H, P); dt: (B, T, H), the step after its softplus (> 0); a: (H,),
the log decay a step takes (< 0: -exp(A_log)); B, C: (B, T, G, N), G groups,
head h reading group h // (H / G); d: (H,), the skip. `ssd_scan` computes this
in chunks of tokens; `ssd_recurrence` is the per-token form, the definition
the chunked form has to equal (tests/test_ssd.py).

Inside one chunk, with l_t = dt_t a (<= 0), gamma_i = l_1 + ... + l_i, the
chunk's entering state S0 and M_ij = exp(gamma_i - gamma_j) for j <= i (0
above the diagonal):

    Y  = ((C B^T) * M) (dt * X) + exp(gamma) * (C S0^T) + d X
    S' = exp(gamma_C) S0 + (exp(gamma_C - gamma) * dt * X)^T B

`C B^T` is a group's, computed once for its H / G heads, which differ by
their decay alone. Everything but S0 is known for every chunk at once: only
the state crosses from one chunk to the next, an elementwise `lax.scan` over
the chunks. No exponent is ever positive, so strong decay underflows to zero
and never overflows. The decays, the state and every accumulation are float32
(`precision.f32_island`); the operands of the products are in x's dtype
(under a bfloat16 policy the chip's default precision, sums in float32, as
ops/gated_delta.py's products; float32 inputs ask for `Precision.HIGHEST`).
One lowering, XLA's, everywhere: a Pallas pair that keeps a chunk and the
state in VMEM is docs/KERNELS.md's next step.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List

import jax.numpy as jnp
from jax import lax

from pytorchvideo_accelerate_tpu.ops.gated_delta import MASKED, pad_tokens
from pytorchvideo_accelerate_tpu.precision import end_island, f32_island

CHUNK = 128  # nemotron_h's published `chunk_size`

_sites: contextvars.ContextVar = contextvars.ContextVar(
    "pva_ssd_scan_sites", default=None)


@contextlib.contextmanager
def count_sites() -> Iterator[List[tuple]]:
    """Collects, while a model is traced inside the block, one entry (the
    operands' shapes) for every `ssd_scan` call (the `pva_ssd_scan_sites`
    gauge, trainer/steps.py)."""
    sites: List[tuple] = []
    token = _sites.set(sites)
    try:
        yield sites
    finally:
        _sites.reset(token)


def ssd_recurrence(x, dt, a, b, c, d):
    """The definition, token by token, float32 throughout. Arguments as the
    module says; returns y (B, T, H, P) and the last state (B, H, P, N)."""
    x, dt, a, b, c, d = (f32_island(v) for v in (x, dt, a, b, c, d))
    batch, _, h, p = x.shape
    g, n = b.shape[2:]
    hi = lax.Precision.HIGHEST

    def token(state, xs):           # state (B, G, H/G, P, N)
        xt, dtt, bt, ct = xs        # (B, H, P), (B, H), (B, G, N), (B, G, N)
        dtt = dtt.reshape(batch, g, h // g)
        state = state * jnp.exp(dtt * a.reshape(g, h // g))[..., None, None] \
            + (dtt[..., None] * xt.reshape(batch, g, h // g, p))[..., None] \
            * bt[:, :, None, None, :]
        y = jnp.einsum("bgrpn,bgn->bgrp", state, ct, precision=hi)
        return state, y.reshape(batch, h, p)

    state0 = jnp.zeros((batch, g, h // g, p, n), jnp.float32)
    state, y = lax.scan(token, state0,
                        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1) + d[:, None] * x
    return y, state.reshape(batch, h, p, n)


def ssd_scan(x, dt, a, b, c, d, chunk: int = CHUNK):
    """The chunked form; arguments and results as `ssd_recurrence`, y in x's
    dtype. T need not be a multiple of the chunk: the tail is padded with
    tokens that leave the state alone (dt 0: no decay, no input)."""
    sites = _sites.get()
    if sites is not None:
        sites.append((x.shape, b.shape))
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    dtype = x.dtype
    x, dt, b, c = pad_tokens(-t % chunk, x, dt, b, c)
    k = x.shape[1] // chunk

    def chunks(v):  # (B, T, ...) -> (B, K, C, ...)
        return v.reshape(batch, k, chunk, *v.shape[2:])

    precision = (lax.Precision.HIGHEST if dtype == jnp.float32
                 else lax.Precision.DEFAULT)

    def mm(eq, u, v):
        return jnp.einsum(eq, u, v, precision=precision,
                          preferred_element_type=jnp.float32)

    dt = chunks(f32_island(dt)).reshape(batch, k, chunk, g, r)
    gamma = jnp.cumsum(dt * f32_island(a).reshape(g, r), axis=2)  # (B,K,C,G,R)
    heads_last = jnp.moveaxis(gamma, 2, -1)                       # (B,K,G,R,C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, heads_last[..., :, None] - heads_last[..., None, :], MASKED))
    bs, cs = chunks(b), chunks(c)                                 # (B,K,C,G,N)
    xdt = end_island(chunks(x).reshape(batch, k, chunk, g, r, p)
                     * dt[..., None], dtype)                      # (B,K,C,G,R,P)
    # within a chunk: a group's C B^T under each of its heads' decay
    scores = end_island(mm("bkign,bkjgn->bkgij", cs, bs)[:, :, :, None]
                        * decay, dtype)                           # (B,K,G,R,C,C)
    y = mm("bkgrij,bkjgrp->bkigrp", scores, xdt)                  # (B,K,C,G,R,P)
    # each chunk's own part of the state it hands on, then the state itself
    last = gamma[:, :, -1]                                        # (B,K,G,R)
    into = end_island(xdt * jnp.exp(last[:, :, None] - gamma)[..., None], dtype)
    local = mm("bkjgrp,bkjgn->bkgrpn", into, bs)                  # (B,K,G,R,P,N)

    def step(state, xs):
        local_k, decay_k = xs
        return state * decay_k[..., None, None] + local_k, state

    state0 = jnp.zeros((batch, g, r, p, n), jnp.float32)
    state, entering = lax.scan(step, state0, (jnp.moveaxis(local, 1, 0),
                                              jnp.moveaxis(jnp.exp(last), 1, 0)))
    entering = end_island(jnp.moveaxis(entering, 0, 1), dtype)    # (B,K,G,R,P,N)
    y = y + mm("bkign,bkgrpn->bkigrp", cs, entering) \
        * jnp.exp(gamma)[..., None]
    y = y.reshape(batch, k * chunk, h, p)[:, :t] \
        + f32_island(d)[:, None] * f32_island(x[:, :t])
    return end_island(y, dtype), state.reshape(batch, h, p, n)

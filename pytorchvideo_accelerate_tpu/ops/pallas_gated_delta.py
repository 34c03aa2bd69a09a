"""The chunked gated delta rule as a Pallas TPU kernel pair (ops/gated_delta.py
has the equations; this is their second lowering, the one the chip takes).

One grid step is one chunk of `CHUNK` tokens of `key_heads_a_step` key heads
and the value heads that share them (hv / hk each; their chains are
independent work for the scheduler to interleave); the chunk axis is the
grid's last, sequential one, and the float32 state (dk x dv a value head)
lives in a VMEM scratch from the sequence's first chunk to its last. The chunk's operands, its decay mask, `A`,
`(I + A)^-1`, `d` and every intermediate product stay in VMEM: what crosses
HBM is `q, k, v` in, `o` out (each read or written once, as (CHUNK, 128)
column blocks of the layer's own (B, T, H*d) arrays: no relayout), and the
within-chunk cumulative decay `gamma` and `beta` as (B, H, chunks, CHUNK)
rows, one block a head.

`jax.custom_vjp`: the forward that a gradient is taken through also writes
each chunk's entering state (float32: (B, H, chunks, dk, dv)) and its
`(I + A)^-1` (compute dtype: (B, H, chunks, CHUNK, CHUNK)); the backward kernel
walks the chunks last to first with the state's cotangent in VMEM, recomputes
the chunk's `d` from them and writes the gradients of all five inputs, those
of `q, k` summed over the value heads of a key head. Derived by hand from the
chunk's equations (with R = beta V - (beta e^gamma K) S0, D = T R):

    dD = P^T dO + Kout dS',   dR = T^T dD,   dA = -dR D^T (strictly lower)
    dS = e^gamma_C dS' + (e^gamma Q)^T dO - (beta e^gamma K)^T dR

and the elementwise chain rule for the scalings, the decay mask and beta.

Precision as the XLA form: every product rounds its operands to the compute
dtype once (bfloat16 under a bfloat16 policy; float32 inputs multiply at
`Precision.HIGHEST`) and accumulates in float32; decay, state, `d` and every
sum are float32; no exponent is positive.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorchvideo_accelerate_tpu.precision import (
    ISLAND_DTYPE,
    end_island,
    f32_island,
)

# tokens a grid step: the kernel's own constant (docs/KERNELS.md has the
# chip's readings), not a caller's parameter
CHUNK = 128
LANES = 128
_MASKED = -1e30  # exponent of an entry above the diagonal: exp gives 0

_NN = ((1,), (0,))  # x @ y
_NT = ((1,), (1,))  # x @ y^T
_TN = ((0,), (0,))  # x^T @ y


def _mm(x, y, dims, dt):
    """One product: operands rounded to `dt` once, float32 sums."""
    precision = (lax.Precision.HIGHEST if dt == ISLAND_DTYPE
                 else lax.Precision.DEFAULT)
    return lax.dot_general(end_island(x, dt), end_island(y, dt),
                           (dims, ((), ())), precision=precision,
                           preferred_element_type=ISLAND_DTYPE)


def _unit_lower_inverse(a, mm):
    """(I + a)^-1 for strictly lower triangular `a` (C, C), C a power of two:
    the Neumann series (I - a)(I + a^2)(I + a^4)..., the products the XLA
    form's `_unit_lower_inverse` has. A level's square and the inverse's
    product with the level before share their right operand, so they go
    through the MXU as one product of twice the rows."""
    c = a.shape[-1]
    levels = max(c.bit_length() - 2, 0)
    inv = jnp.eye(c, dtype=a.dtype) - a
    if not levels:
        return inv
    power = mm(a, a)
    for _ in range(levels - 1):
        both = mm(jnp.concatenate([power, inv], axis=0), power)
        power, inv = both[:c], inv + both[c:]
    return inv + mm(inv, power)


def _masks(c):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return col <= row, col < row, col == row


def _to_col(row, eye):
    """(1, C) -> (C, 1), exactly: each sum has one term."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _total(x):
    return jnp.sum(_rowsum(x), axis=0, keepdims=True)  # (1, 1)


class _Chunk:
    """What both passes derive from one chunk's `gamma`, `beta`, `q`, `k`, `v`
    of one value head (kk = k k^T and qk = q k^T are its key head's)."""

    def __init__(self, q, k, v, kk, qk, gam_row, beta_row, dt):
        c = gam_row.shape[-1]
        self.lower, self.strict, self.eye = _masks(c)
        self.dt = dt
        self.gam = _to_col(gam_row, self.eye)                 # (C, 1)
        self.beta = _to_col(beta_row, self.eye)
        self.decay = jnp.exp(
            jnp.where(self.lower, self.gam - gam_row, _MASKED))
        self.kk_decay = jnp.where(self.strict, kk * self.decay, 0.0)
        self.p = qk * self.decay
        self.eg = jnp.exp(self.gam)
        self.last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
        g_end = _rowsum(jnp.where(self.last, gam_row, 0.0))    # (1, 1)
        self.e_end = jnp.exp(g_end)
        self.ek = jnp.exp(g_end - self.gam)
        self.qf, self.kf, self.vf = (f32_island(x) for x in (q, k, v))
        self.qg = self.qf * self.eg
        self.kb = self.kf * (self.beta * self.eg)
        self.ko = self.kf * self.ek

    def mm(self, x, y, dims=_NN):
        return _mm(x, y, dims, self.dt)

    def delta(self, t, kb_s0):
        """d = T (beta v - (beta e^gamma k) S0), float32 (C, dv)."""
        return self.mm(t, self.vf * self.beta - kb_s0)

    def forward(self, s0):
        """(o, S', T) from the entering state. Products that share their
        right operand go through the MXU as one, of twice the rows."""
        c = self.qg.shape[0]
        t = _unit_lower_inverse(self.kk_decay * self.beta, self.mm)
        into_s0 = self.mm(jnp.concatenate([self.kb, self.qg], axis=0), s0)
        d = self.delta(t, into_s0[:c])
        o = into_s0[c:] + self.mm(self.p, d)
        return o, s0 * self.e_end + self.mm(self.ko, d, _TN), t

    def backward(self, s0, t, do, ds1):
        """Cotangents from (dO, dS'): the (C, C) cotangents of q k^T and
        k k^T (their key head sums them over its value heads before the
        products with k and q), the rest of dq and dk (C, dk), dv, the rows
        dgamma and dbeta (1, C), and dS0."""
        mm = self.mm
        beta, eg, ek = self.beta, self.eg, self.ek
        c = self.qg.shape[0]
        d = self.delta(t, mm(self.kb, s0))
        dd = mm(self.p, do, _TN) + mm(self.ko, ds1)
        dko = mm(d, ds1, _NT)
        dr = mm(t, dd, _TN)
        do_dr = jnp.concatenate([f32_island(do), dr], axis=0)
        onto_d = mm(do_dr, d, _NT)                      # (2C, C)
        dp = jnp.where(self.lower, onto_d[:c], 0.0)
        da = jnp.where(self.strict, -onto_d[c:], 0.0)
        onto_s0 = mm(do_dr, s0, _NT)                    # (2C, dk)
        dqg, dkb = onto_s0[:c], -onto_s0[c:]
        ds0 = ds1 * self.e_end + mm(
            jnp.concatenate([self.qg, -self.kb], axis=0), do_dr, _TN)
        dkb_k = _rowsum(dkb * self.kf)                # d(beta e^gamma)
        dbeta = (_rowsum(da * self.kk_decay) + dkb_k * eg
                 + _rowsum(dr * self.vf))
        # through the decay mask: dM * M = dA * A + dP * P
        through_mask = da * (self.kk_decay * beta) + dp * self.p
        dko_ko = _rowsum(dko * self.ko)
        dgam = (_rowsum(through_mask) + _rowsum(dqg * self.qg)
                + dkb_k * beta * eg - dko_ko)
        d_end = _total(dko_ko) + _total(s0 * ds1) * self.e_end
        dgam_row = (_to_row(dgam, self.eye)
                    - jnp.sum(through_mask, axis=0, keepdims=True)
                    + jnp.where(self.last, d_end, 0.0))
        return (dp * self.decay, da * self.decay * beta, dqg * eg,
                dkb * (beta * eg) + dko * ek, dr * beta, dgam_row,
                _to_row(dbeta, self.eye), ds0)


def _head(i, d):
    """Head i's columns of a (CHUNK, heads * d) block."""
    return slice(i * d, (i + 1) * d)


def _fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, last_ref,
                *rest, key_heads, rep, dk, dv, dt, saving):
    """rest: the backward's residuals (states_ref, t_ref) where `saving`,
    then the state's scratch."""
    s_ref = rest[-1]
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for i in range(key_heads):
        q, k = q_ref[:, _head(i, dk)], k_ref[:, _head(i, dk)]
        kk, qk = _mm(k, k, _NT, dt), _mm(q, k, _NT, dt)
        for h in range(i * rep, (i + 1) * rep):
            chunk = _Chunk(q, k, v_ref[:, _head(h, dv)], kk, qk,
                           gam_ref[h, pl.ds(n, 1), :],
                           beta_ref[h, pl.ds(n, 1), :], dt)
            s0 = s_ref[h]
            o, s1, t = chunk.forward(s0)
            o_ref[:, _head(h, dv)] = end_island(o, o_ref.dtype)
            s_ref[h] = s1
            if saving:
                states_ref, t_ref = rest[:2]
                states_ref[h] = s0
                t_ref[h] = end_island(t, dt)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, states_ref, t_ref,
                do_ref, dlast_ref, dq_ref, dk_ref, dv_ref, dgam_ref,
                dbeta_ref, ds_ref, *, key_heads, rep, dk, dv, dt):
    step = pl.program_id(2)
    n = pl.num_programs(2) - 1 - step  # the chunk: last to first

    @pl.when(step == 0)
    def _():
        ds_ref[...] = dlast_ref[...]

    for i in range(key_heads):
        q, k = q_ref[:, _head(i, dk)], k_ref[:, _head(i, dk)]
        kk, qk = _mm(k, k, _NT, dt), _mm(q, k, _NT, dt)
        key_head_parts = []  # a value head's (dqk, dkk, dq, dk)
        for h in range(i * rep, (i + 1) * rep):
            chunk = _Chunk(q, k, v_ref[:, _head(h, dv)], kk, qk,
                           gam_ref[h, pl.ds(n, 1), :],
                           beta_ref[h, pl.ds(n, 1), :], dt)
            *parts, dv_h, dgam_row, dbeta_row, ds0 = chunk.backward(
                states_ref[h], t_ref[h], do_ref[:, _head(h, dv)], ds_ref[h])
            key_head_parts.append(parts)
            ds_ref[h] = ds0
            dv_ref[:, _head(h, dv)] = end_island(dv_h, dv_ref.dtype)
            dgam_ref[h, pl.ds(n, 1), :] = dgam_row
            dbeta_ref[h, pl.ds(n, 1), :] = dbeta_row
        dqk, dkk, dq, dk_ = (functools.reduce(operator.add, x)
                             for x in zip(*key_head_parts))
        dq = dq + _mm(dqk, k, _NN, dt)
        dk_ = (dk_ + _mm(dqk, q, _TN, dt) + _mm(dkk, k, _NN, dt)
               + _mm(dkk, k, _TN, dt))
        dq_ref[:, _head(i, dk)] = end_island(dq, dq_ref.dtype)
        dk_ref[:, _head(i, dk)] = end_island(dk_, dk_ref.dtype)


def key_heads_a_step(hk: int) -> int:
    """Key heads one grid step works on: their chains are independent, so
    the scheduler fills one's product latencies with the other's work."""
    return 2 if hk % 2 == 0 else 1


class _Call:
    """One `pallas_call` over the grid (batch, key-head group, chunk) and the
    BlockSpecs of its operands; `reverse` walks the chunks last to first."""

    def __init__(self, q, v, gam, hk, reverse):
        self.b = q.shape[0]
        _, self.hv, self.n, self.c = gam.shape
        self.hk, self.dt = hk, q.dtype
        self.dk, self.dv = q.shape[-1] // hk, v.shape[-1] // self.hv
        self.key_heads = key_heads_a_step(hk)
        g, n, c = self.key_heads * (self.hv // hk), self.n, self.c

        def chunk(i):
            return n - 1 - i if reverse else i

        self.qk = pl.BlockSpec((None, c, self.key_heads * self.dk),
                               lambda b, h, i: (b, chunk(i), h))
        self.v = pl.BlockSpec((None, c, g * self.dv),
                              lambda b, h, i: (b, chunk(i), h))
        self.rows = pl.BlockSpec((None, g, n, c), lambda b, h, i: (b, h, 0, 0))
        self.state = pl.BlockSpec((None, g, self.dk, self.dv),
                                  lambda b, h, i: (b, h, 0, 0))
        self.states = pl.BlockSpec((None, g, None, self.dk, self.dv),
                                   lambda b, h, i: (b, h, chunk(i), 0, 0))
        self.inverses = pl.BlockSpec((None, g, None, c, c),
                                     lambda b, h, i: (b, h, chunk(i), 0, 0))

    def __call__(self, kernel, name, in_specs, out_specs, out_shape,
                 operands, interpret):
        rep = self.hv // self.hk
        return pl.pallas_call(
            functools.partial(kernel, key_heads=self.key_heads, rep=rep,
                              dk=self.dk, dv=self.dv, dt=self.dt),
            name=name,
            out_shape=out_shape,
            grid=(self.b, self.hk // self.key_heads, self.n),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((self.key_heads * rep, self.dk,
                                        self.dv), ISLAND_DTYPE)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*operands)


def _forward(q, k, v, gam, beta, hk, interpret, save):
    call = _Call(q, v, gam, hk, reverse=False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype),
                 jax.ShapeDtypeStruct((call.b, call.hv, call.dk, call.dv),
                                      ISLAND_DTYPE)]
    out_specs = [call.v, call.state]
    if save:
        out_shape += [
            jax.ShapeDtypeStruct((call.b, call.hv, call.n, call.dk, call.dv),
                                 ISLAND_DTYPE),
            jax.ShapeDtypeStruct((call.b, call.hv, call.n, call.c, call.c),
                                 q.dtype)]
        out_specs += [call.states, call.inverses]
    return call(functools.partial(_fwd_kernel, saving=save),
                "pva_gdn_fwd_saving" if save else "pva_gdn_fwd",
                [call.qk, call.qk, call.v, call.rows, call.rows], out_specs,
                out_shape, (q, k, v, gam, beta), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gdn_chunks(q, k, v, gam, beta, hk, interpret):
    """q, k: (B, T, hk*dk); v: (B, T, hv*dv), T a multiple of CHUNK; gam (the
    cumulative log decay inside each chunk), beta: (B, hv, T/CHUNK, CHUNK)
    float32. Returns o as `v` and the last state (B, hv, dk, dv) float32."""
    o, last = _forward(q, k, v, gam, beta, hk, interpret, save=False)
    return o, last


def _gdn_chunks_fwd(q, k, v, gam, beta, hk, interpret):
    o, last, states, inverses = _forward(q, k, v, gam, beta, hk, interpret,
                                         save=True)
    return (o, last), (q, k, v, gam, beta, states, inverses)


def _gdn_chunks_bwd(hk, interpret, residuals, cotangents):
    q, k, v, gam, beta, states, inverses = residuals
    do, dlast = cotangents
    call = _Call(q, v, gam, hk, reverse=True)
    gradients = [call.qk, call.qk, call.v, call.rows, call.rows]
    return tuple(call(
        _bwd_kernel, "pva_gdn_bwd",
        gradients + [call.states, call.inverses, call.v, call.state],
        gradients,
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in residuals[:5]],
        (q, k, v, gam, beta, states, inverses, end_island(do, v.dtype),
         f32_island(dlast)), interpret))


gdn_chunks.defvjp(_gdn_chunks_fwd, _gdn_chunks_bwd)

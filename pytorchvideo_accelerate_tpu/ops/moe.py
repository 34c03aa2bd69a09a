"""A share of a routed expert layer: route over all experts, compute the held.

An expert-parallel deployment divides a layer's `num_experts` routed experts
over chips. A chip that holds experts [offset, offset + held) is told so: it
routes every token over ALL experts (the router keeps its published width and
its experts per token), computes the part of the result that its own experts
give, and leaves out what the absent experts would have added. On one chip
there is no exchange; the partial sum is what goes on (docs/TOKENS.md).

    p = softmax_f32(x W_r);  (w_k, e_k) = top-k of p;  w_k <- w_k / sum_k w_k
    y = sum over k with offset <= e_k < offset + held of
            w_k * (act(x W_gate[e_k]) * (x W_up[e_k])) W_down[e_k]
                                        (act: SiLU by default, ReLU for ReGLU experts)

or, for a sigmoid router with a selection bias b (nemotron_h) and experts of
two products:

    s = sigmoid_f32(x W_r);  e_k = top-k of s + b
    w_k = scale * s_{e_k} / (sum_k s_{e_k} + 1e-20)
    y = sum over the held e_k of w_k * act(x W_up[e_k]) W_down[e_k]   (act: relu^2)

No capacity factor and no dropped token: every (token, held expert) pair is
computed, whatever the imbalance. The pairs are sorted by expert, so each held
expert's rows are contiguous and the three products are grouped matrix
products (`lax.ragged_dot`: the TPU's compiler turns it into one tiled kernel
that visits only the tiles that hold rows, the CPU's into plain products).

Shapes are static, so the row buffers need a bound. The worst case (every
pair local: tokens x min(k, held) rows) is `num_experts / held` times the
expected load, and buffers of that size cost that much memory and traffic in
every step. So the layer looks at the count it has just made: where the local
pairs fit `SLACK` (2) times the expected load, in whole tiles (`buffer_rows`;
they do, short of a 2-fold skew toward the held experts), all tokens go
through buffers of that many rows at once; where they do not, the tokens go
through the same function in chunks small enough that a chunk's worst case
fits the same buffers. One `lax.cond` on the count chooses, and says which
(`expert_share`'s third output); each way is rematerialised in the backward
pass (`_way`). Either way every pair is computed with the same products and
sums; only the second way is slower. Where twice the expected load reaches
the worst case (half the experts held or more) there is one way and no
`cond`.

Moving rows is gathers, never a scatter: the rows leave in sorted order
(`x[token of row]`) and come back by each token collecting its k rows
(`_spread`, `_collect`: each is the other's backward pass).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from pytorchvideo_accelerate_tpu.precision import end_island, f32_island


def route(x, router_kernel, top_k: int, norm_topk: bool = True,
          score: str = "softmax", selection_bias=None, scale: float = 1.0):
    """Router in float32: (weights (N, k) float32, experts (N, k) int32).
    `Precision.HIGHEST`, because the top-k is a comparison: logits rounded to
    bfloat16 choose other experts for the tokens near a tie. `score`: the
    experts' scores, "softmax" over all or an independent "sigmoid" each;
    `selection_bias` (num_experts,) is added to the scores to CHOOSE the
    experts and not to weigh them; the weights are times `scale`."""
    logits = jnp.dot(f32_island(x), f32_island(router_kernel),
                     precision=lax.Precision.HIGHEST)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score must be 'softmax' or 'sigmoid', got {score!r}")
    if selection_bias is None:
        weights, experts = lax.top_k(probs, top_k)
    else:
        _, experts = lax.top_k(probs + f32_island(selection_bias), top_k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk:
        total = weights.sum(axis=-1, keepdims=True)
        if score == "sigmoid":  # independent scores may all be near 0
            total = total + 1e-20
        weights = weights / total
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


SLACK = 2    # row buffers hold this many times the expected local pairs
ROW_TILE = 512  # buffers are whole tiles of the grouped product


def buffer_rows(n: int, top_k: int, held: int, num_experts: int) -> int:
    """The row buffers a layer of `n` tokens takes where its local pairs fit
    them: `SLACK` times the expected local pairs in whole tiles, at most the
    worst case (every pair local)."""
    worst = n * min(top_k, held)
    expected = n * top_k * held / num_experts
    return min(worst, -(-int(SLACK * expected) // ROW_TILE) * ROW_TILE)


@jax.custom_vjp
def _spread(x, token_of, position):
    """Rows out: x (N, D) -> x[token_of] (R, D). `position` (N, k) says for
    every token which rows read it (R where a pair has no row)."""
    del position
    return x[token_of]


@jax.custom_vjp
def _collect(ys, token_of, position):
    """Rows back: ys (R, D) -> for every token the sum of its k rows (N, D),
    summed in float32, a slot of the k at a time (no (N, k, D) array)."""
    del token_of
    padded = jnp.concatenate([ys, jnp.zeros((1, ys.shape[1]), ys.dtype)])
    total = f32_island(padded[position[:, 0]])
    for j in range(1, position.shape[1]):
        total = total + f32_island(padded[position[:, j]])
    return end_island(total, ys.dtype)


_spread.defvjp(
    lambda x, token_of, position: (x[token_of], (token_of, position)),
    lambda res, g: (_collect(g, *res), None, None))
_collect.defvjp(
    lambda ys, token_of, position: (_collect(ys, token_of, position),
                                    (token_of, position)),
    lambda res, g: (_spread(g, *res), None, None))


def _share(x, weights, experts, w_gate, w_up, w_down, expert_offset, bound,
           activation=jax.nn.silu):
    """The held experts' part for tokens x (N, D) through buffers of `bound`
    rows; right only where at most `bound` pairs are local. Returns (y (N, D),
    rows (held,) int32)."""
    n, d = x.shape
    top_k = experts.shape[-1]
    held = w_up.shape[0]
    pairs = n * top_k
    with jax.named_scope("dispatch"):
        local = experts.reshape(pairs) - expert_offset
        slot = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(slot, stable=True)       # sorted row -> pair
        rows = jnp.bincount(slot, length=held + 1)[:held].astype(jnp.int32)
        # pair -> its row among the first `bound`, else `bound` (no row)
        position = jnp.minimum(jnp.argsort(order), bound).reshape(n, top_k)
        order = order[:bound]
        token_of = (order // top_k).astype(jnp.int32)
        # a grouped product leaves the rows behind the last group unwritten,
        # in the backward pass too: select them away (never scale: they may
        # hold anything) on the way in, which also selects their cotangent
        valid = jnp.arange(bound) < rows.sum()
        xs = jnp.where(valid[:, None], _spread(x, token_of, position), 0)
    with jax.named_scope("experts"):
        precision = (lax.Precision.HIGHEST if x.dtype == jnp.float32
                     else lax.Precision.DEFAULT)

        def grouped(lhs, w):
            return lax.ragged_dot(lhs, w.astype(x.dtype), rows,
                                  precision=precision)

        if w_gate is None:  # two products: act(x U) V
            ys = grouped(activation(grouped(xs, w_up)), w_down)
        else:
            ys = grouped(activation(grouped(xs, w_gate)) * grouped(xs, w_up),
                         w_down)
    with jax.named_scope("combine"):
        w_rows = jnp.where(valid, weights.reshape(pairs)[order], 0.0)
        ys = jnp.where(valid[:, None], f32_island(ys), 0.0) * w_rows[:, None]
        y = _collect(end_island(ys, x.dtype), token_of, position)
    return y, rows


def expert_share(x, weights, experts, w_gate, w_up, w_down, expert_offset: int,
                 num_experts: int, activation=jax.nn.silu):
    """The held experts' part of the mixture for tokens x (N, D), given the
    routing (`route`) over `num_experts`. `w_gate`, `w_up` (held, D, F) and
    `w_down` (held, F, D) are the experts [expert_offset, expert_offset +
    held); `activation` is the gate's (SiLU: SwiGLU experts; ReLU: ReGLU).
    `w_gate` None: experts of two products, act(x w_up) w_down.
    Returns (y (N, D) in x's dtype, rows (held,) int32: how many tokens each
    held expert computed, tight: whether the local pairs fit the buffers of
    `buffer_rows`; where not, the tokens went through in chunks)."""
    n, _ = x.shape
    top_k = experts.shape[-1]
    held = w_up.shape[0]
    worst = n * min(top_k, held)
    bound = buffer_rows(n, top_k, held, num_experts)
    args = (w_gate, w_up, w_down, expert_offset)
    if bound == worst:
        return (*_share(x, weights, experts, *args, bound, activation),
                jnp.bool_(True))
    chunks = -(-worst // bound)
    per = -(-n // chunks)                      # tokens a chunk; its worst case
    chunk_bound = per * min(top_k, held)       # fits `bound` rows

    def in_chunks(x, weights, experts):
        pad = chunks * per - n                 # padded tokens choose no expert
        x, weights = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, weights))
        experts = jnp.pad(experts, ((0, pad), (0, 0)), constant_values=-1)
        # rematerialised: the backward pass keeps a chunk's inputs, not its rows
        y, rows = lax.map(
            jax.checkpoint(lambda c: _share(*c, *args, chunk_bound, activation)),
            tuple(a.reshape(chunks, per, a.shape[-1])
                  for a in (x, weights, experts)))
        return y.reshape(chunks * per, -1)[:n], rows.sum(axis=0)

    local = experts - expert_offset
    tight = jnp.sum((local >= 0) & (local < held)) <= bound
    y, rows = lax.cond(tight,
                       _way(lambda *a: _share(*a, *args, bound, activation)),
                       _way(in_chunks), x, weights, experts)
    return y, rows, tight


def _way(way):
    """A branch of `expert_share`'s `cond`, rematerialised: the backward pass
    keeps the cond's inputs, where it would keep the taken way's rows (and
    zeros for the other's): 15 ms a step and 1.8 GB of peak memory in
    `smallthinker_21b_a3b.train_16k`, 4 ms in `qwen3_next_80b_a3b.train_8k`
    (PERF.md §6, PR 37), and no third forward run under the layer's remat.
    Its ops open `moe/` again after the cond's `cond/branch_<i>_fun/`, so
    that a trace's scope tables find `moe/dispatch/`, `moe/experts/`,
    `moe/combine/` whichever way ran."""
    def scoped(*operands):
        with jax.named_scope("moe"):
            return way(*operands)
    return jax.checkpoint(scoped)

"""Plain reference of the SmallThinker decoder: the published equations in
`jax.numpy`, float32, `Precision.HIGHEST`. No kernels, no sliced key ranges,
no grouped products; nothing is imported from the program.

Source: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
config.json (`model_name` `smallthinker_21b_instruct`; arXiv:2507.20984);
`arch` holds its keys, plus the share held here: `experts_held` of the
`moe_num_primary_experts` routed experts from `expert_offset` on, and
`vocab_size` rows of the vocabulary. The layouts may be the published 52
entries: layer i reads entry i. x: (B, T, hidden); `norm(x) = w * x *
rsqrt(mean(x^2) + eps)`; no biases anywhere.

  layer i:  n = norm_in(x);  (w_k, e_k) = route(n);  h = x + attn_i(n)
            y = h + moe(norm_post(h); w_k, e_k). Last: norm_f, untied head,
            mean next-token cross-entropy over the held vocabulary.
  attn_i:   q = n Wq (Hq heads), k, v = n Wk, n Wv (Hkv heads); where
            rope_layout[i] is 1, rotate-half rotary over the whole head on q
            and k; where 0, no positional encoding at all. softmax(q k^T /
            sqrt(head_dim)) v, each key-value head serving Hq / Hkv query
            heads; where sliding_window_layout[i] is 1 query t reads keys s
            with 0 <= t - s < sliding_window_size, where 0 keys s <= t;
            out = attn Wo. No q/k norm, no output gate.
  route:    p = softmax(n W_r) over all experts; top-k; weights = chosen p
            over their sum (equal to the published top-k of the logits
            followed by a softmax over the k).
  moe:      E_e(u) = (relu(u W_gate,e) * (u W_up,e)) W_down,e;  moe(u) = sum
            over the chosen experts HELD HERE of w_k E_ek(u): a loop over the
            held experts with a mask. What absent experts would add is left
            out. No shared expert.

Departures from the published model, as in the program: the router's input
(the paper's pre-attention router; config.json has no key for it), no
secondary experts, no router auxiliary loss, no dropout, one document a
sequence.

Attention and the loss are taken a block of positions at a time (dense masked
products of a block of queries against ALL keys: the band is a mask, never a
slice; each block rematerialised) so that the reference fits beside its AdamW
state at the cell's size; the arithmetic is the dense one.

`q` is the control's switch (plain.quantize), as in `reference/qwen3_next.py`:
projection operands and results, activations and the residual stream are held
in `q`, forward and cotangent; the float32 islands (router, softmax, norm
statistics, loss) stay float32. `fault` plants what `correct` has to catch:
"half_batch" (half the sequences; of a single sequence its first half),
"experts_skipped", "bf16_router", and this family's own: "window_ignored"
(every layer reads all keys behind it), "rope_everywhere" (the full layers
rotated too), "router_after_attention" (the router reads norm_post(h)),
"silu_experts"; "state_unchanged" is `follow`'s.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
from jax import lax

from . import plain
from .qwen3_next import adamw_update

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 128    # query rows computed together against all keys
ROW_BLOCK = 512      # loss positions computed together

# the model's scopes as patterns over an op's name stack, for the job's
# table of a traced run (jobs/train_fit_lm.py)
MODEL_SCOPES = ("attn/qkv/", "attn/core/", "attn/out/", "swa/qkv/",
                "swa/core/", "swa/out/", "moe/router/", "moe/dispatch/",
                "moe/experts/", "moe/combine/", "lm_head/", "loss/")
# what `--stand-in` may name for this family
STAND_INS = {
    "control": {"q": "control"},
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
    "experts_skipped": {"fault": "experts_skipped"},
    "bf16_router": {"fault": "bf16_router"},
    "window_ignored": {"fault": "window_ignored"},
    "rope_everywhere": {"fault": "rope_everywhere"},
    "router_after_attention": {"fault": "router_after_attention"},
    "silu_experts": {"fault": "silu_experts"},
}
# leaves whose first gradient `follow` hands back whole, by the reading they
# feed (the norm of the program's difference from it, over its norm): what a
# leaf's norm alone cannot tell apart, such as other keys read at the same
# scale
DIRECTION_LEAVES = {"grad_dir_gap_k_proj": "k_proj",
                    "grad_dir_gap_router": "router"}


def held(arch):
    return arch.get("experts_held") or arch["moe_num_primary_experts"]


def rotary(arch, i):
    return bool(arch["rope_layout"][i])


def window(arch, i):
    """The band of layer i: tokens, or None where it reads all keys."""
    return (arch["sliding_window_size"] if arch["sliding_window_layout"][i]
            else None)


# --- parameters (the program's tree: the one interface both sides share) ----

def init_params(arch, seed):
    """Seeded leaves under the program's paths: matrices N(0, 0.02), the
    embedding N(0, 1), norm scales 1. A full layer's attention lives under
    `attn`, a windowed layer's under `swa`; the router beside it, in the
    mixer. These are the BENCHMARK's weights (`give_weights` hands them to the
    program, whose own initialiser keeps the embedding at 0.02 and is never
    run in a cell). The embedding at 0.02 too would leave a token's own vector
    no larger than what attention adds, the average of thousands of random
    tokens' values, which is nearly the same for every query: the routers of
    the later layers then see one vector and send most tokens to the same
    few experts. A trained model's routing is balanced; unit-variance
    embeddings keep it so here at step 1."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    f, e = arch["moe_ffn_hidden_size"], held(arch)
    keys = iter(jax.random.split(jax.random.key(int(seed) % (2 ** 31)), 4096))

    def mat(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def scale():
        return {"scale": jnp.ones((d,))}

    params = {"embed": mat(v, d) / 0.02, "lm_head": mat(d, v),
              "final_norm": scale()}
    for i in range(arch["num_hidden_layers"]):
        params[f"mixer_{i}"] = {
            "input_norm": scale(),
            "moe": {"router": mat(d, arch["moe_num_primary_experts"])},
            "swa" if window(arch, i) else "attn": {
                "q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                "v_proj": mat(d, hkv * hd), "o_proj": mat(hq * hd, d)}}
        params[f"mixture_{i}"] = {
            "post_norm": scale(),
            "moe": {"w_gate": mat(e, d, f), "w_up": mat(e, d, f),
                    "w_down": mat(e, f, d)}}
    return params


def init_variables(arch, seed):
    """{"params", "batch_stats"}, made in one jitted call: the same call gives
    the same leaves to the program and, later, to the reference."""
    return {"params": jax.jit(lambda: init_params(arch, seed))(),
            "batch_stats": {}}


# --- the layers ---------------------------------------------------------------

def norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def _dot(x, w, q):
    """x W with both operands and the result held as the policy holds them."""
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    return keep(jnp.dot(keep(x), keep(w), precision=HI))


def rotate_half(x, theta):
    """Rotate-half rotary over the whole head of x (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_core(q, k, v, scale, band, remat, skip):
    """Dense masked product: token t reads keys s <= t, under a `band` those
    with t - s < band. q (B, T, Hq, D), k and v (B, T, Hkv, D); rows of
    queries a block at a time against ALL keys."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if skip:  # test hook: no product of the core's class, every input read
        return q + sum(jnp.repeat(y, hq // hkv, axis=2) for y in (k, v))

    def rows(q_blk, start):
        # query head h * (Hq / Hkv) + g reads key-value head h
        n = q_blk.shape[1]
        q_blk = q_blk.reshape(b, n, hkv, hq // hkv, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k, precision=HI) * scale
        delta = (start + jnp.arange(n))[:, None] - jnp.arange(t)[None, :]
        mask = delta >= 0
        if band is not None:
            mask = mask & (delta < band)
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v, precision=HI)
        return out.reshape(b, n, hq, d)

    if t <= QUERY_BLOCK:
        return rows(q, 0)
    if remat:
        rows = jax.checkpoint(rows)
    # one block after another (`lax.map`), so that one block's scores exist
    pad = -t % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (t + pad) // QUERY_BLOCK, QUERY_BLOCK, hq, d)
    out = lax.map(lambda xs: rows(*xs),
                  (jnp.moveaxis(blocks, 1, 0),
                   jnp.arange(0, t + pad, QUERY_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t + pad, hq, d)[:, :t]


def attention(p, x, arch, i, q, remat, skip, fault):
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    b, t, _ = x.shape
    hq, hkv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    qh = _dot(x, p["q_proj"], q).reshape(b, t, hq, d)
    kh = _dot(x, p["k_proj"], q).reshape(b, t, hkv, d)
    vh = _dot(x, p["v_proj"], q).reshape(b, t, hkv, d)
    if rotary(arch, i) or fault == "rope_everywhere":
        qh = keep(rotate_half(qh, arch["rope_theta"]))
        kh = keep(rotate_half(kh, arch["rope_theta"]))
    band = None if fault == "window_ignored" else window(arch, i)
    o = keep(attention_core(qh, kh, vh, d ** -0.5, band, remat, skip))
    return _dot(o.reshape(b, t, hq * d), p["o_proj"], q)


def routing(router, x, arch, fault=None):
    """(weights (N, k), experts (N, k)) over ALL experts, float32."""
    if fault == "bf16_router":
        x, router = (y.astype(jnp.bfloat16).astype(jnp.float32)
                     for y in (x, router))
        logits = jnp.dot(x, router, precision=HI)
        logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        logits = jnp.dot(x, router, precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, arch["moe_num_active_primary_experts"])
    if arch.get("norm_topk_prob", True):
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, experts


def mixture(p, x, weights, experts, arch, q, remat, skip, fault):
    """(y, rows): the held experts' part for tokens x (N, D) under the given
    routing, and how many tokens chose each held expert."""
    act = jax.nn.silu if fault == "silu_experts" else jax.nn.relu
    keep = lambda t: plain.quantize(t, q)  # noqa: E731

    def expert(y, xs):
        w_gate, w_up, w_down, e = xs
        # the weight this expert has for each token (0 where it was not chosen)
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        hidden = keep(act(_dot(x, w_gate, q)) * _dot(x, w_up, q))
        out = _dot(hidden, w_down, q)
        return y + out * w_e[:, None], jnp.sum(experts == e)

    if remat:
        expert = jax.checkpoint(expert)
    ids = arch.get("expert_offset", 0) + jnp.arange(held(arch))
    if skip or fault == "experts_skipped":
        # (the test hook reads the weights, so that the router's gradient
        # products stay in the jaxpr; the planted fault adds nothing)
        y = x * weights.sum(axis=-1, keepdims=True) if skip \
            else jnp.zeros_like(x)
        rows = jnp.sum(experts[:, :, None] == ids[None, None, :], axis=(0, 1))
        if fault == "experts_skipped":
            rows = jnp.zeros_like(rows)
        return keep(y), rows
    y, rows = lax.scan(expert, jnp.zeros_like(x),
                       (p["w_gate"], p["w_up"], p["w_down"], ids))
    return keep(y), rows


def _per_sequence(fn):
    """fn(x (B, T, D), p) -> (y (B, T, D), rows) applied to the sequences one
    after another."""
    def mapped(x, p):
        y, rows = lax.map(lambda xi: fn(xi[None], p), x)
        return y[:, 0], rows
    return mapped


def trunk(params, tokens, arch, q=None, remat=True, skip=(), fault=None):
    """Hidden states after the last norm (B, T, hidden) and the held
    experts' rows a layer (layers, held)."""
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    eps = arch["rms_norm_eps"]
    x = keep(jnp.take(params["embed"], tokens, axis=0))
    rows = []
    for i in range(arch["num_hidden_layers"]):

        def layer(x, p, i=i):
            mixer, mix = p
            b, t, d = x.shape
            normed = keep(norm(x, mixer["input_norm"], eps))
            h = keep(x + attention(mixer["swa" if window(arch, i) else "attn"],
                                   normed, arch, i, q, remat,
                                   "attn_core" in skip, fault))
            post = keep(norm(h, mix["post_norm"], eps))
            read = post if fault == "router_after_attention" else normed
            weights, experts = routing(mixer["moe"]["router"],
                                       read.reshape(b * t, d), arch, fault)
            y, r = mixture(mix["moe"], post.reshape(b * t, d), weights,
                           experts, arch, q, remat, "moe_experts" in skip,
                           fault)
            return keep(h + y.reshape(b, t, d)), r

        if remat:
            # a sequence after another, each layer rematerialised: a layer's
            # float32 activations of ONE sequence are what has to fit beside
            # the AdamW state (tokens of different sequences never meet)
            layer = _per_sequence(jax.checkpoint(layer))
        x, r = layer(x, (params[f"mixer_{i}"], params[f"mixture_{i}"]))
        rows.append(r.reshape(-1, r.shape[-1]).sum(axis=0))
    return keep(norm(x, params["final_norm"], eps)), jnp.stack(rows)


def logits(params, tokens, arch, **kw):
    """(B, T, vocab) float32: small sizes only."""
    x, _ = trunk(params, tokens, arch, **kw)
    return jnp.dot(x, params["lm_head"], precision=HI)


def loss_and_rows(params, tokens, arch, q=None, remat=True, skip=(),
                  fault=None):
    """Mean next-token cross-entropy (position t against token t + 1; the
    last position of a sequence against nothing) and the held experts' rows."""
    if fault == "half_batch":
        tokens = (tokens[:tokens.shape[0] // 2] if tokens.shape[0] > 1
                  else tokens[:, :tokens.shape[1] // 2])
    x, rows = trunk(params, tokens, arch, q=q, remat=remat, skip=skip,
                    fault=fault)
    x, targets = x[:, :-1], tokens[:, 1:]
    n = targets.size
    x, targets = x.reshape(n, -1), targets.reshape(n)
    head = plain.quantize(params["lm_head"], q)

    def block(xs):
        xb, yb, wb = xs
        z = jnp.dot(xb, head, precision=HI)
        return jnp.sum(wb * (jax.nn.logsumexp(z, axis=-1)
                             - jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]))

    if remat:
        block = jax.checkpoint(block)
    # a block of positions after another; the tail is padded with weight 0
    size = min(ROW_BLOCK, n)
    pad = -n % size
    parts = (jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(targets, (0, pad)),
             jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)))
    if n + pad == size:
        return block(parts) / n, rows
    total = lax.map(block, tuple(
        a.reshape((n + pad) // size, size, *a.shape[1:]) for a in parts)).sum()
    return total / n, rows


# --- the steps (AdamW is reference/qwen3_next.py's, written out there) --------

def _direction_leaves(grads):
    """{path: leaf} of the leaves `DIRECTION_LEAVES` names."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if any(part in DIRECTION_LEAVES.values() for part in name.split("/")):
            out[name] = leaf
    return out


_STEPS = {}


def make_step(arch, optim, q=None, fault=None):
    """(params, mu, nu, tokens, count) -> (params, mu, nu, loss, clipped
    gradient norms by leaf, rows (layers, held), the clipped gradient's
    `DIRECTION_LEAVES`), jitted once a variant."""
    key = json.dumps([arch, optim, q, fault], sort_keys=True)
    if key not in _STEPS:
        def step(params, mu, nu, tokens, count):
            (loss, rows), grads = jax.value_and_grad(
                lambda p: loss_and_rows(p, tokens, arch, q=q, fault=fault),
                has_aux=True)(params)
            params, mu, nu, grads = adamw_update(params, mu, nu, grads,
                                                 count.astype(jnp.float32), optim)
            return (params, mu, nu, loss, plain.leaf_norms(grads), rows,
                    _direction_leaves(grads))

        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1, 2))
    return _STEPS[key]


def follow(arch, optim, params, batches, q=None, fault=None, note=None):
    """Drive the reference through `batches` ({"tokens"} each, on one device)
    from `params`. Returns what `reference/qwen3_next.py` `follow` returns
    (the losses, the first step's clipped gradient norms by leaf, the norms of
    the parameters' change over all the steps by leaf, the leaves' sizes, the
    (token, held expert) pairs of each step) and `grad_leaves`: the first
    clipped gradient of the `DIRECTION_LEAVES`, on the host.
    `fault="state_unchanged"` keeps the first state through every step."""
    step = make_step(arch, optim, q=q,
                     fault=None if fault == "state_unchanged" else fault)
    # the step takes its whole state in place (parameters and both moments),
    # so the start is kept on the host
    start = jax.device_get(params)
    zeros = lambda: jax.tree.map(jnp.zeros_like, start)  # noqa: E731
    mu, nu = zeros(), zeros()
    losses, pairs, grad_norms, grad_leaves = [], [], None, None
    for i, batch in enumerate(batches):
        params, mu, nu, loss, norms, rows, leaves = step(
            params, mu, nu, batch["tokens"], jnp.int32(i))
        if fault == "state_unchanged":
            del params, mu, nu
            params, mu, nu = jax.device_put(start), zeros(), zeros()
        losses.append(float(loss))
        pairs.append(int(rows.sum()))
        if note:
            note(f"reference step {i + 1} done")
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms.items()}
            grad_leaves = jax.device_get(leaves)
        del leaves
    del mu, nu
    delta = jax.jit(lambda a, b: plain.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))(params, start)
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path): int(leaf.size)
             for path, leaf in jax.tree_util.tree_flatten_with_path(start)[0]}
    return {"losses": losses, "grad_norms": grad_norms, "sizes": sizes,
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "pairs": pairs, "grad_leaves": grad_leaves}

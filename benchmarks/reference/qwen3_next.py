"""Plain reference of the Qwen3-Next decoder: the published equations in
`jax.numpy`, float32, `Precision.HIGHEST`. No kernels, no chunked scan, no
grouped products; nothing is imported from the program.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json
(`model_type` `qwen3_next`); `arch` holds its keys, plus the share held here:
`experts_held` of the `num_experts` routed experts from `expert_offset` on,
and `vocab_size` rows of the vocabulary. x: (B, T, hidden); every norm is
`x * rsqrt(mean(x^2) + eps)`, "zero-centred" scales multiply by 1 + w.

  layer i:  h = x + mixer_i(zc_norm(x));  y = h + moe(zc_norm(h))
            mixer_i = gated attention where (i + 1) % full_attention_interval
            == 0, else Gated DeltaNet. Last: zc_norm, untied head, mean
            next-token cross-entropy over the held vocabulary.
  gated attention: [q | gate] = split_per_head(x Wq); k, v = x Wk, x Wv;
            zc_norm per head on q and k; rotary (rotate-half) on the first
            head_dim * partial_rotary_factor dims; causal softmax(q k^T /
            sqrt(head_dim)) v, each key-value head serving Hq / Hkv query
            heads; out = (attn * sigmoid(gate)) Wo.
  Gated DeltaNet: [q, k, v, z] = x W_qkvz; [b, a] = x W_ba; [q, k, v] <-
            silu(causal depthwise conv over time); beta = sigmoid(b);
            g = -exp(A_log) * softplus(a + dt_bias); q, k l2-normed per head,
            q / sqrt(dk), repeated to the value heads; per head, S from zero:
            S <- exp(g_t) S; d = (v_t - S^T k_t) beta_t; S <- S + k_t d^T;
            o_t = S^T q_t (a `lax.scan` over tokens, rematerialised in
            blocks); out = (rms(o) * w * silu(z)) W_out.
  mixture:  p = softmax(x W_r) over all num_experts; top-k; weights = chosen
            p over their sum; E_e(x) = (silu(x W_gate,e) * (x W_up,e))
            W_down,e; moe(x) = sum over the chosen experts HELD HERE of
            w_k E_ek(x) + sigmoid(x w_sg) * E_shared(x): a loop over the held
            experts with a mask. What absent experts would add is left out.

Departures from the published model, as in the program: no router auxiliary
loss, no multi-token-prediction module, no dropout, one document a sequence.

Attention and the loss are taken a block of positions at a time (dense masked
products, each block rematerialised) so that the reference fits beside its
AdamW state at the cell's size; the arithmetic is the dense one.

`q` is the control's switch (plain.quantize): every tensor the policy holds
in bfloat16 (projection operands and results, activations, the residual
stream) is held in `q` instead, forward and cotangent; the float32 islands
(router, decay and state, norm statistics, loss) stay float32. `fault` plants
what `correct` has to catch: "half_batch", "experts_skipped" (the held
experts' part left out), "bf16_router" (router logits from operands rounded
to bfloat16), "bf16_state" (the DeltaNet state rounded to bfloat16 after every
token); "state_unchanged" is `follow`'s.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

HI = lax.Precision.HIGHEST
SCAN_BLOCK = 64      # tokens of the DeltaNet scan rematerialised together
ROW_BLOCK = 1024     # query rows / loss positions computed together


def layer_type(arch, i):
    return ("full_attention" if (i + 1) % arch["full_attention_interval"] == 0
            else "linear_attention")


def held(arch):
    return arch.get("experts_held") or arch["num_experts"]


# --- parameters (the program's tree: the one interface both sides share) ----

def init_params(arch, seed):
    """Seeded leaves under the program's paths: matrices N(0, 0.02),
    A_log = log(U(0, 16)), dt_bias 1, zero-centred norm scales 0, the
    DeltaNet output norm 1."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    kdim = arch["linear_num_key_heads"] * arch["linear_key_head_dim"]
    hv, dv = arch["linear_num_value_heads"], arch["linear_value_head_dim"]
    vdim = hv * dv
    f, fs = arch["moe_intermediate_size"], arch["shared_expert_intermediate_size"]
    e = held(arch)
    keys = iter(jax.random.split(jax.random.key(int(seed) % (2 ** 31)), 4096))

    def mat(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    params = {"embed": mat(v, d), "lm_head": mat(d, v),
              "final_norm": {"scale": jnp.zeros((d,))}}
    for i in range(arch["num_hidden_layers"]):
        if layer_type(arch, i) == "full_attention":
            mixer = {"attn": {
                "q_proj": mat(d, hq * 2 * hd), "k_proj": mat(d, hkv * hd),
                "v_proj": mat(d, hkv * hd), "o_proj": mat(hq * hd, d),
                "q_norm": {"scale": jnp.zeros((hd,))},
                "k_norm": {"scale": jnp.zeros((hd,))}}}
        else:
            mixer = {"gdn": {
                "in_proj_qkvz": mat(d, 2 * kdim + 2 * vdim),
                "in_proj_ba": mat(d, 2 * hv),
                "conv": mat(arch["linear_conv_kernel_dim"], 2 * kdim + vdim),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (hv,), minval=1e-3, maxval=16.0)),
                "dt_bias": jnp.ones((hv,)), "norm": jnp.ones((dv,)),
                "out_proj": mat(vdim, d)}}
        mixer["input_norm"] = {"scale": jnp.zeros((d,))}
        params[f"mixer_{i}"] = mixer
        params[f"mixture_{i}"] = {
            "post_norm": {"scale": jnp.zeros((d,))},
            "moe": {"router": mat(d, arch["num_experts"]),
                    "w_gate": mat(e, d, f), "w_up": mat(e, d, f),
                    "w_down": mat(e, f, d),
                    "shared_gate_proj": mat(d, fs), "shared_up_proj": mat(d, fs),
                    "shared_down_proj": mat(fs, d),
                    "shared_expert_gate": mat(d, 1)}}
    return params


def init_variables(arch, seed):
    """{"params", "batch_stats"}, made in one jitted call: the same call gives
    the same leaves to the program and, later, to the reference."""
    return {"params": jax.jit(lambda: init_params(arch, seed))(),
            "batch_stats": {}}


# --- the layers ---------------------------------------------------------------

def rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def zc_norm(x, p, eps):
    return rms(x, eps) * (1.0 + p["scale"])


def _dot(x, w, q):
    """x W with both operands and the result held as the policy holds them."""
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    return keep(jnp.dot(keep(x), keep(w), precision=HI))


def rotate_half(x, theta, rotary_dim):
    t, half = x.shape[1], rotary_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary_dim:]], axis=-1)


def attention_core(q, k, v, scale, remat, skip):
    """Dense masked product: token t reads keys 0..t. q (B, T, Hq, D), k and
    v (B, T, Hkv, D); rows of queries a block at a time against ALL keys."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    k, v = (jnp.repeat(y, hq // hkv, axis=2) for y in (k, v))
    if skip:  # test hook: no product of the core's class, every input read
        return q + k + v

    def rows(q_blk, start):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k, precision=HI) * scale
        mask = (jnp.arange(t)[None, :]
                <= start + jnp.arange(q_blk.shape[1])[:, None])
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HI)

    if t <= ROW_BLOCK:
        return rows(q, 0)
    if remat:
        rows = jax.checkpoint(rows)
    # one block after another (`lax.map`), so that one block's scores exist
    pad = -t % ROW_BLOCK
    blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (t + pad) // ROW_BLOCK, ROW_BLOCK, hq, d)
    out = lax.map(lambda xs: rows(*xs),
                  (jnp.moveaxis(blocks, 1, 0),
                   jnp.arange(0, t + pad, ROW_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t + pad, hq, d)[:, :t]


def gated_attention(p, x, arch, q, remat, skip):
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    b, t, _ = x.shape
    hq, hkv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    qg = _dot(x, p["q_proj"], q).reshape(b, t, hq, 2 * d)
    qh, gate = qg[..., :d], qg[..., d:]
    kh = _dot(x, p["k_proj"], q).reshape(b, t, hkv, d)
    vh = _dot(x, p["v_proj"], q).reshape(b, t, hkv, d)
    eps = arch["rms_norm_eps"]
    rotary = int(d * arch["partial_rotary_factor"])
    qh = keep(rotate_half(keep(zc_norm(qh, p["q_norm"], eps)),
                          arch["rope_theta"], rotary))
    kh = keep(rotate_half(keep(zc_norm(kh, p["k_norm"], eps)),
                          arch["rope_theta"], rotary))
    o = keep(attention_core(qh, kh, vh, d ** -0.5, remat, skip))
    o = keep(o * jax.nn.sigmoid(gate))
    return _dot(o.reshape(b, t, hq * d), p["o_proj"], q)


def delta_scan(qh, kh, vh, g, beta, remat, fault=None):
    """The per-token recurrence. qh, kh (B, T, H, dk), vh (B, T, H, dv),
    g, beta (B, T, H); the state starts at zero."""
    b, t, h, dk = qh.shape
    dv = vh.shape[-1]

    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None, None]
        d = (vt - jnp.einsum("bhkv,bhk->bhv", state, kt, precision=HI)) \
            * bt[..., None]
        state = state + kt[..., :, None] * d[..., None, :]
        if fault == "bf16_state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=HI)

    def block(state, xs):
        return lax.scan(token, state, xs)

    if remat:
        block = jax.checkpoint(block)
    pad = -t % SCAN_BLOCK
    xs = []
    for x in (qh, kh, vh, g, beta):
        x = jnp.moveaxis(x, 1, 0)                            # (T, B, H, ...)
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        xs.append(x.reshape((t + pad) // SCAN_BLOCK, SCAN_BLOCK, *x.shape[1:]))
    _, o = lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), tuple(xs))
    return jnp.moveaxis(o.reshape(t + pad, b, h, dv)[:t], 0, 1)


def gated_delta_net(p, x, arch, q, remat, skip, fault):
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    b, t, _ = x.shape
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    kdim, vdim = hk * dk, hv * dv
    qkvz = _dot(x, p["in_proj_qkvz"], q)
    ba = _dot(x, p["in_proj_ba"], q)
    qkv, z = qkvz[..., :2 * kdim + vdim], qkvz[..., 2 * kdim + vdim:]
    taps = arch["linear_conv_kernel_dim"]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    w = keep(p["conv"])
    qkv = keep(jax.nn.silu(sum(padded[:, i:i + t] * w[i] for i in range(taps))))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])

    def l2norm(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    qh = keep(l2norm(qkv[..., :kdim].reshape(b, t, hk, dk)) * dk ** -0.5)
    kh = keep(l2norm(qkv[..., kdim:2 * kdim].reshape(b, t, hk, dk)))
    vh = qkv[..., 2 * kdim:].reshape(b, t, hv, dv)
    qh, kh = (jnp.repeat(y, hv // hk, axis=2) for y in (qh, kh))
    if skip:  # test hook: no product of the scan's class, every input read
        o = vh * (beta + g)[..., None] + jnp.sum(qh * kh, -1, keepdims=True)
    else:
        o = delta_scan(qh, kh, vh, g, beta, remat, fault)
    o = keep(rms(keep(o), arch["rms_norm_eps"]) * p["norm"]
             * jax.nn.silu(z.reshape(b, t, hv, dv)))
    return _dot(o.reshape(b, t, vdim), p["out_proj"], q)


def routing(p, x, arch, fault=None):
    """(weights (N, k), experts (N, k)) over ALL experts, float32."""
    xr, wr = x, p["router"]
    if fault == "bf16_router":
        xr, wr = (y.astype(jnp.bfloat16).astype(jnp.float32) for y in (xr, wr))
        logits = jnp.dot(xr, wr, precision=HI)
        logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        logits = jnp.dot(xr, wr, precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, arch["num_experts_per_tok"])
    if arch.get("norm_topk_prob", True):
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, experts


def mixture(p, x, arch, q, remat, skip, fault):
    """(y, rows): the held experts' part plus the shared expert, and how many
    tokens chose each held expert."""
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    weights, experts = routing(p, flat, arch, fault)
    offset = arch.get("expert_offset", 0)

    def expert(y, xs):
        w_gate, w_up, w_down, e = xs
        # the weight this expert has for each token (0 where it was not chosen)
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        hidden = keep(jax.nn.silu(_dot(flat, w_gate, q)) * _dot(flat, w_up, q))
        out = _dot(hidden, w_down, q)
        return y + out * w_e[:, None], jnp.sum(experts == e)

    if remat:
        expert = jax.checkpoint(expert)
    n_held = held(arch)
    ids = offset + jnp.arange(n_held)
    if skip or fault == "experts_skipped":
        # (the test hook reads the weights, so that the router's gradient
        # products stay in the jaxpr; the planted fault adds nothing)
        y = flat * weights.sum(axis=-1, keepdims=True) if skip \
            else jnp.zeros_like(flat)
        rows = jnp.sum(experts[:, :, None] == ids[None, None, :], axis=(0, 1))
        if fault == "experts_skipped":
            rows = jnp.zeros_like(rows)
    else:
        y, rows = lax.scan(expert, jnp.zeros_like(flat),
                           (p["w_gate"], p["w_up"], p["w_down"], ids))
    hidden = keep(jax.nn.silu(_dot(flat, p["shared_gate_proj"], q))
                  * _dot(flat, p["shared_up_proj"], q))
    shared = _dot(hidden, p["shared_down_proj"], q)
    gate = jax.nn.sigmoid(_dot(flat, p["shared_expert_gate"], q))
    y = keep(keep(y) + keep(shared * gate))
    return y.reshape(b, t, d), rows


def _per_sequence(fn):
    """fn(x (B, T, D), p) applied to the sequences one after another."""
    def mapped(x, p):
        out = lax.map(lambda xi: fn(xi[None], p), x)
        return jax.tree.map(lambda y: y[:, 0] if y.ndim > 2 else y, out)
    return mapped


def trunk(params, tokens, arch, q=None, remat=True, skip=(), fault=None):
    """Hidden states after the last norm (B, T, hidden) and the held
    experts' rows a layer (layers, held)."""
    keep = lambda t: plain.quantize(t, q)  # noqa: E731
    eps = arch["rms_norm_eps"]
    x = keep(jnp.take(params["embed"], tokens, axis=0))
    rows = []
    for i in range(arch["num_hidden_layers"]):
        kind = layer_type(arch, i)

        def mixer(x, p, kind=kind):
            normed = keep(zc_norm(x, p["input_norm"], eps))
            if kind == "full_attention":
                out = gated_attention(p["attn"], normed, arch, q, remat,
                                      "attn_core" in skip)
            else:
                out = gated_delta_net(p["gdn"], normed, arch, q, remat,
                                      "gdn_scan" in skip, fault)
            return keep(x + out)

        def mix(h, p):
            normed = keep(zc_norm(h, p["post_norm"], eps))
            y, r = mixture(p["moe"], normed, arch, q, remat,
                           "moe_experts" in skip, fault)
            return keep(h + y), r

        if remat:
            # a sequence after another, each layer rematerialised: a layer's
            # float32 activations of ONE sequence are what has to fit beside
            # the AdamW state (tokens of different sequences never meet)
            mixer, mix = (_per_sequence(jax.checkpoint(f)) for f in (mixer, mix))
        x = mixer(x, params[f"mixer_{i}"])
        x, r = mix(x, params[f"mixture_{i}"])
        rows.append(r.reshape(-1, r.shape[-1]).sum(axis=0))
    return keep(zc_norm(x, params["final_norm"], eps)), jnp.stack(rows)


def logits(params, tokens, arch, **kw):
    """(B, T, vocab) float32: small sizes only."""
    x, _ = trunk(params, tokens, arch, **kw)
    return jnp.dot(x, params["lm_head"], precision=HI)


def loss_and_rows(params, tokens, arch, q=None, remat=True, skip=(),
                  fault=None):
    """Mean next-token cross-entropy (position t against token t + 1; the
    last position of a sequence against nothing) and the held experts' rows."""
    if fault == "half_batch":
        tokens = tokens[:tokens.shape[0] // 2]
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


# --- AdamW, as optax.adamw computes it, written out ---------------------------

def learning_rate(optim, count):
    """`build_lr_schedule`'s cosine over the epoch, no warm-up."""
    frac = jnp.minimum(count, optim["total_steps"]) / optim["total_steps"]
    return optim["lr"] * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))


def adamw_update(params, mu, nu, grads, count, optim):
    """clip_by_global_norm, then adamw (b1 0.9, b2 0.999, eps 1e-8, decoupled
    weight decay on every leaf). Returns (params, mu, nu, clipped grads)."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    clip = optim.get("grad_clip_norm", 0.0)
    if clip > 0:
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        # optax: g * clip / max(norm, clip), no epsilon
        grads = jax.tree.map(lambda g: g * clip / jnp.maximum(norm, clip), grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    t = count + 1
    lr = learning_rate(optim, count)

    def leaf(p, m, v):
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps)
                         + optim["weight_decay"] * p)

    return jax.tree.map(leaf, params, mu, nu), mu, nu, grads


_STEPS = {}


def make_step(arch, optim, q=None, fault=None):
    """(params, mu, nu, tokens, count) -> (params, mu, nu, loss, clipped
    gradient norms by leaf, rows (layers, held)), jitted once a variant."""
    key = json.dumps([arch, optim, q, fault], sort_keys=True)
    if key not in _STEPS:
        def step(params, mu, nu, tokens, count):
            (loss, rows), grads = jax.value_and_grad(
                lambda p: loss_and_rows(p, tokens, arch, q=q, fault=fault),
                has_aux=True)(params)
            params, mu, nu, grads = adamw_update(params, mu, nu, grads,
                                                 count.astype(jnp.float32), optim)
            return params, mu, nu, loss, plain.leaf_norms(grads), rows

        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1, 2))
    return _STEPS[key]


def follow(arch, optim, params, batches, q=None, fault=None, note=None):
    """Drive the reference through `batches` ({"tokens"} each, on one device)
    from `params`. Returns the losses, the first step's clipped gradient
    norms by leaf, the norms of the parameters' change over all the steps by
    leaf, the leaves' sizes, and the (token, held expert) pairs of each step.
    `fault="state_unchanged"` keeps the first state through every step."""
    step = make_step(arch, optim, q=q,
                     fault=None if fault == "state_unchanged" else fault)
    # the step takes its whole state in place (parameters and both moments:
    # 7.5 GB of the chip at the cell's size), so the start is kept on the host
    start = jax.device_get(params)
    zeros = lambda: jax.tree.map(jnp.zeros_like, start)  # noqa: E731
    mu, nu = zeros(), zeros()
    losses, pairs, grad_norms = [], [], None
    for i, batch in enumerate(batches):
        params, mu, nu, loss, norms, rows = step(params, mu, nu,
                                                 batch["tokens"], jnp.int32(i))
        if fault == "state_unchanged":
            del params, mu, nu
            params, mu, nu = jax.device_put(start), zeros(), zeros()
        losses.append(float(loss))
        pairs.append(int(rows.sum()))
        if note:
            note(f"reference step {i + 1} done")
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms.items()}
    del mu, nu
    delta = jax.jit(lambda a, b: plain.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))(params, start)
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path): int(leaf.size)
             for path, leaf in jax.tree_util.tree_flatten_with_path(start)[0]}
    return {"losses": losses, "grad_norms": grad_norms, "sizes": sizes,
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "pairs": pairs}

"""Plain reference of the Nemotron-H decoder: the published equations in
`jax.numpy`, float32, `Precision.HIGHEST`. The Mamba-2 layer is the per-token
recurrence, a loop over the held experts, dense masked attention; no kernels,
no chunked scan, no grouped products; nothing is imported from the program.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
config.json (`model_type` `nemotron_h`); `arch` holds its numbers, plus the
share held here: `experts_held` of the `n_routed_experts` routed experts from
`expert_offset` on, and `vocab_size` rows of the vocabulary. Layer i is the
i-th character of `hybrid_override_pattern` (`arch`'s, else the published
`PATTERN`: the job's `arch` holds numbers only), the first
`num_hidden_layers` of it. x: (B, T, hidden); `norm(x) = w * x *
rsqrt(mean(x^2) + eps)`, eps `layer_norm_epsilon`; every layer is x +
mixer(norm(x)); last: norm_f, the untied head, the mean next-token
cross-entropy over the held vocabulary.

  M (Mamba-2; H heads of P, G groups of state N, conv of K taps, I = H P):
      [z | xBC | dt] = u W_in  (widths I | I + 2 G N | H)
      xBC = silu(b_conv + sum_j w_j xBC_{t-K+1+j}) = [x | B | C]
      dt = softplus(dt + dt_bias);  a_t = exp(-dt_t exp(A_log))
      S_t = a_t S_{t-1} + dt_t x_t B_t^T  (P x N a head, 0 at the start; head
            h reads group h // (H / G));  y_t = S_t C_t + D x_t
      out = ((y * silu(z)) normed over each of G groups of I / G columns,
            times w_norm) W_out
  E:  s = sigmoid(u W_r) over ALL experts; the top-k of s + b choose
      (b = `e_score_correction_bias`, no gradient); w_k = routed_scaling_factor
      * s_{e_k} / (sum_j s_{e_j} + 1e-20); out = sum over the chosen experts
      HELD HERE of w_k relu(u U_e)^2 V_e (a loop over the held experts with a
      mask; what absent experts would add is left out) + relu(u U_s)^2 V_s
  *:  q = u Wq (Hq heads), k, v = u Wk, u Wv (Hkv heads), no positional
      encoding; softmax(q k^T / sqrt(head_dim)) v over keys s <= t, each
      key-value head serving Hq / Hkv query heads; out = . Wo

Departures, as in the program: no multi-token prediction, no update rule for
the correction bias, no dropout, one document a sequence. The recurrence is a
`lax.scan` over the tokens, rematerialised a block of `TOKEN_BLOCK` tokens at
a time; attention and the loss a block of positions at a time; each layer
rematerialised and run a sequence at a time, so that 2 x 8,192 tokens fit
beside the AdamW state. The arithmetic is the plain one.

`q` is the control's switch (`reference/ouro.py` `quantize`: an 8-bit value
clipped to its format's range): projection operands and results, activations
and the residual stream are held in `q`, forward and cotangent; the float32
islands (router, softmax, norm statistics, the recurrence's decay and state,
the loss) stay float32. `fault` plants what `correct` has to catch: the
standard "half_batch", "experts_skipped", "bf16_router", and this family's
own: "softmax_router" (a softmax over all experts in the sigmoid's place),
"unscaled_routing" (routed_scaling_factor 1), "relu_experts" (relu, not
squared), "d_skip_dropped" (no D x), "norm_ungrouped" (one RMS over all I
columns), "groups_shared" (every head reads group 0's B and C),
"decay_ignored" (a_t = 1); "state_unchanged" is `follow`'s. Every fault
but "half_batch" (another shape) is a switch of the one compiled step
(`SWITCHED`, a traced vector: `follow` compiles the sound step once for
them all); named to `loss_and_rows` it is a Python choice, so that the sound
jaxpr holds none of their arithmetic.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain
from .ouro import quantize
from .qwen3_next import adamw_update
from .smallthinker import HI, ROW_BLOCK, attention_core, norm

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # published
TOKEN_BLOCK = 128    # tokens of the recurrence rematerialised together

# the model's scopes as patterns over an op's name stack, for the job's
# table of a traced run (jobs/train_fit_lm.py)
MODEL_SCOPES = ("mamba/in_proj/", "mamba/conv/", "mamba/scan/", "mamba/out/",
                "moe/router/", "moe/dispatch/", "moe/experts/", "moe/combine/",
                "moe/shared/", "attn/qkv/", "attn/core/", "attn/out/",
                "lm_head/", "loss/")
FAULTS = ("softmax_router", "unscaled_routing", "relu_experts",
          "d_skip_dropped", "norm_ungrouped", "groups_shared", "decay_ignored")
# what `--stand-in` may name for this family
STAND_INS = {
    "control": {"q": "control"},
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
    "experts_skipped": {"fault": "experts_skipped"},
    "bf16_router": {"fault": "bf16_router"},
    **{name: {"fault": name} for name in FAULTS},
}
# the faults one compiled step takes as traced switches (`_pick`)
SWITCHED = FAULTS + ("experts_skipped", "bf16_router")
# leaves whose first gradient `follow` hands back whole, by the reading they
# feed (jobs/train_fit_lm.py `direction_gaps`): what a leaf's norm alone
# cannot tell apart, such as another group's B and C read at the same scale
DIRECTION_LEAVES = {"grad_dir_gap_in_proj": "in_proj",
                    "grad_dir_gap_router": "router"}


def held(arch):
    return arch.get("experts_held") or arch["n_routed_experts"]


def pattern(arch):
    """The kinds of the layers run: the first `num_hidden_layers`."""
    return arch.get("hybrid_override_pattern", PATTERN)[:arch["num_hidden_layers"]]


def widths(arch):
    """(H, P, G, N, I, width of xBC)."""
    h, p = arch["mamba_num_heads"], arch["mamba_head_dim"]
    g, n = arch["n_groups"], arch["ssm_state_size"]
    return h, p, g, n, h * p, h * p + 2 * g * n


# --- parameters (the program's tree: the one interface both sides share) ----

def init_params(arch, seed):
    """Seeded leaves under the program's paths: matrices N(0, 0.02), the
    embedding N(0, 1), norm scales 1, the correction bias 0; the conv's
    weights and bias U(-1/2, 1/2) (PyTorch's default at fan-in 4), A_log =
    log(1..H), D = 1, dt_bias the Mamba-2 rule's (dt log-uniform over
    [time_step_min, time_step_max], at least time_step_floor, stored as
    softplus's inverse). These are the BENCHMARK's weights (`give_weights`
    hands them to the program, whose own initialiser keeps the embedding at
    0.02); a unit-variance embedding keeps the routing of random weights
    balanced, as in the other token cells (reference/smallthinker.py).
    `seed` may be traced."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    h, _p, _g, _n, inner, conv_dim = widths(arch)
    f, fs = arch["moe_intermediate_size"], arch["moe_shared_expert_intermediate_size"]
    experts, taps = arch["n_routed_experts"], arch["conv_kernel"]
    keys = iter(jax.random.split(jax.random.key(seed), 1024))

    def mat(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(bound, *shape):
        return jax.random.uniform(next(keys), shape, minval=-bound, maxval=bound)

    def scale(width=d):
        return {"scale": jnp.ones((width,))}

    lo, hi = math.log(arch["time_step_min"]), math.log(arch["time_step_max"])
    params = {"embed": mat(v, d) / 0.02, "lm_head": mat(d, v),
              "final_norm": scale()}
    for i, kind in enumerate(pattern(arch)):
        layer = {"norm": scale()}
        if kind == "M":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(next(keys), (h,))
                                     * (hi - lo) + lo), arch["time_step_floor"])
            layer["mamba"] = {
                "in_proj": mat(d, inner + conv_dim + h),
                "conv": uniform(taps ** -0.5, taps, conv_dim),
                "conv_bias": uniform(taps ** -0.5, conv_dim),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
                "D": jnp.ones((h,)), "norm": jnp.ones((inner,)),
                "out_proj": mat(inner, d)}
        elif kind == "E":
            layer["moe"] = {
                "router": mat(d, experts),
                "e_score_correction_bias": jnp.zeros((experts,)),
                "w_up": mat(held(arch), d, f), "w_down": mat(held(arch), f, d),
                "shared_up_proj": mat(d, fs), "shared_down_proj": mat(fs, d)}
        else:
            layer["attn"] = {"q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                             "v_proj": mat(d, hkv * hd), "o_proj": mat(hq * hd, d)}
        params[f"layer_{i}"] = layer
    return params


def init_variables(arch, seed):
    """{"params", "batch_stats"}, made in one jitted call that takes the seed
    as an argument: the same call gives the same leaves to the program and,
    later, to the reference, and a new seed compiles nothing."""
    make = jax.jit(lambda s: init_params(arch, s))
    return {"params": make(jnp.uint32(int(seed) % (2 ** 31))),
            "batch_stats": {}}


# --- the layers ---------------------------------------------------------------

def switches(fault=None, flags=None):
    """{switched fault: on}: Python bools from the fault's name, or the
    entries of a traced vector over `SWITCHED`."""
    if flags is None:
        return {name: name == fault for name in SWITCHED}
    return {name: flags[i] for i, name in enumerate(SWITCHED)}


def _pick(on, when_on, when_off):
    """`when_on()` where the fault is on, else `when_off()`: a Python choice
    for a Python bool, a select of both for a traced switch."""
    if isinstance(on, bool):
        return when_on() if on else when_off()
    return jnp.where(on, when_on(), when_off())


def _dot(x, w, q):
    """x W with both operands and the result held as the policy holds them."""
    return quantize(jnp.dot(quantize(x, q), quantize(w, q), precision=HI), q)


def recurrence(x, dt, a, b, c, d, skip=False, on=None):
    """y (B, T, H, P) of the per-token recurrence; x (B, T, H, P), dt (B, T,
    H) after its softplus, a (H,) = -exp(A_log), b and c (B, T, G, N), d (H,).
    A scan over the tokens, `TOKEN_BLOCK` of them rematerialised together
    where there are more. `on`: the switched faults (`switches`)."""
    on = on or switches()
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    if skip:  # test hook: no product of the recurrence, every input read
        return x * (dt + a)[..., None] + jnp.repeat(
            (b + c).sum(axis=-1), r, axis=2)[..., None] + d[:, None] * x
    b, c = (_pick(on["groups_shared"],
                  lambda y=y: jnp.repeat(y[:, :, :1], g, axis=2), lambda y=y: y)
            for y in (b, c))

    def token(state, xs):           # state (B, G, H/G, P, N)
        xt, dtt, bt, ct = xs        # (B, H, P), (B, H), (B, G, N), (B, G, N)
        dtt = dtt.reshape(batch, g, r)
        keep = _pick(on["decay_ignored"], lambda: jnp.ones_like(dtt),
                     lambda: jnp.exp(dtt * a.reshape(g, r)))
        state = state * keep[..., None, None] \
            + (dtt[..., None] * xt.reshape(batch, g, r, p))[..., None] \
            * bt[:, :, None, None, :]
        y = jnp.einsum("bgrpn,bgn->bgrp", state, ct, precision=HI)
        return state, y.reshape(batch, h, p)

    state0 = jnp.zeros((batch, g, r, p, n), jnp.float32)
    xs = tuple(jnp.moveaxis(y, 1, 0) for y in (x, dt, b, c))   # tokens first
    if t <= TOKEN_BLOCK:
        _, y = lax.scan(token, state0, xs)
    else:
        # padded tokens (dt 0, x 0) leave the state alone; a block's states
        # exist only while its backward pass runs
        pad = -t % TOKEN_BLOCK
        xs = tuple(jnp.pad(y, ((0, pad),) + ((0, 0),) * (y.ndim - 1)).reshape(
            (t + pad) // TOKEN_BLOCK, TOKEN_BLOCK, *y.shape[1:]) for y in xs)
        _, y = lax.scan(jax.checkpoint(lambda s, blk: lax.scan(token, s, blk)),
                        state0, xs)
        y = y.reshape(t + pad, batch, h, p)[:t]
    y = jnp.moveaxis(y, 0, 1)
    return _pick(on["d_skip_dropped"], lambda: y, lambda: y + d[:, None] * x)


def mamba(p, x, arch, q, skip, on):
    keep = lambda y: quantize(y, q)  # noqa: E731
    batch, t, _ = x.shape
    h, hp, g, n, inner, conv_dim = widths(arch)
    proj = _dot(x, p["in_proj"], q)
    z, xbc, step = (proj[..., :inner], proj[..., inner:inner + conv_dim],
                    proj[..., inner + conv_dim:])
    taps = arch["conv_kernel"]
    w = quantize(p["conv"], q)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = keep(jax.nn.silu(p["conv_bias"] + sum(
        padded[:, j:j + t] * w[j] for j in range(taps))))
    dt = jax.nn.softplus(step + p["dt_bias"])
    y = recurrence(xbc[..., :inner].reshape(batch, t, h, hp), dt,
                   -jnp.exp(p["A_log"]),
                   xbc[..., inner:inner + g * n].reshape(batch, t, g, n),
                   xbc[..., inner + g * n:].reshape(batch, t, g, n), p["D"],
                   skip, on)
    y = keep(y).reshape(batch, t, inner) * jax.nn.silu(z)
    eps = arch["layer_norm_epsilon"]

    def normed(v):
        return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)

    y = _pick(on["norm_ungrouped"], lambda: normed(y), lambda: normed(
        y.reshape(batch, t, g, inner // g)).reshape(batch, t, inner))
    return _dot(keep(y * p["norm"]), p["out_proj"], q)


def attention(p, x, arch, q, remat, skip):
    keep = lambda y: quantize(y, q)  # noqa: E731
    b, t, _ = x.shape
    hq, hkv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    qh = _dot(x, p["q_proj"], q).reshape(b, t, hq, d)
    kh = _dot(x, p["k_proj"], q).reshape(b, t, hkv, d)
    vh = _dot(x, p["v_proj"], q).reshape(b, t, hkv, d)
    o = keep(attention_core(qh, kh, vh, d ** -0.5, None, remat, skip))
    return _dot(o.reshape(b, t, hq * d), p["o_proj"], q)


def routing(p, x, arch, on=None):
    """(weights (N, k), experts (N, k)) over ALL experts, float32."""
    on = on or switches()
    router = p["router"]

    def rounded():
        bf16 = lambda y: y.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return bf16(jnp.dot(bf16(x), bf16(router), precision=HI))

    logits = _pick(on["bf16_router"], rounded,
                   lambda: jnp.dot(x, router, precision=HI))
    scores = _pick(on["softmax_router"], lambda: jax.nn.softmax(logits, axis=-1),
                   lambda: jax.nn.sigmoid(logits))
    _, experts = lax.top_k(scores + p["e_score_correction_bias"],
                           arch["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if arch.get("norm_topk_prob", True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * _pick(on["unscaled_routing"], lambda: 1.0,
                           lambda: arch["routed_scaling_factor"]), experts


def mixture(p, x, arch, q, remat, skip, on):
    """(y, rows): the held experts' part for tokens x (N, D) plus the shared
    expert's, and how many tokens chose each held expert."""
    keep = lambda y: quantize(y, q)  # noqa: E731

    def act(y):
        return _pick(on["relu_experts"], lambda: jax.nn.relu(y),
                     lambda: jnp.square(jax.nn.relu(y)))

    weights, experts = routing(p, x, arch, on)
    shared = _dot(keep(act(_dot(x, p["shared_up_proj"], q))),
                  p["shared_down_proj"], q)

    def expert(y, xs):
        w_up, w_down, e = xs
        # the weight this expert has for each token (0 where it was not chosen)
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        out = _dot(keep(act(_dot(x, w_up, q))), w_down, q)
        return y + out * w_e[:, None], jnp.sum(experts == e)

    if remat:
        expert = jax.checkpoint(expert)
    ids = arch.get("expert_offset", 0) + jnp.arange(held(arch))
    if skip:
        # test hook: reads the weights, so that the router's gradient
        # products stay in the jaxpr
        rows = jnp.sum(experts[:, :, None] == ids[None, None, :], axis=(0, 1))
        return keep(x * weights.sum(axis=-1, keepdims=True) + shared), rows
    y, rows = lax.scan(expert, jnp.zeros_like(x), (p["w_up"], p["w_down"], ids))
    # the planted fault: the held experts add nothing, and count no row
    y, rows = (_pick(on["experts_skipped"], lambda v=v: jnp.zeros_like(v),
                     lambda v=v: v) for v in (y, rows))
    return keep(y + shared), rows


def trunk(params, tokens, arch, q=None, remat=True, skip=(), on=None):
    """Hidden states after the last norm (B, T, hidden) and the held
    experts' rows a mixture layer (mixture layers, held). `on`: the switched
    faults (`switches`)."""
    on = on or switches()
    keep = lambda y: quantize(y, q)  # noqa: E731
    eps = arch["layer_norm_epsilon"]
    x = keep(jnp.take(params["embed"], tokens, axis=0))
    rows = []
    for i, kind in enumerate(pattern(arch)):

        def layer(x, p, kind=kind):
            if q is not None:
                # the control's rounded weights are made in the layer that
                # uses them, not all nine layers' ahead of the first
                x, p = lax.optimization_barrier((x, p))
            b, t, d = x.shape
            u = keep(norm(x, p["norm"], eps))
            r = jnp.zeros((held(arch),), jnp.int32)
            if kind == "M":
                y = mamba(p["mamba"], u, arch, q, "ssd_scan" in skip, on)
            elif kind == "*":
                y = attention(p["attn"], u, arch, q, remat, "attn_core" in skip)
            else:
                y, r = mixture(p["moe"], u.reshape(b * t, d), arch, q, remat,
                               "moe_experts" in skip, on)
                y = y.reshape(b, t, d)
            return keep(x + y), r

        if remat:
            # a sequence after another, each layer rematerialised: a layer's
            # float32 activations of ONE sequence are what has to fit beside
            # the AdamW state (tokens of different sequences never meet)
            one = jax.checkpoint(layer)

            def layer(x, p, one=one):
                y, r = lax.map(lambda xi: one(xi[None], p), x)
                return y[:, 0], r.sum(axis=0)

        x, r = layer(x, params[f"layer_{i}"])
        if kind == "E":
            rows.append(r)
    return keep(norm(x, params["final_norm"], eps)), jnp.stack(rows)


def logits(params, tokens, arch, **kw):
    """(B, T, vocab) float32: small sizes only."""
    x, _ = trunk(params, tokens, arch, **kw)
    return jnp.dot(x, params["lm_head"], precision=HI)


def loss_and_rows(params, tokens, arch, q=None, remat=True, skip=(),
                  fault=None, flags=None):
    """Mean next-token cross-entropy (position t against token t + 1; the
    last position of a sequence against nothing) and the held experts' rows.
    `flags`: a traced vector over `SWITCHED` in the place of `fault`'s name."""
    if fault == "half_batch":
        tokens = (tokens[:tokens.shape[0] // 2] if tokens.shape[0] > 1
                  else tokens[:, :tokens.shape[1] // 2])
    x, rows = trunk(params, tokens, arch, q=q, remat=remat, skip=skip,
                    on=switches(fault, flags))
    x, targets = x[:, :-1], tokens[:, 1:]
    n = targets.size
    x, targets = x.reshape(n, -1), targets.reshape(n)
    head = quantize(params["lm_head"], q)

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
    """(params, mu, nu, tokens, count, flags) -> (params, mu, nu, loss,
    clipped gradient norms by leaf, rows (mixture layers, held), the clipped
    gradient's `DIRECTION_LEAVES`), jitted once a variant: `flags` (over
    `SWITCHED`) picks a switched fault, so the sound step serves them all."""
    fault = None if fault in SWITCHED else fault
    key = json.dumps([arch, optim, q, fault], sort_keys=True)
    if key not in _STEPS:
        def step(params, mu, nu, tokens, count, flags):
            (loss, rows), grads = jax.value_and_grad(
                lambda p: loss_and_rows(p, tokens, arch, q=q, fault=fault,
                                        flags=flags),
                has_aux=True)(params)
            params, mu, nu, grads = adamw_update(params, mu, nu, grads,
                                                 count.astype(jnp.float32), optim)
            return (params, mu, nu, loss, plain.leaf_norms(grads), rows,
                    _direction_leaves(grads))

        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1, 2))
    return _STEPS[key]


def follow(arch, optim, params, batches, q=None, fault=None, note=None):
    """Drive the reference through `batches` ({"tokens"} each, on one device)
    from `params`. Returns what the other token references' `follow` returns
    (the losses, the first step's clipped gradient norms by leaf, the norms of
    the parameters' change over all the steps by leaf, the leaves' sizes, the
    (token, held expert) pairs of each step) and `grad_leaves`: the first
    clipped gradient of the `DIRECTION_LEAVES`, on the host.
    `fault="state_unchanged"` keeps the first state through every step."""
    step = make_step(arch, optim, q=q,
                     fault=None if fault == "state_unchanged" else fault)
    # the step takes its whole state in place (parameters and both moments),
    # so the start is kept on the host. The moments are made where the
    # parameters are (committed to their device, as every later step's are):
    # uncommitted ones would compile the first step a second time
    start = jax.device_get(params)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    zeros = lambda p: jax.tree.map(jnp.zeros_like, p)  # noqa: E731
    mu, nu = zeros(params), zeros(params)
    losses, pairs, grad_norms, grad_leaves = [], [], None, None
    flags = jnp.array([name == fault for name in SWITCHED])
    for i, batch in enumerate(batches):
        params, mu, nu, loss, norms, rows, leaves = step(
            params, mu, nu, batch["tokens"], jnp.int32(i), flags)
        if fault == "state_unchanged":
            del params, mu, nu
            params = jax.device_put(start, device)
            mu, nu = zeros(params), zeros(params)
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

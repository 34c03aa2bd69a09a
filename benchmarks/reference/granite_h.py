"""Plain reference of the Granite-4.0-H decoder: the published equations in
`jax.numpy`, float32, `Precision.HIGHEST`. The Mamba-2 layer is the per-token
recurrence, attention a dense masked product; no kernels, no chunked scan;
nothing is imported from the program.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro config.json
(`model_type` `granitemoehybrid`, `num_local_experts` 0); `arch` holds its
numbers, plus the share held here: `vocab_size` rows of the vocabulary and
the first `num_hidden_layers` of `layer_types` (`arch`'s, else the published
`LAYER_TYPES`: the job's `arch` holds numbers only). x: (B, T, hidden);
`norm(x) = w * x * rsqrt(mean(x^2) + eps)`, eps `rms_norm_eps`; E the tied
table (vocab_size x hidden); m_e, m_r, m_a, s = `embedding_multiplier`,
`residual_multiplier`, `attention_multiplier`, `logits_scaling`:

  h = m_e E[tokens]
  every layer:  h = h + m_r mixer(norm_in(h))
                h = h + m_r (silu(u W_g) * (u W_u)) W_d,  u = norm_post(h),
                    [W_g | W_u] = input_linear (gate first)
  logits = (norm_f(h) / s) E^T;  the loss the mean next-token cross-entropy

  mamba (H heads of P, G groups of state N, conv of K taps, I = H P):
      [z | xBC | dt] = u W_in  (widths I | I + 2 G N | H)
      xBC = silu(b_conv + sum_j w_j xBC_{t-K+1+j}) = [x | B | C]
      dt = softplus(dt + dt_bias);  a_t = exp(-dt_t exp(A_log))
      S_t = a_t S_{t-1} + dt_t x_t B_t^T  (P x N a head, 0 at the start; head
            h reads group h // (H / G));  y_t = S_t C_t + D x_t
      out = ((y * silu(z)) normed over each of G groups of I / G columns,
            times w_norm) W_out
  attention: q = u Wq (Hq heads of hidden / Hq), k, v = u Wk, u Wv (Hkv
      heads), no positional encoding; softmax(m_a q k^T) v over keys s <= t,
      each key-value head serving Hq / Hkv query heads; out = . Wo

Departures, as in the program: no dropout, one document a sequence. The
recurrence is a `lax.scan` over the tokens, rematerialised a block of
`TOKEN_BLOCK` tokens at a time; attention and the loss a block of positions
at a time; each unit (mixer, MLP) rematerialised and run a
sequence at a time. The arithmetic is the plain one.

`q` is the control's switch (`reference/ouro.py` `quantize`): projection
operands and results, activations and the residual stream are held in `q`,
forward and cotangent; the float32 islands (softmax, norm statistics, the
recurrence's decay and state, the loss) stay float32. `fault` plants what
`correct` has to catch: "half_batch" (another shape), "head_untied" (the head
reads a copy of the table that only the head trains: another tree) and
`follow`'s "state_unchanged", and this family's switches of ONE compiled step
(`SWITCHED`, a traced vector): "residual_unscaled" (m_r 1),
"embedding_unscaled" (m_e 1), "attn_scale_sqrt_d" (head_dim^-1/2 for m_a),
"logits_unscaled" (s 1), "groups_as_8" (the gated norm over 8 groups),
"state_dropped_between_chunks" (the state restarts at every
`mamba_chunk_size`-th token), "d_skip_dropped" (no D x), "decay_ignored"
(a_t = 1). Named to `loss_and_count` a switch is a Python choice, so that the
sound jaxpr holds none of their arithmetic.
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

# published: attention at layers 5, 15, 25 and 35 of 40, Mamba-2 elsewhere
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))
TOKEN_BLOCK = 128    # tokens of the recurrence rematerialised together
TIME_STEP = (0.001, 0.1, 1e-4)   # assumed: dt_bias's rule (min, max, floor)
TABLE_STD = 0.1      # assumed: the Granite family's initializer_range
NORM_GROUPS_FAULT = 8

# the model's scopes as patterns over an op's name stack, for the job's
# table of a traced run (jobs/train_fit_lm.py)
MODEL_SCOPES = ("mamba/in_proj/", "mamba/conv/", "mamba/scan/", "mamba/out/",
                "attn/qkv/", "attn/core/", "attn/out/", "mlp/gate_up/",
                "mlp/down/", "lm_head/", "loss/")
FAULTS = ("residual_unscaled", "embedding_unscaled", "attn_scale_sqrt_d",
          "logits_unscaled", "groups_as_8", "state_dropped_between_chunks",
          "d_skip_dropped", "decay_ignored")
# what `--stand-in` may name for this family
STAND_INS = {
    "control": {"q": "control"},
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
    "head_untied": {"fault": "head_untied"},
    **{name: {"fault": name} for name in FAULTS},
}
# the faults one compiled step takes as traced switches (`_pick`)
SWITCHED = FAULTS
# leaves whose first gradient `follow` hands back whole, by the reading they
# feed (jobs/train_fit_lm.py `direction_gaps`): what a leaf's norm alone
# cannot tell apart, such as the table's two uses in another proportion
DIRECTION_LEAVES = {"grad_dir_gap_in_proj": "in_proj",
                    "grad_dir_gap_embed": "embed"}


def layer_types(arch):
    """The kinds of the layers run: the first `num_hidden_layers`."""
    return tuple(arch.get("layer_types", LAYER_TYPES))[:arch["num_hidden_layers"]]


def widths(arch):
    """(H, P, G, N, I, width of xBC)."""
    h, p = arch["mamba_n_heads"], arch["mamba_d_head"]
    g, n = arch["mamba_n_groups"], arch["mamba_d_state"]
    return h, p, g, n, h * p, h * p + 2 * g * n


def head_dim(arch):
    return arch["hidden_size"] // arch["num_attention_heads"]


# --- parameters (the program's tree: the one interface both sides share) ----

def init_params(arch, seed):
    """Seeded leaves under the program's paths: matrices N(0, 0.02), the tied
    table N(0, `TABLE_STD`) (m_e E is of about unit size; tied, the head
    reads each position's own token back, a logit near 25), norm scales 1; the conv's weights and
    bias U(-1/2, 1/2) (PyTorch's default at fan-in 4), A_log = log(1..H), D =
    1, dt_bias the Mamba-2 rule's (dt log-uniform over [0.001, 0.1], at least
    1e-4, stored as softplus's inverse). These are the BENCHMARK's weights
    (`give_weights` hands them to the program). `seed` may be traced."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   head_dim(arch))
    h, _p, _g, _n, inner, conv_dim = widths(arch)
    f, taps = arch["shared_intermediate_size"], arch["mamba_d_conv"]
    keys = iter(jax.random.split(jax.random.key(seed), 1024))

    def mat(*shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(bound, *shape):
        return jax.random.uniform(next(keys), shape, minval=-bound, maxval=bound)

    def scale(width=d):
        return {"scale": jnp.ones((width,))}

    lo, hi = math.log(TIME_STEP[0]), math.log(TIME_STEP[1])
    params = {"embed": mat(v, d, std=TABLE_STD), "final_norm": scale()}
    for i, kind in enumerate(layer_types(arch)):
        mixer = {"norm": scale()}
        if kind == "mamba":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(next(keys), (h,))
                                     * (hi - lo) + lo), TIME_STEP[2])
            mixer["mamba"] = {
                "in_proj": mat(d, inner + conv_dim + h),
                "conv": uniform(taps ** -0.5, taps, conv_dim),
                "conv_bias": uniform(taps ** -0.5, conv_dim),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
                "D": jnp.ones((h,)), "norm": jnp.ones((inner,)),
                "out_proj": mat(inner, d)}
        else:
            mixer["attn"] = {"q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                             "v_proj": mat(d, hkv * hd), "o_proj": mat(hq * hd, d)}
        params[f"layer_{i}"] = {
            "mixer": mixer,
            "ffn": {"norm": scale(),
                    "mlp": {"input_linear": mat(d, 2 * f),
                            "output_linear": mat(f, d)}}}
    return params


_INITS = {}


def init_variables(arch, seed):
    """{"params", "batch_stats"}, made in one jitted call that takes the seed
    as an argument, compiled once an `arch`: the same call gives the same
    leaves to the program and, later, to the reference, and a new seed
    compiles nothing."""
    key = json.dumps(arch, sort_keys=True)
    if key not in _INITS:
        _INITS[key] = jax.jit(lambda s: init_params(arch, s))
    return {"params": _INITS[key](jnp.uint32(int(seed) % (2 ** 31))),
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


def recurrence(x, dt, a, b, c, d, chunk, skip=False, on=None):
    """y (B, T, H, P) of the per-token recurrence; x (B, T, H, P), dt (B, T,
    H) after its softplus, a (H,) = -exp(A_log), b and c (B, T, G, N), d (H,).
    A scan over the tokens, `TOKEN_BLOCK` of them rematerialised together
    where there are more. `chunk`: where "state_dropped_between_chunks"
    restarts the state. `on`: the switched faults (`switches`)."""
    on = on or switches()
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    if skip:  # test hook: no product of the recurrence, every input read
        return x * (dt + a)[..., None] + jnp.repeat(
            (b + c).sum(axis=-1), r, axis=2)[..., None] + d[:, None] * x

    def token(state, xs):           # state (B, G, H/G, P, N)
        xt, dtt, bt, ct, start = xs  # (B, H, P), (B, H), (B, G, N) x2, ()
        dtt = dtt.reshape(batch, g, r)
        keep = _pick(on["decay_ignored"], lambda: jnp.ones_like(dtt),
                     lambda: jnp.exp(dtt * a.reshape(g, r)))
        keep = _pick(on["state_dropped_between_chunks"],
                     lambda: jnp.where(start, 0.0, keep), lambda: keep)
        state = state * keep[..., None, None] \
            + (dtt[..., None] * xt.reshape(batch, g, r, p))[..., None] \
            * bt[:, :, None, None, :]
        y = jnp.einsum("bgrpn,bgn->bgrp", state, ct, precision=HI)
        return state, y.reshape(batch, h, p)

    state0 = jnp.zeros((batch, g, r, p, n), jnp.float32)
    starts = jnp.arange(t) % chunk == 0
    xs = tuple(jnp.moveaxis(y, 1, 0) for y in (x, dt, b, c)) + (starts,)
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
    taps = arch["mamba_d_conv"]
    w = quantize(p["conv"], q)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = keep(jax.nn.silu(p["conv_bias"] + sum(
        padded[:, j:j + t] * w[j] for j in range(taps))))
    dt = jax.nn.softplus(step + p["dt_bias"])
    y = recurrence(xbc[..., :inner].reshape(batch, t, h, hp), dt,
                   -jnp.exp(p["A_log"]),
                   xbc[..., inner:inner + g * n].reshape(batch, t, g, n),
                   xbc[..., inner + g * n:].reshape(batch, t, g, n), p["D"],
                   arch["mamba_chunk_size"], skip, on)
    y = keep(y).reshape(batch, t, inner) * jax.nn.silu(z)
    eps = arch["rms_norm_eps"]

    def grouped(groups):
        v = y.reshape(batch, t, groups, inner // groups)
        v = v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        return v.reshape(batch, t, inner)

    y = _pick(on["groups_as_8"], lambda: grouped(NORM_GROUPS_FAULT),
              lambda: grouped(g))
    return _dot(keep(y * p["norm"]), p["out_proj"], q)


def attention(p, x, arch, q, remat, skip, on):
    keep = lambda y: quantize(y, q)  # noqa: E731
    b, t, _ = x.shape
    hq, hkv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  head_dim(arch))
    qh = _dot(x, p["q_proj"], q).reshape(b, t, hq, d)
    kh = _dot(x, p["k_proj"], q).reshape(b, t, hkv, d)
    vh = _dot(x, p["v_proj"], q).reshape(b, t, hkv, d)
    # the scale multiplies q (the same logits as scaling q k^T): a switch
    # of one compiled step picks it
    qh = qh * _pick(on["attn_scale_sqrt_d"], lambda: d ** -0.5,
                    lambda: arch["attention_multiplier"])
    o = keep(attention_core(qh, kh, vh, 1.0, None, remat, skip))
    return _dot(o.reshape(b, t, hq * d), p["o_proj"], q)


def mlp(p, x, arch, q):
    keep = lambda y: quantize(y, q)  # noqa: E731
    f = arch["shared_intermediate_size"]
    both = _dot(x, p["input_linear"], q)
    return _dot(keep(jax.nn.silu(both[..., :f]) * both[..., f:]),
                p["output_linear"], q)


def _per_sequence(unit, remat):
    """The unit rematerialised and run a sequence at a time: a unit's
    float32 activations of ONE sequence are what has to fit beside the AdamW
    state (tokens of different sequences never meet)."""
    if not remat:
        return unit
    one = jax.checkpoint(unit)

    def run(x, p):
        return lax.map(lambda xi: one(xi[None], p), x)[:, 0]
    return run


def trunk(params, tokens, arch, q=None, remat=True, skip=(), on=None):
    """Hidden states after the last norm (B, T, hidden). `on`: the switched
    faults (`switches`)."""
    on = on or switches()
    keep = lambda y: quantize(y, q)  # noqa: E731
    eps = arch["rms_norm_eps"]
    residual = _pick(on["residual_unscaled"], lambda: 1.0,
                     lambda: arch["residual_multiplier"])
    x = keep(jnp.take(params["embed"], tokens, axis=0) * _pick(
        on["embedding_unscaled"], lambda: 1.0,
        lambda: arch["embedding_multiplier"]))
    for i, kind in enumerate(layer_types(arch)):

        def mixer(x, p, kind=kind):
            if q is not None:
                # the control's rounded weights are made in the unit that
                # uses them, not all units' ahead of the first
                x, p = lax.optimization_barrier((x, p))
            u = keep(norm(x, p["norm"], eps))
            if kind == "mamba":
                y = mamba(p["mamba"], u, arch, q, "ssd_scan" in skip, on)
            else:
                y = attention(p["attn"], u, arch, q, remat, "attn_core" in skip,
                              on)
            return keep(x + residual * y)

        def ffn(x, p):
            if q is not None:
                x, p = lax.optimization_barrier((x, p))
            u = keep(norm(x, p["norm"], eps))
            return keep(x + residual * mlp(p["mlp"], u, arch, q))

        layer = params[f"layer_{i}"]
        x = _per_sequence(mixer, remat)(x, layer["mixer"])
        x = _per_sequence(ffn, remat)(x, layer["ffn"])
    return keep(norm(x, params["final_norm"], eps))


def logits(params, tokens, arch, **kw):
    """(B, T, vocab) float32: small sizes only."""
    x = trunk(params, tokens, arch, **kw)
    return jnp.dot(x / arch["logits_scaling"], params["embed"].T, precision=HI)


def loss_and_count(params, tokens, arch, q=None, remat=True, skip=(),
                   fault=None, flags=None):
    """Mean next-token cross-entropy (position t against token t + 1; the
    last position of a sequence against nothing) and the scored positions.
    `flags`: a traced vector over `SWITCHED` in the place of `fault`'s name."""
    if fault == "half_batch":
        tokens = (tokens[:tokens.shape[0] // 2] if tokens.shape[0] > 1
                  else tokens[:, :tokens.shape[1] // 2])
    on = switches(fault, flags)
    x = trunk(params, tokens, arch, q=q, remat=remat, skip=skip, on=on)
    x, targets = x[:, :-1], tokens[:, 1:]
    n = targets.size
    scale = _pick(on["logits_unscaled"], lambda: 1.0,
                  lambda: arch["logits_scaling"])
    x, targets = x.reshape(n, -1) / scale, targets.reshape(n)
    # the tied table, or under "head_untied" the copy only the head trains
    head = quantize(params["lm_head" if fault == "head_untied" else "embed"].T, q)

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
        return block(parts) / n, n
    total = lax.map(block, tuple(
        a.reshape((n + pad) // size, size, *a.shape[1:]) for a in parts)).sum()
    return total / n, n


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
    clipped gradient norms by leaf, the clipped gradient's
    `DIRECTION_LEAVES`), jitted once a variant: `flags` (over `SWITCHED`)
    picks a switched fault, so the sound step serves them all."""
    fault = None if fault in SWITCHED else fault
    key = json.dumps([arch, optim, q, fault], sort_keys=True)
    if key not in _STEPS:
        def step(params, mu, nu, tokens, count, flags):
            loss, grads = jax.value_and_grad(
                lambda p: loss_and_count(p, tokens, arch, q=q, fault=fault,
                                         flags=flags)[0])(params)
            params, mu, nu, grads = adamw_update(params, mu, nu, grads,
                                                 count.astype(jnp.float32), optim)
            return (params, mu, nu, loss, plain.leaf_norms(grads),
                    _direction_leaves(grads))

        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1, 2))
    return _STEPS[key]


@jax.jit
def _delta_norms(a, b):
    return plain.leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


def follow(arch, optim, params, batches, q=None, fault=None, note=None):
    """Drive the reference through `batches` ({"tokens"} each, on one device)
    from `params`. Returns what the other token references' `follow` returns
    (the losses, the first step's clipped gradient norms by leaf, the norms of
    the parameters' change over all the steps by leaf, the leaves' sizes, the
    (token, held expert) pairs of each step: 0, there are no experts) and
    `grad_leaves`: the first clipped gradient of the `DIRECTION_LEAVES`, on
    the host. `fault="state_unchanged"` keeps the first state through every
    step; "head_untied" starts the head's copy `lm_head` as the table."""
    step = make_step(arch, optim, q=q,
                     fault=None if fault == "state_unchanged" else fault)
    if fault == "head_untied":
        params = {**params, "lm_head": jnp.copy(params["embed"])}
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
        params, mu, nu, loss, norms, leaves = step(
            params, mu, nu, batch["tokens"], jnp.int32(i), flags)
        if fault == "state_unchanged":
            del params, mu, nu
            params = jax.device_put(start, device)
            mu, nu = zeros(params), zeros(params)
        losses.append(float(loss))
        pairs.append(0)
        if note:
            note(f"reference step {i + 1} done")
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms.items()}
            grad_leaves = jax.device_get(leaves)
        del leaves
    del mu, nu
    delta = _delta_norms(params, start)
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path): int(leaf.size)
             for path, leaf in jax.tree_util.tree_flatten_with_path(start)[0]}
    return {"losses": losses, "grad_norms": grad_norms, "sizes": sizes,
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "pairs": pairs, "grad_leaves": grad_leaves}

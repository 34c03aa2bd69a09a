"""Plain reference of the Ouro looped decoder: the equations below in
`jax.numpy`, float32, `Precision.HIGHEST`. A Python loop over the layers
inside one `lax.scan` over the passes, no kernels; nothing is imported from
the program.

Source: https://huggingface.co/ByteDance/Ouro-2.6B config.json (`model_type`
`ouro`; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741); `arch` holds its keys, `num_hidden_layers` and `vocab_size`
as held here, and the assumed `exit_entropy_beta`. x: (B, T, hidden);
`norm(x; w) = w * x * rsqrt(mean(x^2) + eps)`; no biases in a layer.

  layer_i(x):  h = x + norm_a2( attn( norm_a1(x) ) )
               y = h + norm_m2( mlp ( norm_m1(h) ) )
  attn:   q, k, v = n Wq, n Wk, n Wv (16 heads each, groups of one),
          rotate-half rotary over the whole head on q and k,
          softmax(q k^T / sqrt(head_dim)) v over the keys s <= t, out = . Wo
  mlp(u)  = (silu(u W_gate) * (u W_up)) W_down
  passes: x_0 = embed[tokens];  x_t = norm_f( layer_{L-1}( .. layer_0( x_{t-1} ) ) )
          for t = 1..P = total_ut_steps: the SAME layers and norm_f every pass
  heads:  logits_t = x_t W_head (untied);  lambda_t = sigmoid(x_t . w_exit + b_exit)
  exit:   p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < P,
          p_P = prod_{j<P} (1 - lambda_j)
  loss of a scored position (t against token t + 1; the last of a sequence
          against nothing): sum_t p_t CE(logits_t) - beta H(p), H(p) =
          -sum_t p_t log p_t; the mean over the scored positions.

Assumed (no key in config.json; the paper and the published `modeling_ouro.py`
are the ground): the four norms a layer, norm_f's output feeding the next
pass, one gate with a bias for all passes, the paper's stage-I objective with
a uniform prior, beta 0.1. Departures, as in the program: no stage-II gate
training, no early exit (`early_exit_threshold` is an inference rule), no
cache shared between passes, no dropout, one document a sequence.

Attention and the loss are taken a block of positions at a time (dense masked
products of a block of queries against ALL keys; a block of rows through the
head for one pass after another), and each layer EXECUTION is rematerialised,
so that 8 layers x 4 passes at 4,096 tokens fit beside the AdamW state; the
arithmetic is the dense one.

`q` is the control's switch (`quantize`, below), as in the other token
references: projection operands and results, activations and the residual
stream are held in `q`, forward and cotangent; the float32 islands (softmax,
norm statistics, the gate's sigmoid, the exit distribution, the loss) stay
float32. `fault` plants what `correct` has to catch: "half_batch" (half the
sequences; of a single sequence its first half), and the loop's own:
"one_pass" (`total_ut_steps` 1), "norm_once" (norm_f after the last pass
only: the earlier passes hand on, and give their heads, the raw stream),
"pre_norm_only" (norm_a2 and norm_m2 left out), "gate_ignored" (p_t = 1 / P),
"entropy_dropped" (beta 0), "last_loss_only" (the last pass's cross-entropy
alone); "state_unchanged" is `follow`'s.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from . import plain
from .qwen3_next import adamw_update
from .smallthinker import HI, attention_core, norm, rotate_half

ROW_BLOCK = 512      # loss positions computed together, a pass at a time

# the model's scopes as patterns over an op's name stack, for the job's
# table of a traced run (jobs/train_fit_lm.py)
MODEL_SCOPES = ("attn/qkv/", "attn/core/", "attn/out/", "mlp/gate_up/",
                "mlp/down/", "exit/gate/", "exit/pdf/", "lm_head/", "loss/")
# what `--stand-in` may name for this family
STAND_INS = {
    "control": {"q": "control"},
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
    "one_pass": {"fault": "one_pass"},
    "norm_once": {"fault": "norm_once"},
    "pre_norm_only": {"fault": "pre_norm_only"},
    "gate_ignored": {"fault": "gate_ignored"},
    "entropy_dropped": {"fault": "entropy_dropped"},
    "last_loss_only": {"fault": "last_loss_only"},
}
# leaves whose first gradient `follow` hands back whole, by the reading they
# feed (jobs/train_fit_lm.py `direction_gaps`): the gate's, and a projection
# every pass shares, whose gradient is the sum over the passes
DIRECTION_LEAVES = {"grad_dir_gap_exit_gate": "exit_gate",
                    "grad_dir_gap_k_proj": "k_proj"}
NORMS = ("input_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm")


# --- parameters (the program's tree: the one interface both sides share) ----

def init_params(arch, seed):
    """Seeded leaves under the program's paths: matrices and the gate's
    vector N(0, 0.02), the embedding N(0, 1), norm scales 1, the gate's bias
    0. These are the BENCHMARK's weights (`give_weights` hands them to the
    program, whose own initialiser keeps the embedding at 0.02 and is never run
    in a cell). Every sub-layer's output is normed to unit size before it
    joins the residual stream, and attention over thousands of random tokens
    gives nearly the same direction at every position: an embedding of 0.02
    would be a fiftieth of that and every position's stream one vector. A
    trained model's positions differ; unit-variance embeddings keep them so
    here. `seed` may be traced."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    f = arch["intermediate_size"]
    keys = iter(jax.random.split(jax.random.key(seed), 1024))

    def mat(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def scale():
        return {"scale": jnp.ones((d,))}

    stack = {"final_norm": scale()}
    for i in range(arch["num_hidden_layers"]):
        stack[f"layer_{i}"] = {
            **{name: scale() for name in NORMS},
            "attn": {"q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                     "v_proj": mat(d, hkv * hd), "o_proj": mat(hq * hd, d)},
            "mlp": {"gate_proj": mat(d, f), "up_proj": mat(d, f),
                    "down_proj": mat(f, d)}}
    return {"embed": mat(v, d) / 0.02, "lm_head": mat(d, v),
            "exit_gate": mat(d), "exit_bias": jnp.zeros(()), "stack": stack}


def init_variables(arch, seed):
    """{"params", "batch_stats"}, made in one jitted call that takes the seed
    as an argument: the same call gives the same leaves to the program and,
    later, to the reference, and a new seed compiles nothing."""
    make = jax.jit(lambda s: init_params(arch, s))
    return {"params": make(jnp.uint32(int(seed) % (2 ** 31))),
            "batch_stats": {}}


# --- the control's precision ----------------------------------------------------

def _round(x, q):
    """`plain._round`, an 8-bit value clipped to the format's range before the
    cast. max|x| over its own scale is the format's largest number to within
    a rounding, and float8_e4m3fn, which has no infinity, turns what lands
    above it into NaN on the chip: every other token cell's float8 control is
    non-finite there (PERF.md section 2)."""
    dt = jnp.dtype(q)
    if dt.itemsize > 1:
        return x.astype(dt).astype(jnp.float32)
    top = float(jnp.finfo(dt).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jnp.clip(x / scale, -top, top).astype(dt).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fake_quant(x, q):
    return _round(x, q)


_fake_quant.defvjp(lambda x, q: (_round(x, q), None),
                   lambda q, _, g: (_round(g, q),))


def quantize(x, q):
    """`plain.quantize` (identity for q=None; forward the value, backward the
    cotangent rounded to `q`, an 8-bit float under a per-tensor scale), with
    `_round`'s clip."""
    return x if q is None else _fake_quant(x, q)


def _dot(x, w, q):
    """x W with both operands and the result held as the policy holds them."""
    return quantize(jnp.dot(quantize(x, q), quantize(w, q), precision=HI), q)


# --- the layers ---------------------------------------------------------------

def attention(p, x, arch, q, remat, skip):
    keep = lambda t: quantize(t, q)  # noqa: E731
    b, t, _ = x.shape
    hq, hkv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    qh = _dot(x, p["q_proj"], q).reshape(b, t, hq, d)
    kh = _dot(x, p["k_proj"], q).reshape(b, t, hkv, d)
    vh = _dot(x, p["v_proj"], q).reshape(b, t, hkv, d)
    qh = keep(rotate_half(qh, arch["rope_theta"]))
    kh = keep(rotate_half(kh, arch["rope_theta"]))
    o = keep(attention_core(qh, kh, vh, d ** -0.5, None, remat, skip))
    return _dot(o.reshape(b, t, hq * d), p["o_proj"], q)


def mlp(p, x, q):
    hidden = quantize(
        jax.nn.silu(_dot(x, p["gate_proj"], q)) * _dot(x, p["up_proj"], q), q)
    return _dot(hidden, p["down_proj"], q)


def layer(p, x, arch, q=None, remat=True, skip=(), fault=None):
    """One layer execution: attention and MLP, each between two norms."""
    keep = lambda t: quantize(t, q)  # noqa: E731
    eps = arch["rms_norm_eps"]
    if q is not None:
        # the control's rounded copies of a layer's weights are made in the
        # layer that uses them, in every pass: the barrier keeps the compiler
        # from making all eight layers' once, ahead of the loop over the
        # passes, where they would not fit the chip beside the state (2.4 GB)
        x, p = lax.optimization_barrier((x, p))
    after = (lambda y, name: y) if fault == "pre_norm_only" else \
        (lambda y, name: keep(norm(y, p[name], eps)))
    a = attention(p["attn"], keep(norm(x, p["input_norm"], eps)), arch, q,
                  remat, "attn_core" in skip)
    h = keep(x + after(a, "attn_out_norm"))
    m = mlp(p["mlp"], keep(norm(h, p["mlp_norm"], eps)), q)
    return keep(h + after(m, "mlp_out_norm"))


def trunk(params, tokens, arch, q=None, remat=True, skip=(), fault=None):
    """(P, B, T, hidden): x_1 .. x_P, what every pass hands its head and its
    gate (and, normed, the next pass). With `remat` (the cell's size) the
    passes are one `lax.scan` of a Python loop over the layers, so that the
    compiled step holds the layers' code once and not P times (unrolled, the
    float8 control's step did not fit the chip beside its state: PERF.md
    section 6, PR 36); without, a Python loop over the passes too."""
    keep = lambda t: quantize(t, q)  # noqa: E731
    stack, eps = params["stack"], arch["rms_norm_eps"]
    run = lambda p, x: layer(p, x, arch, q, remat, skip, fault)  # noqa: E731
    if remat:
        run = jax.checkpoint(run)

    def one_pass(x, _):
        for i in range(arch["num_hidden_layers"]):
            x = run(stack[f"layer_{i}"], x)
        if fault != "norm_once":
            x = keep(norm(x, stack["final_norm"], eps))
        return x, x

    x = keep(jnp.take(params["embed"], tokens, axis=0))
    total = 1 if fault == "one_pass" else arch["total_ut_steps"]
    if remat:
        _, hidden = lax.scan(one_pass, x, None, length=total)
    else:  # small sizes: the passes one after another, no loop in the jaxpr
        hidden = []
        for _ in range(total):
            x, _ = one_pass(x, None)
            hidden.append(x)
        hidden = jnp.stack(hidden)
    if fault == "norm_once":   # norm_f after the last pass only
        hidden = hidden.at[-1].set(keep(norm(hidden[-1], stack["final_norm"], eps)))
    return hidden


def logits(params, tokens, arch, **kw):
    """The LAST pass's (B, T, vocab) float32: small sizes only."""
    return jnp.dot(trunk(params, tokens, arch, **kw)[-1], params["lm_head"],
                   precision=HI)


def exit_pdf(z):
    """p (P, N) from the gates' logits z (P, N): pass t is left with
    lambda_t of what the passes before it left; the last takes the rest."""
    lam = jax.nn.sigmoid(z)
    left, p = jnp.ones_like(z[0]), []
    for t in range(z.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def loss_and_ut(params, tokens, arch, q=None, remat=True, skip=(),
                fault=None):
    """The mean exit-weighted loss over the scored positions, and by pass the
    means of p_t and of CE_t ({"exit_mass": (P,), "loss": (P,)})."""
    if fault == "half_batch":
        tokens = (tokens[:tokens.shape[0] // 2] if tokens.shape[0] > 1
                  else tokens[:, :tokens.shape[1] // 2])
    hidden = trunk(params, tokens, arch, q=q, remat=remat, skip=skip,
                   fault=fault)
    targets = tokens[:, 1:].reshape(-1)
    n, total = targets.size, hidden.shape[0]
    x = hidden[:, :, :-1].reshape(total, n, -1)                    # (P, n, D)
    head = quantize(params["lm_head"], q)
    gate, bias = quantize(params["exit_gate"], q), params["exit_bias"]
    beta = 0.0 if fault == "entropy_dropped" else arch["exit_entropy_beta"]

    def block(xs):
        xb, yb, wb = xs            # (P, rows, D), (rows,), (rows,)
        ce = []
        for t in range(total):     # a pass's logits after another
            z = jnp.dot(xb[t], head, precision=HI)
            ce.append(jax.nn.logsumexp(z, axis=-1)
                      - jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0])
        ce = jnp.stack(ce)
        p = exit_pdf(quantize(jnp.dot(xb, gate, precision=HI), q) + bias)
        if fault == "gate_ignored":
            p = jnp.full_like(p, 1.0 / total)
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
        per = ce[-1] if fault == "last_loss_only" else \
            jnp.sum(p * ce, axis=0) - beta * entropy
        return jnp.sum(wb * per), jnp.sum(wb * p, axis=1), \
            jnp.sum(wb * ce, axis=1)

    if remat:
        block = jax.checkpoint(block)
    # a block of positions after another; the tail is padded with weight 0
    size = min(ROW_BLOCK, n)
    pad = -n % size
    parts = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))), jnp.pad(targets, (0, pad)),
             jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)))
    if n + pad == size:
        loss, mass, ce = block(parts)
    else:
        blocks = (n + pad) // size
        loss, mass, ce = (a.sum(axis=0) for a in lax.map(block, (
            jnp.moveaxis(parts[0].reshape(total, blocks, size, -1), 1, 0),
            parts[1].reshape(blocks, size), parts[2].reshape(blocks, size))))
    return loss / n, {"exit_mass": mass / n, "loss": ce / n}


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
    gradient norms by leaf, the clipped gradient's `DIRECTION_LEAVES`), jitted
    once a variant."""
    key = json.dumps([arch, optim, q, fault], sort_keys=True)
    if key not in _STEPS:
        def step(params, mu, nu, tokens, count):
            loss, grads = jax.value_and_grad(
                lambda p: loss_and_ut(p, tokens, arch, q=q, fault=fault)[0]
            )(params)
            params, mu, nu, grads = adamw_update(params, mu, nu, grads,
                                                 count.astype(jnp.float32), optim)
            return (params, mu, nu, loss, plain.leaf_norms(grads),
                    _direction_leaves(grads))

        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1, 2))
    return _STEPS[key]


def follow(arch, optim, params, batches, q=None, fault=None, note=None):
    """Drive the reference through `batches` ({"tokens"} each, on one device)
    from `params`. Returns what the other token references' `follow` returns
    (the losses, the first step's clipped gradient norms by leaf, the norms of
    the parameters' change over all the steps by leaf, the leaves' sizes;
    `pairs` is 0 a step: no expert, no pair) and `grad_leaves`: the first
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
    losses, grad_norms, grad_leaves = [], None, None
    for i, batch in enumerate(batches):
        params, mu, nu, loss, norms, leaves = step(
            params, mu, nu, batch["tokens"], jnp.int32(i))
        if fault == "state_unchanged":
            del params, mu, nu
            params = jax.device_put(start, device)
            mu, nu = zeros(params), zeros(params)
        losses.append(float(loss))
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
            "pairs": [0] * len(losses), "grad_leaves": grad_leaves}

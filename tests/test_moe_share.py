"""ops/moe.py: a share of a routed expert layer. The parts that all shares
give add up to the uncut layer; no (token, held expert) pair is dropped under
any skew; the router's gradient flows through the normalised weights."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.reference import qwen3_next as ref
from pytorchvideo_accelerate_tpu.ops import moe

N, D, F, E, K = 192, 16, 8, 16, 4
ARCH = {"num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": True}


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.key(0), 9)
    mat = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    return {"x": jax.random.normal(ks[0], (1, N, D)),
            "p": {"router": mat(ks[1], D, E), "w_gate": mat(ks[2], E, D, F),
                  "w_up": mat(ks[3], E, D, F), "w_down": mat(ks[4], E, F, D),
                  "shared_gate_proj": mat(ks[5], D, F),
                  "shared_up_proj": mat(ks[6], D, F),
                  "shared_down_proj": mat(ks[7], F, D),
                  "shared_expert_gate": mat(ks[8], D, 1)}}


def _share_params(p, offset, held):
    cut = {k: v[offset:offset + held] for k, v in p.items()
           if k in ("w_gate", "w_up", "w_down")}
    return {**p, **cut}


def _program_share(p, x, offset, held, experts=None):
    flat = x.reshape(-1, D)
    weights, chosen = moe.route(flat, p["router"], K)
    if experts is not None:
        chosen = experts
    return moe.expert_share(flat, weights, chosen, p["w_gate"][offset:offset + held],
                            p["w_up"][offset:offset + held],
                            p["w_down"][offset:offset + held], offset, E)


def _shared_part(p, x):
    flat = x.reshape(-1, D)
    hidden = jax.nn.silu(flat @ p["shared_gate_proj"]) * (flat @ p["shared_up_proj"])
    return (hidden @ p["shared_down_proj"]) * jax.nn.sigmoid(
        flat @ p["shared_expert_gate"])


def test_route_is_the_references_routing(layer):
    w, e = moe.route(layer["x"].reshape(-1, D), layer["p"]["router"], K)
    w_ref, e_ref = ref.routing(layer["p"], layer["x"].reshape(-1, D), ARCH)
    assert bool(jnp.all(e == e_ref))
    assert float(jnp.abs(w - w_ref).max()) < 1e-6
    assert float(jnp.abs(w.sum(-1) - 1.0).max()) < 1e-6  # norm_topk_prob


@pytest.mark.parametrize("shares", [4, 2, 1], ids=lambda s: f"{s}_shares")
def test_all_shares_add_up_to_the_uncut_reference_layer(layer, shares):
    """16 experts as `shares` shares: the routed parts of all shares, plus
    the shared expert counted ONCE, equal the reference's uncut layer (which
    adds the shared expert itself). float32 on the CPU: 1e-5 is summation
    order over 4 experts a token."""
    p, x = layer["p"], layer["x"]
    held = E // shares
    whole, rows_whole = ref.mixture(p, x, {**ARCH, "experts_held": E},
                                    None, False, False, None)
    total = _shared_part(p, x)
    rows = []
    for s in range(shares):
        y, r = _program_share(p, x, s * held, held)
        total = total + y
        rows.append(r)
        # and each share alone is the reference's same share
        y_ref, r_ref = ref.mixture(
            _share_params(p, s * held, held), x,
            {**ARCH, "experts_held": held, "expert_offset": s * held},
            None, False, False, None)
        assert float(jnp.abs(y + _shared_part(p, x)
                             - y_ref.reshape(-1, D)).max()) < 1e-5
        assert bool(jnp.all(r == r_ref))
    assert float(jnp.abs(total - whole.reshape(-1, D)).max()) < 1e-5
    assert bool(jnp.all(jnp.concatenate(rows) == rows_whole))
    assert int(jnp.concatenate(rows).sum()) == N * K  # every pair, once


@pytest.mark.parametrize("first,want_rows", [
    pytest.param(4, N, id="every_token_to_the_held_experts"),
    pytest.param(9, 0, id="no_token_to_the_held_experts"),
])
def test_planted_routers_lose_no_pair_and_give_finite_gradients(
        layer, first, want_rows):
    """A router that sends every token to experts first..first+3, against a
    share that holds experts 4..7: all N x 4 pairs are local (16 times the
    expected load: the chunked path) or none is."""
    p, x = layer["p"], layer["x"]
    planted = jnp.broadcast_to(first + jnp.arange(K)[None, :], (N, K))

    def run(x, w_gate):
        y, rows = _program_share({**p, "w_gate": w_gate}, x, 4, 4, planted)
        return jnp.sum(y ** 2), (y, rows)

    (_, (y, rows)), grads = jax.value_and_grad(run, argnums=(0, 1),
                                               has_aux=True)(x, p["w_gate"])
    assert rows.tolist() == [want_rows] * 4
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    flat = x.reshape(-1, D)
    weights, _ = moe.route(flat, p["router"], K)
    want = jnp.zeros_like(flat)
    if want_rows:
        for j in range(K):
            e = first + j
            want = want + weights[:, j:j + 1] * (
                (jax.nn.silu(flat @ p["w_gate"][e]) * (flat @ p["w_up"][e]))
                @ p["w_down"][e])
    assert float(jnp.abs(y - want).max()) < 1e-5
    if not want_rows:
        assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


def test_router_gradient_flows_through_the_normalised_weights(layer):
    """d loss / d router through weights = p_top / sum(p_top), program
    against reference (the top-k's choice itself has no gradient)."""
    p, x = layer["p"], layer["x"]

    def program(router):
        y, _ = _program_share({**p, "router": router}, x, 0, E)
        return jnp.sum((y + _shared_part(p, x)) ** 2)

    def reference(router):
        y, _ = ref.mixture({**p, "router": router}, x,
                           {**ARCH, "experts_held": E}, None, False, False, None)
        return jnp.sum(y ** 2)

    got, want = jax.grad(program)(p["router"]), jax.grad(reference)(p["router"])
    assert float(jnp.abs(want).max()) > 1e-3
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())


# --- ReGLU experts with no shared expert (benchmarks/reference/smallthinker.py)

def _reglu_arch(held, offset=0):
    return {"moe_num_primary_experts": E, "moe_num_active_primary_experts": K,
            "norm_topk_prob": True, "experts_held": held,
            "expert_offset": offset}


@pytest.mark.parametrize("shares", [4, 2, 1], ids=lambda s: f"{s}_shares")
def test_relu_shares_add_up_to_the_uncut_reference_layer(layer, shares):
    """The same 16 experts with `activation=relu` and no shared expert, as
    `shares` shares: their parts add up to the uncut ReGLU layer of the other
    family's reference, and each share alone is that reference's share."""
    from benchmarks.reference import smallthinker as st

    p, x = layer["p"], layer["x"]
    flat = x.reshape(-1, D)
    held = E // shares
    weights, chosen = moe.route(flat, p["router"], K)
    w_ref, e_ref = st.routing(p["router"], flat, _reglu_arch(E))
    assert bool(jnp.all(chosen == e_ref))
    whole, rows_whole = st.mixture(p, flat, w_ref, e_ref, _reglu_arch(E),
                                   None, False, False, None)
    total, rows = jnp.zeros_like(flat), []
    for s in range(shares):
        cut = slice(s * held, (s + 1) * held)
        y, r = moe.expert_share(flat, weights, chosen, p["w_gate"][cut],
                                p["w_up"][cut], p["w_down"][cut], s * held, E,
                                activation=jax.nn.relu)
        total = total + y
        rows.append(r)
        y_ref, r_ref = st.mixture(_share_params(p, s * held, held), flat,
                                  w_ref, e_ref, _reglu_arch(held, s * held),
                                  None, False, False, None)
        assert float(jnp.abs(y - y_ref).max()) < 1e-5
        assert bool(jnp.all(r == r_ref))
    assert float(jnp.abs(total - whole).max()) < 1e-5
    assert bool(jnp.all(jnp.concatenate(rows) == rows_whole))
    assert int(jnp.concatenate(rows).sum()) == N * K  # every pair, once
    # and the gate is the ReLU: the SiLU layer is another layer
    silu, _ = moe.expert_share(flat, weights, chosen, p["w_gate"], p["w_up"],
                               p["w_down"], 0, E)
    if shares == 1:
        assert float(jnp.abs(silu - whole).max()) > 1e-2

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
                            p["w_down"][offset:offset + held], offset, E)[:2]


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
        y, r, _ = moe.expert_share(flat, weights, chosen, p["w_gate"][cut],
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
    silu, _, _ = moe.expert_share(flat, weights, chosen, p["w_gate"],
                                  p["w_up"], p["w_down"], 0, E)
    if shares == 1:
        assert float(jnp.abs(silu - whole).max()) > 1e-2


# --- the row buffers a layer takes, by the step's own count (PR 37)

@pytest.mark.parametrize("geometry,rows", [
    pytest.param((16384, 6, 16, 64), 49152, id="smallthinker_cell"),
    pytest.param((16384, 10, 32, 512), 20480, id="qwen3_next_cell"),
    pytest.param((128, 2, 2, 8), 256, id="toy_worst_case_alone"),
])
def test_buffer_rows_at_the_cells_shapes(geometry, rows):
    """Twice the expected local pairs in whole tiles, at most the worst case:
    half of the worst case where a quarter of the experts is held (before PR
    37 the buffers were the worst case there, with no `cond`), an eighth of
    it at 32 of 512; a toy with a quarter held and 128 tokens reaches the
    worst case, and takes no `cond`."""
    assert moe.buffer_rows(*geometry) == rows


def _planted(local_counts, k, offset, held, num_experts):
    """(N, k) experts: token i chooses `local_counts[i]` held experts and the
    rest outside the share, all k distinct."""
    rows = []
    for i, n_local in enumerate(local_counts):
        outside = [e for e in range(num_experts)
                   if not offset <= e < offset + held]
        rows.append([offset + (i + j) % held for j in range(n_local)]
                    + [outside[(i + j) % len(outside)]
                       for j in range(k - n_local)])
    return jnp.asarray(rows, jnp.int32)


# (top-k, held, row tile, local pairs, tight buffers taken): a quarter of 16
# experts held, top-4, is the geometry that had no `cond` before (buffers of
# 512 rows, worst case 768: two chunks); 2 of 16 held, top-2, under a 16-row
# tile (buffers of 96, worst case 384: four chunks)
PATHS = [
    pytest.param(4, 4, 512, 512, True, id="quarter_held_at_the_tight_size"),
    pytest.param(4, 4, 512, 513, False, id="quarter_held_one_pair_past_it"),
    pytest.param(4, 4, 512, 768, False, id="quarter_held_worst_case"),
    pytest.param(2, 2, 16, 96, True, id="eighth_held_at_the_tight_size"),
    pytest.param(2, 2, 16, 97, False, id="eighth_held_one_pair_past_it"),
    pytest.param(2, 2, 16, 193, False, id="eighth_held_twice_the_tight_size"),
    pytest.param(2, 2, 16, 384, False, id="eighth_held_worst_case"),
]


@pytest.mark.parametrize("k,held,tile,local,tight", PATHS)
def test_both_ways_are_the_uncut_reference_layer(layer, monkeypatch, k, held,
                                                 tile, local, tight):
    """Routers planted so that a share's local pairs land at the tight
    buffers' size, one past it, or at the worst case: whichever way the count
    sends the layer (the tight buffers or the chunks, as its third output
    says), its y, rows and gradients (x, routing weights, the three expert
    matrices) are the plain reference's (`benchmarks/reference/
    smallthinker.py` `mixture`, a loop over the held experts)."""
    from benchmarks.reference import smallthinker as st

    monkeypatch.setattr(moe, "ROW_TILE", tile)
    p, flat = layer["p"], layer["x"].reshape(-1, D)
    offset = 4
    counts = [min(k, max(0, local - k * i)) for i in range(N)]
    experts = _planted(counts, k, offset, held, E)
    assert int(((experts >= offset) & (experts < offset + held)).sum()) == local
    weights, _ = moe.route(flat, p["router"], k)
    cut = {name: p[name][offset:offset + held]
           for name in ("w_gate", "w_up", "w_down")}
    arch = {**_reglu_arch(held, offset), "moe_num_active_primary_experts": k}

    def program(x, weights, mats):
        y, rows, took = moe.expert_share(x, weights, experts, mats["w_gate"],
                                         mats["w_up"], mats["w_down"], offset,
                                         E, activation=jax.nn.relu)
        return jnp.sum(jnp.sin(y)), (y, rows, took)

    def reference(x, weights, mats):
        y, rows = st.mixture(mats, x, weights, experts, arch, None, False,
                             False, None)
        return jnp.sum(jnp.sin(y)), (y, rows)

    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2),  # noqa: E731
                                        has_aux=True)(flat, weights, cut)
    (_, (y, rows, took)), g = grad(program)
    (_, (y_ref, rows_ref)), g_ref = grad(reference)
    assert bool(took) == tight
    assert rows.tolist() == rows_ref.tolist() and int(rows.sum()) == local
    assert float(jnp.abs(y - y_ref).max()) < 1e-5
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        scale = max(float(jnp.abs(want).max()), 1e-6)
        assert float(jnp.abs(got - want).max()) < 1e-5 * scale


@pytest.mark.parametrize("first_of,want", [
    # token i chooses experts 2i, 2i + 1 (mod 8): a quarter of its pairs local
    pytest.param(lambda i: (2 * i) % 8, 1.0, id="balanced_routing"),
    pytest.param(lambda i: 0 * i, 0.0, id="every_token_to_the_held_experts"),
])
def test_step_counts_the_layers_that_fit_the_tight_buffers(monkeypatch,
                                                           first_of, want):
    """`obs/moe_tight_buffer_share` through `make_lm_step` on the toy
    SmallThinker (8 experts, top-2, experts 0 and 1 held; 8 x 64 tokens:
    tight buffers of 512 rows, worst case 1024) under planted routers: every
    layer fits where the pairs spread over all experts, none where every
    token goes to the two held experts. A model without experts puts out no
    such share (tests/test_ouro.py `test_step_outputs_by_family`)."""
    import optax

    from pytorchvideo_accelerate_tpu.config import MeshConfig, ModelConfig
    from pytorchvideo_accelerate_tpu.models import create_model, smallthinker
    from pytorchvideo_accelerate_tpu.parallel.mesh import make_train_mesh
    from pytorchvideo_accelerate_tpu.trainer.steps import (lm_log_values,
                                                           make_lm_step)
    from pytorchvideo_accelerate_tpu.trainer.train_state import TrainState

    def planted(x, kernel, top_k, norm_topk=True):
        weights, _ = moe.route(x, kernel, top_k, norm_topk)
        first = first_of(jnp.arange(x.shape[0], dtype=jnp.int32))
        return weights, first[:, None] + jnp.arange(top_k, dtype=jnp.int32)

    monkeypatch.setattr(smallthinker, "route", planted)
    model = create_model(ModelConfig(name="smallthinker_t", experts_held=2),
                         "fp32")
    tx = optax.adamw(1e-3)
    mesh = make_train_mesh(MeshConfig(data=len(jax.devices())))
    tokens = jax.random.randint(jax.random.key(1), (8, 64), 0, 256)
    assert moe.buffer_rows(tokens.size, 2, 2, 8) == 512
    variables = model.init(jax.random.key(0), tokens[:1])
    state = TrainState.create(variables["params"], {}, tx)
    _, metrics = make_lm_step(model, tx, mesh)(state, {"tokens": tokens},
                                               jax.random.key(0))
    assert float(metrics["moe_tight_buffer_share"]) == want
    assert float(lm_log_values(metrics)["obs/moe_tight_buffer_share"]) == want

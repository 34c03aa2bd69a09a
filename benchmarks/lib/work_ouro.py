"""Work of one Ouro training step: FLOPs and least bytes by class.

From `arch` (the configuration's sizes) and the batch's shape alone, forward
and backward, recompute not counted; never from the program's jaxpr, XLA's
cost model or the trace. The layer stack, the head and the gate run
`total_ut_steps` times a step, so every class is counted that many times:

* `dot`: the attention projections q, k, v, o of every layer execution, the
  exit gate (hidden x 1) and the head of every pass, the last two on the
  scored positions, batch x (T - 1): one forward product and its two gradient
  products each, 2 M K N apiece, as `lib/flops.py` counts the reference's
  `dot_general`s (tests/benchmarks/test_pvabench_ouro.py holds `dot` + `mlp`
  equal to it at the toy size);
* `mlp`: the gated MLP's three products (gate, up, down) of every layer
  execution, counted the same way: a class of its own, because the model's
  time is there (`mlp_roofline`);
* `attn_core`: q k^T and p v, counted causal: T (T + 1) / 2 pairs a head a
  sequence, two products forward, four backward, groups of one.

Per contraction the least time on a chip is max(flops / peak, bytes / bw),
bytes = each operand read once and the result written once at the width the
configuration computes in (q k v o once a pass for an attention core). The
least times of a class add up. A weight shared by the passes is read in each.
"""

from __future__ import annotations

CLASSES = ("dot", "mlp", "attn_core")


def dots(arch, batch, seq):
    """{class: [(M, K, N)]} of every forward product of one step."""
    n, scored = batch * seq, batch * (seq - 1)
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    out = {"dot": [], "mlp": []}
    for _ in range(arch["total_ut_steps"]):
        for _ in range(arch["num_hidden_layers"]):
            out["dot"] += [(n, d, hq * hd), (n, d, hkv * hd), (n, d, hkv * hd),
                           (n, hq * hd, d)]
            out["mlp"] += [(n, d, f), (n, d, f), (n, f, d)]
        out["dot"] += [(scored, d, 1), (scored, d, arch["vocab_size"])]
    return out


def _add(acc, flops, nbytes, peaks):
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    acc["flops"] += flops
    acc["bytes"] += nbytes
    acc["least_s"] += max(t_flops, t_bytes)
    acc["n"] += 1
    acc["memory_bound"] += t_bytes > t_flops


def step_work(arch, batch, seq, routed_rows, peaks, bytes_per_element=2):
    """{"flops_per_step", "by_class"} in the shape `metrics/step_mfu.py` and
    `lib/roofline.py` read. `routed_rows` is the token jobs' argument for a
    mixture's pairs: there are none here, and it is not read."""
    del routed_rows
    by = {c: {"flops": 0.0, "bytes": 0.0, "least_s": 0.0, "n": 0,
              "memory_bound": 0} for c in CLASSES}
    bpe = bytes_per_element
    for name, products in dots(arch, batch, seq).items():
        for m, k, n in products:
            for _ in range(3):  # forward, data gradient, weight gradient
                _add(by[name], 2.0 * m * k * n, (m * k + k * n + m * n) * bpe,
                     peaks)
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    qkvo = batch * seq * (2 * hq + 2 * hkv) * hd * bpe
    work = batch * hq * (seq * (seq + 1) / 2.0) * 2.0 * hd   # one product
    for _ in range(arch["total_ut_steps"] * arch["num_hidden_layers"]):
        _add(by["attn_core"], 2 * work, qkvo, peaks)
        _add(by["attn_core"], 4 * work, 2.0 * qkvo, peaks)
    return {"flops_per_step": sum(c["flops"] for c in by.values()),
            "by_class": by}

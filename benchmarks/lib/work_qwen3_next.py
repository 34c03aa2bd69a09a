"""Work of one Qwen3-Next training step: FLOPs and least bytes by class.

From `arch` (the configuration's sizes) and the batch's shape alone, forward
and backward, recompute not counted; never from the program's jaxpr, XLA's
cost model or the trace. The classes:

* `dot`: every projection (DeltaNet in/out, attention q/k/v/o, router,
  shared expert and its gate, head): one forward product and its two
  gradient products each, 2 M K N apiece, as `lib/flops.py` counts the
  reference's `dot_general`s (tests/benchmarks/test_pvabench_tokens.py
  holds the two equal at the toy size). The head runs on the scored
  positions, batch x (T - 1);
* `gdn_scan`: the per-token recurrence of every DeltaNet head: decay,
  S^T k, the rank-one update, S^T q: 7 dk dv a token forward, twice that
  backward. The chunked form the program runs does more arithmetic (products
  inside a chunk); that surplus is the program's, not work;
* `attn_core`: q k^T and p v, counted causal: T (T + 1) / 2 pairs a head a
  sequence, two products forward, four backward;
* `moe_experts`: the three grouped products at the rows the reference's
  routing sent to the held experts (`routed_rows`, pairs a step, all
  layers), forward and two gradient products each.

Per contraction the least time on a chip is max(flops / peak, bytes / bw),
bytes = each operand read once and the result written once at the width the
configuration computes in; an expert's weights are read once a pass. The
least times of a class add up.
"""

from __future__ import annotations

CLASSES = ("dot", "gdn_scan", "attn_core", "moe_experts")


def layer_kinds(arch):
    period = arch["full_attention_interval"]
    return ["full_attention" if (i + 1) % period == 0 else "linear_attention"
            for i in range(arch["num_hidden_layers"])]


def dots(arch, batch, seq):
    """[(M, K, N)] of every projection's forward product."""
    n = batch * seq
    d = arch["hidden_size"]
    kdim = arch["linear_num_key_heads"] * arch["linear_key_head_dim"]
    hv = arch["linear_num_value_heads"]
    vdim = hv * arch["linear_value_head_dim"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    fs = arch["shared_expert_intermediate_size"]
    out = []
    for kind in layer_kinds(arch):
        if kind == "full_attention":
            out += [(n, d, hq * 2 * hd), (n, d, hkv * hd), (n, d, hkv * hd),
                    (n, hq * hd, d)]
        else:
            out += [(n, d, 2 * kdim + 2 * vdim), (n, d, 2 * hv), (n, vdim, d)]
        out += [(n, d, arch["num_experts"]), (n, d, fs), (n, d, fs),
                (n, fs, d), (n, d, 1)]
    out.append((batch * (seq - 1), d, arch["vocab_size"]))
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
    `lib/roofline.py` read. `routed_rows`: (token, held expert) pairs of one
    step, summed over the layers (the reference's routing)."""
    by = {c: {"flops": 0.0, "bytes": 0.0, "least_s": 0.0, "n": 0,
              "memory_bound": 0} for c in CLASSES}
    bpe = bytes_per_element
    for m, k, n in dots(arch, batch, seq):
        for _ in range(3):  # forward, data gradient, weight gradient
            _add(by["dot"], 2.0 * m * k * n, (m * k + k * n + m * n) * bpe, peaks)
    kinds = layer_kinds(arch)
    tokens = batch * seq
    # DeltaNet recurrence: one scan a layer forward, one backward
    hv, dk, dv = (arch["linear_num_value_heads"], arch["linear_key_head_dim"],
                  arch["linear_value_head_dim"])
    per_pass = tokens * hv * 7.0 * dk * dv
    io = tokens * hv * (2 * dk + 2 * dv + 2) * bpe     # q k v o g beta
    for _ in range(kinds.count("linear_attention")):
        _add(by["gdn_scan"], per_pass, io, peaks)
        _add(by["gdn_scan"], 2.0 * per_pass, 2.0 * io, peaks)
    # attention core, causal
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    pairs = batch * hq * seq * (seq + 1) / 2.0
    qkvo = tokens * (2 * hq + 2 * hkv) * hd * bpe
    for _ in range(kinds.count("full_attention")):
        _add(by["attn_core"], 2 * 2.0 * pairs * hd, qkvo, peaks)
        _add(by["attn_core"], 4 * 2.0 * pairs * hd, 2.0 * qkvo, peaks)
    # the held experts' grouped products
    d, f = arch["hidden_size"], arch["moe_intermediate_size"]
    held = arch.get("experts_held") or arch["num_experts"]
    rows = routed_rows / max(len(kinds), 1)
    for _ in kinds:
        for k, n in ((d, f), (d, f), (f, d)):
            for _ in range(3):
                _add(by["moe_experts"], 2.0 * rows * k * n,
                     (rows * k + held * k * n + rows * n) * bpe, peaks)
    return {"flops_per_step": sum(c["flops"] for c in by.values()),
            "by_class": by}

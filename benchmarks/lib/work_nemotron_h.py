"""Work of one Nemotron-H training step: FLOPs and least bytes by class.

From `arch` (the configuration's sizes; the layer kinds are the published
pattern's, `reference/nemotron_h.py` `pattern`) and the batch's shape alone,
forward and backward, recompute not counted; never from the program's jaxpr,
XLA's cost model or the trace. The classes:

* `dot`: every projection (the Mamba-2 mixers' W_in and W_out, the routers,
  the shared experts' two products, attention q/k/v/o, the head): one forward
  product and its two gradient products each, 2 M K N apiece, as
  `lib/flops.py` counts the reference's `dot_general`s
  (tests/benchmarks/test_pvabench_nemotron_h.py holds the two equal at the
  toy size). The head runs on the scored positions, batch x (T - 1);
* `ssd_scan`: the Mamba-2 recurrence, the per-token form's 5 P N a head a
  token forward (decay the state, add dt x B^T, read S C) and twice that
  backward; least bytes x, B, C, dt and y once each forward, twice backward.
  The count is the algorithm's: the chunked form's extra products earn
  nothing;
* `attn_core`: q k^T and p v, counted causal: T (T + 1) / 2 pairs a head a
  sequence, two products forward, four backward;
* `moe_experts`: the two grouped products of each routed expert at the rows
  the reference's routing sent to the held experts (`routed_rows`, pairs a
  step, all mixture layers), forward and two gradient products each.

Per contraction the least time on a chip is max(flops / peak, bytes / bw),
bytes = each operand read once and the result written once at the width the
configuration computes in (q k v o once a pass for an attention core); an
expert's weights are read once a pass. The least times of a class add up.
"""

from __future__ import annotations

from benchmarks.reference.nemotron_h import pattern, widths

CLASSES = ("dot", "ssd_scan", "attn_core", "moe_experts")


def dots(arch, batch, seq):
    """[(M, K, N)] of every projection's forward product."""
    n = batch * seq
    d = arch["hidden_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    h, _p, _g, _n, inner, conv_dim = widths(arch)
    fs = arch["moe_shared_expert_intermediate_size"]
    out = []
    for kind in pattern(arch):
        if kind == "M":
            out += [(n, d, inner + conv_dim + h), (n, inner, d)]
        elif kind == "E":
            out += [(n, d, arch["n_routed_experts"]), (n, d, fs), (n, fs, d)]
        else:
            out += [(n, d, hq * hd), (n, d, hkv * hd), (n, d, hkv * hd),
                    (n, hq * hd, d)]
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
    step, summed over the mixture layers (the reference's routing)."""
    by = {c: {"flops": 0.0, "bytes": 0.0, "least_s": 0.0, "n": 0,
              "memory_bound": 0} for c in CLASSES}
    bpe = bytes_per_element
    for m, k, n in dots(arch, batch, seq):
        for _ in range(3):  # forward, data gradient, weight gradient
            _add(by["dot"], 2.0 * m * k * n, (m * k + k * n + m * n) * bpe, peaks)
    kinds = pattern(arch)
    tokens = batch * seq
    # the recurrence: 5 P N a head a token forward, twice that backward
    h, p, g, n, inner, _ = widths(arch)
    scan_flops = 5.0 * tokens * h * p * n
    scan_bytes = tokens * (2 * inner + 2 * g * n + h) * bpe   # x, y, B, C, dt
    for _ in range(kinds.count("M")):
        _add(by["ssd_scan"], scan_flops, scan_bytes, peaks)
        _add(by["ssd_scan"], 2 * scan_flops, 2 * scan_bytes, peaks)
    # attention cores, causal
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    qkvo = tokens * (2 * hq + 2 * hkv) * hd * bpe
    work = batch * hq * (seq * (seq + 1) / 2.0) * 2.0 * hd   # one product
    for _ in range(kinds.count("*")):
        _add(by["attn_core"], 2 * work, qkvo, peaks)
        _add(by["attn_core"], 4 * work, 2.0 * qkvo, peaks)
    # the held experts' grouped products: two an expert
    d, f = arch["hidden_size"], arch["moe_intermediate_size"]
    held = arch.get("experts_held") or arch["n_routed_experts"]
    mixtures = kinds.count("E")
    rows = routed_rows / max(mixtures, 1)
    for _ in range(mixtures):
        for k, n_out in ((d, f), (f, d)):
            for _ in range(3):
                _add(by["moe_experts"], 2.0 * rows * k * n_out,
                     (rows * k + held * k * n_out + rows * n_out) * bpe, peaks)
    return {"flops_per_step": sum(c["flops"] for c in by.values()),
            "by_class": by}

"""Work of one SmallThinker training step: FLOPs and least bytes by class.

From `arch` (the configuration's sizes and layouts) and the batch's shape
alone, forward and backward, recompute not counted; never from the program's
jaxpr, XLA's cost model or the trace. The classes:

* `dot`: every projection (attention q/k/v/o, router, head): one forward
  product and its two gradient products each, 2 M K N apiece, as
  `lib/flops.py` counts the reference's `dot_general`s
  (tests/benchmarks/test_pvabench_lm.py holds the two equal at the toy size).
  The head runs on the scored positions, batch x (T - 1);
* `attn_core`: q k^T and p v of the FULL layers, counted causal:
  T (T + 1) / 2 pairs a head a sequence, two products forward, four backward;
* `attn_window`: the same of the WINDOWED layers, counted under the band:
  W (W + 1) / 2 + (T - W) W pairs a head a sequence for T > W, else causal.
  The count is the algorithm's: a lowering that computes pairs outside the
  band gets no credit for them;
* `moe_experts`: the three grouped products at the rows the reference's
  routing sent to the held experts (`routed_rows`, pairs a step, all
  layers), forward and two gradient products each.

Per contraction the least time on a chip is max(flops / peak, bytes / bw),
bytes = each operand read once and the result written once at the width the
configuration computes in (q k v o once a pass for an attention core); an
expert's weights are read once a pass. The least times of a class add up.
"""

from __future__ import annotations

CLASSES = ("dot", "attn_core", "attn_window", "moe_experts")


def windows(arch):
    """The band of each layer run: tokens, or None where it reads all keys."""
    return [arch["sliding_window_size"] if arch["sliding_window_layout"][i]
            else None for i in range(arch["num_hidden_layers"])]


def pairs(seq, window=None):
    """(query, key) pairs a head a sequence: causal, or under the band."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def dots(arch, batch, seq):
    """[(M, K, N)] of every projection's forward product."""
    n = batch * seq
    d = arch["hidden_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    out = []
    for _ in range(arch["num_hidden_layers"]):
        out += [(n, d, hq * hd), (n, d, hkv * hd), (n, d, hkv * hd),
                (n, hq * hd, d), (n, d, arch["moe_num_primary_experts"])]
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
    # attention cores: causal in the full layers, under the band in the others
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    qkvo = batch * seq * (2 * hq + 2 * hkv) * hd * bpe
    layers = windows(arch)
    for window in layers:
        acc = by["attn_core" if window is None else "attn_window"]
        work = batch * hq * pairs(seq, window) * 2.0 * hd   # one product
        _add(acc, 2 * work, qkvo, peaks)
        _add(acc, 4 * work, 2.0 * qkvo, peaks)
    # the held experts' grouped products
    d, f = arch["hidden_size"], arch["moe_ffn_hidden_size"]
    held = arch.get("experts_held") or arch["moe_num_primary_experts"]
    rows = routed_rows / max(len(layers), 1)
    for _ in layers:
        for k, n in ((d, f), (d, f), (f, d)):
            for _ in range(3):
                _add(by["moe_experts"], 2.0 * rows * k * n,
                     (rows * k + held * k * n + rows * n) * bpe, peaks)
    return {"flops_per_step": sum(c["flops"] for c in by.values()),
            "by_class": by}

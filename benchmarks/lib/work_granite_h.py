"""Work of one Granite-4.0-H training step: FLOPs and least bytes by class.

From `arch` (the configuration's sizes; the layer kinds are the published
`layer_types`, `reference/granite_h.py` `layer_types`) and the batch's shape
alone, forward and backward, recompute not counted; never from the program's
jaxpr, XLA's cost model or the trace. The classes:

* `dot`: the Mamba-2 mixers' W_in and W_out, attention q/k/v/o and the tied
  head (on the scored positions, batch x (T - 1)): one forward product and
  its two gradient products each, 2 M K N apiece, as `lib/flops.py` counts
  the reference's `dot_general`s (tests/benchmarks/test_pvabench_granite_h.py
  holds `dot` + `mlp` equal to it at the toy size);
* `mlp`: the SwiGLU MLP's two products a layer, `input_linear` (both
  halves: K = hidden, N = 2 x shared_intermediate_size) and
  `output_linear`, counted the same way: a class of its own, as Ouro's
  (`mlp_roofline`);
* `ssd_scan`: the Mamba-2 recurrence, the per-token form's 5 P N a head a
  token forward (decay the state, add dt x B^T, read S C) and twice that
  backward; least bytes x, B, C, dt and y once each forward, twice backward.
  The count is the algorithm's: the chunked form's extra products earn
  nothing, and one group of B and C (G = 1) is read once for all heads;
* `attn_core`: q k^T and p v, counted causal: T (T + 1) / 2 pairs a head a
  sequence, two products forward, four backward.

Per contraction the least time on a chip is max(flops / peak, bytes / bw),
bytes = each operand read once and the result written once at the width the
configuration computes in (q k v o once a pass for an attention core). The
least times of a class add up.
"""

from __future__ import annotations

from benchmarks.reference.granite_h import head_dim, layer_types, widths

CLASSES = ("dot", "mlp", "ssd_scan", "attn_core")


def dots(arch, batch, seq):
    """{class: [(M, K, N)]} of every forward product of one step."""
    n = batch * seq
    d, f = arch["hidden_size"], arch["shared_intermediate_size"]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   head_dim(arch))
    h, _p, _g, _n, inner, conv_dim = widths(arch)
    out = {"dot": [], "mlp": []}
    for kind in layer_types(arch):
        if kind == "mamba":
            out["dot"] += [(n, d, inner + conv_dim + h), (n, inner, d)]
        else:
            out["dot"] += [(n, d, hq * hd), (n, d, hkv * hd), (n, d, hkv * hd),
                           (n, hq * hd, d)]
        out["mlp"] += [(n, d, 2 * f), (n, f, d)]
    out["dot"].append((batch * (seq - 1), d, arch["vocab_size"]))
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
    kinds = layer_types(arch)
    tokens = batch * seq
    # the recurrence: 5 P N a head a token forward, twice that backward
    h, p, g, n, inner, _ = widths(arch)
    scan_flops = 5.0 * tokens * h * p * n
    scan_bytes = tokens * (2 * inner + 2 * g * n + h) * bpe   # x, y, B, C, dt
    for _ in range(kinds.count("mamba")):
        _add(by["ssd_scan"], scan_flops, scan_bytes, peaks)
        _add(by["ssd_scan"], 2 * scan_flops, 2 * scan_bytes, peaks)
    # attention cores, causal
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   head_dim(arch))
    qkvo = tokens * (2 * hq + 2 * hkv) * hd * bpe
    work = batch * hq * (seq * (seq + 1) / 2.0) * 2.0 * hd   # one product
    for _ in range(kinds.count("attention")):
        _add(by["attn_core"], 2 * work, qkvo, peaks)
        _add(by["attn_core"], 4 * work, 2.0 * qkvo, peaks)
    return {"flops_per_step": sum(c["flops"] for c in by.values()),
            "by_class": by}

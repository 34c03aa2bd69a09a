#!/usr/bin/env python
"""Does the system still start, compile and run on the chip?

    python chip_smoke.py               one TPU chip: device, kernels, train, serve
    python chip_smoke.py --four-chip   four chips: one slowfast_r50 train step on
                                       a data=4 mesh against the same step on one
                                       chip, and nothing else
    ... --rehearse                     the same control flow at toy sizes, for a
                                       CPU run before chip time is spent; it lifts
                                       the TPU requirement and says so in its
                                       last line

One process holds the chip from start to finish: the trainer runs through
`pytorchvideo_accelerate_tpu.run.main(argv)` and the server through
`serving.server.build_server(cfg)`, both here, and no child process is
started. Every phase prints its wall seconds on a line of its own; a phase
that fails raises, and the script then prints `{"ok": false, ...}` as its
last line and exits 1. The last line of a good run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Times, bytes and compile seconds on earlier lines are facts of this run,
not benchmark metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# tolerances of the interpret-mode tests (tests/test_pallas_attention.py
# test_bf16_in_bf16_out_f32_accumulate, tests/test_zkernels.py
# test_fused_matches_unfused_under_bf16_policy), taken against the largest
# reference magnitude so small gradients are not compared with a fixed atol
KERNEL_TOL = 2e-2
# __graft_entry__.py phase 1: sharded step vs the same step on one device
FOUR_CHIP_LOSS_RTOL = 2e-2
FOUR_CHIP_GNORM_RTOL = 1e-1


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounters:
    """Compile seconds and persistent-cache traffic, from jax.monitoring
    (jax counts a "miss" when it writes an entry, i.e. for compiles of a
    second or more)."""

    def __init__(self):
        import jax

        self.compiles = []  # (seconds, jitted function), cache reads included
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((secs, fun_name))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (len(self.compiles), self.hits, self.misses)

    def since(self, snap) -> str:
        n, h, m = snap
        new = sorted(self.compiles[n:], reverse=True)
        slowest = ", ".join(f"{name} {secs:.1f}s" for secs, name in new[:5])
        return (f"compile_s={sum(s for s, _ in new):.1f} "
                f"cache_hits={self.hits - h} cache_written={self.misses - m} "
                f"slowest: {slowest}")


@contextlib.contextmanager
def phase(name: str, counters: CompileCounters):
    snap = counters.snapshot()
    t0 = time.perf_counter()
    yield
    print(f"[{name}] {counters.since(snap)}")
    print(f"[{name}] wall_s={time.perf_counter() - t0:.1f}", flush=True)


# --- kernels ----------------------------------------------------------------


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def kernels_phase(rehearse: bool) -> None:
    """Each of the `pallas_call`s in ops/, compiled for the chip at a
    BASELINE-width shape in the models' compute dtype (bf16), against its
    float32 reference on the same values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorchvideo_accelerate_tpu.ops import kbench_refs as refs
    from pytorchvideo_accelerate_tpu.ops import pallas_fused as pf
    from pytorchvideo_accelerate_tpu.ops import attention, gated_delta
    from pytorchvideo_accelerate_tpu.ops.attention import dense_attention
    from pytorchvideo_accelerate_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from pytorchvideo_accelerate_tpu.ops.pallas_depthwise import (
        pallas_depthwise3d_s1,
    )

    # on the chip the kernels are compiled, said explicitly; the rehearsal
    # leaves the choice to the kernels' own default (interpreted off-TPU)
    interpret = None if rehearse else False
    rng = np.random.default_rng(0)

    def arr(shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    def report(name, shape, err):
        print(f"[kernels] {name} {tuple(shape)}: max_rel_err={err:.2e} "
              f"(tol {KERNEL_TOL:.0e})")
        check(err <= KERNEL_TOL,
              f"kernel {name} differs from its reference: {err:.3e}")

    def reference(fn, *xs):
        """`fn` on float32 copies, with true-f32 matmuls on the chip."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*(x.astype(jnp.float32) for x in xs))

    # videomae_b: 12 heads x 64, 8x14x14 = 1568 tokens
    qkv_shape = (1, 40, 2, 16) if rehearse else (2, 1568, 12, 64)
    q, k, v, g = (arr(qkv_shape) for _ in range(4))
    g32 = g.astype(jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, interpret=interpret)

    report("attention_fwd", qkv_shape,
           _rel_err(jax.jit(flash)(q, k, v),
                    reference(dense_attention, q, k, v)))
    dq, dk, dv = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash(q, k, v).astype(jnp.float32) * g32),
        argnums=(0, 1, 2)))(q, k, v)
    rq, rk, rv = reference(jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v) * g32),
        argnums=(0, 1, 2)), q, k, v)
    report("attention_bwd_dq", qkv_shape, _rel_err(dq, rq))
    report("attention_bwd_dkv", qkv_shape,
           max(_rel_err(dk, rk), _rel_err(dv, rv)))

    def affine(c):
        return (jnp.asarray(1.0 + 0.1 * rng.standard_normal(c), jnp.float32),
                jnp.asarray(0.1 * rng.standard_normal(c), jnp.float32))

    def fused(name, fn, ref, act, x, w):
        s, b = affine(w.shape[-1])
        got = jax.jit(lambda x, w: fn(
            x, w, s, b, act=act, mode="pallas", interpret=interpret))(x, w)
        want = reference(lambda x, w: ref(x, w, s, b, act=act), x, w)
        report(name, x.shape, _rel_err(got, want))

    # x3d_s res3: 13 frames, 20x20, 48 -> inner 108
    b, t, hw, cin, c = (1, 4, 6, 8, 12) if rehearse else (2, 13, 20, 48, 108)
    fused("fused_pointwise", pf.fused_pointwise_bn_act, refs.ref_pw_bn_act,
          "relu", arr((b, t, hw, hw, cin)), arr((1, 1, 1, cin, c), 0.1))
    fused("fused_depthwise", pf.fused_depthwise_bn_act, refs.ref_dw_bn_act,
          "silu", arr((b, t, hw, hw, c)), arr((3, 3, 3, 1, c), 0.1))
    x, kern = arr((b, t, hw, hw, c)), arr((3, 3, 3, 1, c), 0.1)
    ones, zeros = jnp.ones((c,), jnp.float32), jnp.zeros((c,), jnp.float32)
    report("depthwise", x.shape, _rel_err(
        jax.jit(lambda x, k: pallas_depthwise3d_s1(x, k, interpret))(x, kern),
        reference(lambda x, k: refs.ref_dw_bn_act(
            x, k, ones, zeros, act="identity"), x, kern)))
    # slowfast_r50 slow res4 conv_b: 8 frames, 16x16, 256 -> 256
    b, t, hw, c = (1, 2, 6, 8) if rehearse else (2, 8, 16, 256)
    fused("fused_conv133", pf.fused_conv3d_bn_act, refs.ref_conv_bn_act,
          "relu", arr((b, t, hw, hw, c)), arr((1, 3, 3, c, c), 0.05))
    # qwen3_next_80b_a3b's DeltaNet heads (2 value heads a key head, 128
    # wide) over 1000 tokens: the chunked rule as the backend and the shapes
    # choose it (on the chip the kernel pair of ops/pallas_gated_delta.py,
    # in the rehearsal the XLA form) against the per-token recurrence
    b, t, hk, hv, d = (1, 40, 1, 2, 16) if rehearse else (2, 1000, 4, 8, 128)
    q, k = (arr((b, t, hk, d)) for _ in range(2))
    q, k = (x / jnp.linalg.norm(x.astype(jnp.float32), axis=-1,
                                keepdims=True).astype(x.dtype) for x in (q, k))
    v, g = arr((b, t, hv, d)), arr((b, t, hv, d))
    decay = -jnp.asarray(rng.uniform(0.01, 0.5, (b, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (b, t, hv)), jnp.float32)

    def read_out(fn):
        return lambda *xs: jnp.sum(
            fn(*xs)[0].astype(jnp.float32) * g.astype(jnp.float32))

    with gated_delta.count_sites() as sites:
        got = jax.jit(lambda *xs: gated_delta.gated_delta_rule(*xs)[0])(
            q, k, v, decay, beta)
        grads = jax.jit(jax.grad(read_out(gated_delta.gated_delta_rule),
                                 argnums=(0, 1, 2, 3, 4)))(q, k, v, decay, beta)
    check(len(sites) == (0 if rehearse else 2),
          f"gated_delta_rule took the kernel at {len(sites)} of 2 traces")
    want = reference(lambda *xs: gated_delta.gated_delta_recurrence(*xs)[0],
                     q, k, v, decay, beta)
    want_grads = reference(jax.grad(
        read_out(gated_delta.gated_delta_recurrence),
        argnums=(0, 1, 2, 3, 4)), q, k, v, decay, beta)
    report("gated_delta_fwd", v.shape, _rel_err(got, want))
    report("gated_delta_bwd", v.shape, max(
        _rel_err(a, w) for a, w in zip(grads, want_grads)))
    # the token cells' attention cores: qwen3_next_80b_a3b.train_8k's (groups
    # of 8, heads of 256), smallthinker_21b_a3b.train_16k's under its band
    # (groups of 7, heads of 128) and ouro_2_6b.train_4k's (groups of ONE,
    # heads of 128, 4096 tokens), as the backend and the shapes choose the
    # lowering (on the chip the flash kernels of ops/pallas_attention.py, in
    # the rehearsal the XLA form) against the XLA form in float32
    cases = [("causal_attention", (1, 48, 4, 2, 16) if rehearse
              else (2, 8192, 16, 2, 256), None),
             ("window_attention", (1, 48, 7, 1, 16) if rehearse
              else (1, 16384, 28, 4, 128), 20 if rehearse else 4096),
             ("causal_attention_g1", (1, 48, 4, 4, 16) if rehearse
              else (1, 4096, 16, 16, 128), None)]
    with attention.count_kernel_sites() as sites:
        for name, (b, t, hq, hkv, d), window in cases:
            q, g = arr((b, t, hq, d)), arr((b, t, hq, d))
            k, v = arr((b, t, hkv, d)), arr((b, t, hkv, d))

            def read_out(fn):
                return lambda *xs: jnp.sum(
                    fn(*xs).astype(jnp.float32) * g.astype(jnp.float32))

            def core(*xs):
                return attention.causal_gqa_attention(*xs, window=window)

            def xla_form(*xs):
                return attention.blocked_causal_attention(
                    *xs, d ** -0.5, 512, window)

            if window is None:
                report(f"{name}_fwd", q.shape, _rel_err(
                    jax.jit(core)(q, k, v), reference(xla_form, q, k, v)))
            grads = jax.jit(jax.grad(read_out(core), argnums=(0, 1, 2)))(
                q, k, v)
            want_grads = reference(jax.grad(read_out(xla_form),
                                            argnums=(0, 1, 2)), q, k, v)
            report(f"{name}_bwd", q.shape, max(
                _rel_err(a, w) for a, w in zip(grads, want_grads)))
    check(len(sites) == (0 if rehearse else 5),
          f"causal_gqa_attention took the kernels at {len(sites)} of 5 traces")


# --- train ------------------------------------------------------------------


def _train_argv(work: str, rehearse: bool) -> list:
    """The reference recipe's geometry (scripts/run_slowfast_r50.sh,
    benchmarks/configs/slowfast_r50.json): slowfast_r50, 32 frames, 256^2, batch 8, alpha 4,
    bf16, 700 classes — on synthetic clips, for a handful of steps. The
    learning rate is a tenth of the recipe's 0.1, at which a from-scratch
    network on random clips leaves 7.6 for the hundreds within two steps."""
    frames, crop, batch, videos = (8, 64, 2, 16) if rehearse else (32, 256,
                                                                   8, 64)
    return [
        "--synthetic", "--data.synthetic_num_videos", str(videos),
        "--model.name", "slowfast_r50", "--model.num_classes", "700",
        "--slowfast_alpha", "4", "--mixed_precision", "bf16",
        "--num_frames", str(frames), "--data.crop_size", str(crop),
        "--data.min_short_side_scale", str(crop),
        "--data.max_short_side_scale", str(crop * 5 // 4),
        "--batch_size", str(batch), "--num_workers", "4", "--lr", "0.01",
        "--checkpointing_steps", "epoch",
        "--output_dir", os.path.join(work, "out"),
        "--logging_dir", os.path.join(work, "runs"),
    ]


def train_phase(work: str, rehearse: bool) -> str:
    """CLI path: train, validate, checkpoint; then resume and export."""
    import math

    import jax

    from pytorchvideo_accelerate_tpu import run

    steps_per_epoch, epochs = 4, 2
    base = _train_argv(work, rehearse)
    result = run.main(base + [
        "--num_epochs", str(epochs),
        "--limit_train_batches", str(steps_per_epoch),
        "--limit_val_batches", "1",
        "--with_tracking", "--trackers", "jsonl", "--log_every", "1",
    ])
    total = steps_per_epoch * epochs
    check(result["steps"] == total,
          f"trainer took {result['steps']} optimizer steps, expected {total}")
    check(result["train_recompiles"] == 0,
          f"train step recompiled after its first step: "
          f"train_recompiles={result['train_recompiles']}")
    check(math.isfinite(result["train_loss"]), "non-finite epoch train loss")
    check(math.isfinite(result["val_accuracy"]), "non-finite val accuracy")

    # per-step record the trainer itself wrote (trainer/tracking.py jsonl)
    (log,) = glob.glob(os.path.join(work, "runs", "*.jsonl"))
    with open(log) as f:
        rows = [row for row in map(json.loads, f) if "train_loss_step" in row]
    check(len(rows) == total,
          f"tracker logged {len(rows)} train steps, expected {total}")
    for row in rows:
        check(math.isfinite(row["train_loss_step"])
              and math.isfinite(row["grad_norm"])
              and row["obs/nonfinite"] == 0.0,
              f"non-finite training step: {row}")
        # steps.py health gauge: |delta params| / |params| of this update
        check(row["obs/update_ratio"] > 0.0,
              f"parameters did not move at step {row['step']}")
    print("[train] loss by step: "
          + " ".join(f"{r['train_loss_step']:.4f}" for r in rows))
    # epoch 0 pays the compiles; epoch 1 is steady state, closed by the
    # trainer's own value fetch of the last loss (bench_setup.fetch_loss)
    first, steady = result["epoch_train_times"]
    print(f"[train] first epoch {first:.1f}s incl. compile; steady "
          f"{steady / steps_per_epoch:.4f} s/step over {steps_per_epoch} "
          f"steps, closed by a value fetch, of which waiting for input "
          f"{result['input_wait_frac']:.2f}")
    stats = jax.devices()[0].memory_stats()
    print(f"[train] peak_bytes_in_use="
          f"{stats['peak_bytes_in_use'] if stats else 'not reported'}")

    artifact = os.path.join(work, "artifact")
    exported = run.main(base + ["--resume_from_checkpoint", "auto",
                                "--export_inference", artifact])
    check(exported == {"exported": artifact}, f"export returned {exported}")
    with open(os.path.join(artifact, "meta.json")) as f:
        meta = json.load(f)
    # a fresh Trainer starts at step 0: the artifact carries the trained
    # step only if the checkpoint restored
    check(meta["step"] == total,
          f"checkpoint did not restore: exported step {meta['step']}, "
          f"trained {total}")
    print(f"[train] checkpoint restored at step {meta['step']}, exported "
          f"{artifact}")
    return artifact


# --- serve ------------------------------------------------------------------


def _http(url: str, body=None, timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def serve_phase(artifact: str, device: dict) -> None:
    import numpy as np

    from pytorchvideo_accelerate_tpu.config import parse_cli
    from pytorchvideo_accelerate_tpu.serving.server import build_server

    cfg = parse_cli(["--serve.checkpoint", artifact, "--serve.port", "0",
                     "--serve.max_batch_size", "2",
                     "--output_dir", os.path.dirname(artifact)])
    server = build_server(cfg).start()
    try:
        host, port = server.address
        url = f"http://{host}:{port}"
        status, health = _http(url + "/healthz")
        check(status == 200 and health["status"] == "healthy",
              f"/healthz: {status} {health}")
        check((health["platform"], health["device_kind"],
               health["device_count"])
              == (device["platform"], device["kind"], device["count"]),
              f"/healthz reports another device than JAX: {health}")
        rng = np.random.default_rng(0)
        for i in range(3):
            # small integers keep a 32x256x256x3 clip's JSON body short
            clip = {k: rng.integers(-3, 4, shape).astype(np.float32)
                    for k, shape in health["clip_spec"].items()}
            status, reply = _http(
                url + "/predict", {k: v.tolist() for k, v in clip.items()})
            check(status == 200, f"/predict {i}: {status} {reply}")
            logits = np.asarray(reply["logits"], np.float32)
            check(logits.shape == (health["num_classes"],)
                  and bool(np.all(np.isfinite(logits))),
                  f"/predict {i}: bad logits {logits.shape}")
            direct = server.engine.predict(
                {k: v[None] for k, v in clip.items()})[0]
            check(reply["top1"] == int(np.argmax(direct))
                  and np.allclose(logits, direct, rtol=1e-5, atol=1e-5),
                  f"/predict {i}: top1 {reply['top1']} / logits differ from "
                  f"the direct engine call (top1 {int(np.argmax(direct))})")
            print(f"[serve] /predict {i}: 200 top1={reply['top1']} "
                  f"== engine, server latency_ms={reply['latency_ms']}")
        status, stats = _http(url + "/stats")
        check(status == 200 and stats["requests"] == 3,
              f"/stats: {status} {stats}")
    finally:
        server.close()
    try:
        _http(url + "/healthz", timeout=5.0)
    except (urllib.error.URLError, ConnectionError):
        print("[serve] clean shutdown: port closed")
    else:
        raise SmokeFailure("server still answers after close()")


# --- four chips -------------------------------------------------------------


def four_chip_phase(rehearse: bool) -> None:
    """One slowfast_r50 train step, global batch 8, on the trainer's data=4
    mesh against the same batch and seed on one chip."""
    import jax
    import numpy as np

    from pytorchvideo_accelerate_tpu.utils.bench_setup import (
        build_step_setup,
        fetch_loss,
    )

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chip needs 4 devices, JAX found "
                             f"{len(devices)}")
    frames, crop = (8, 64) if rehearse else (32, 256)

    def one_step(devs):
        setup = build_step_setup(
            "slowfast_r50", frames=frames, crop=crop, batch_per_chip=0,
            global_batch=8, num_classes=700, alpha=4, devices=devs)
        batch = setup.device_batch(0)
        shards = {s.device for s in batch["fast"].addressable_shards}
        rows = {s.data.shape[0] for s in batch["fast"].addressable_shards}
        state, metrics = setup.step(setup.state, batch, jax.random.key(0))
        loss = fetch_loss(metrics)
        gnorm = float(np.asarray(metrics["grad_norm"]))
        in_use = [d.memory_stats() for d in devs]
        return loss, gnorm, shards, rows, in_use

    loss4, gnorm4, shards, rows, in_use = one_step(devices)
    gc.collect()  # the four-chip state and batch are unreachable now
    check(shards == set(devices) and rows == {2},
          f"batch of 8 is not split 2 rows to each of 4 devices: devices "
          f"{sorted(d.id for d in shards)}, rows per shard {sorted(rows)}")
    if all(s is None for s in in_use):
        check(rehearse, "devices report no memory_stats()")
        print("[four-chip] bytes_in_use: not reported by this backend")
    else:
        used = [s["bytes_in_use"] for s in in_use]
        print(f"[four-chip] bytes_in_use per device after the step: {used}")
        check(all(u > 0 for u in used),
              f"a device holds nothing after the sharded step: {used}")
    loss1, gnorm1, *_ = one_step(devices[:1])
    print(f"[four-chip] data=4: loss={loss4:.6f} grad_norm={gnorm4:.6f}; "
          f"one chip: loss={loss1:.6f} grad_norm={gnorm1:.6f}")
    for name, a, b, rtol in (("loss", loss4, loss1, FOUR_CHIP_LOSS_RTOL),
                             ("grad_norm", gnorm4, gnorm1,
                              FOUR_CHIP_GNORM_RTOL)):
        check(np.isfinite(a) and np.isfinite(b), f"non-finite {name}")
        check(abs(a - b) <= rtol * abs(b),
              f"{name} on four chips {a} vs one chip {b}: off by more "
              f"than rtol {rtol}")


# --- main -------------------------------------------------------------------


def run_smoke(args) -> dict:
    import jax

    from pytorchvideo_accelerate_tpu import native
    from pytorchvideo_accelerate_tpu.utils.compile_cache import (
        cache_entries,
        enable_compile_cache,
    )
    from pytorchvideo_accelerate_tpu.utils.hw import device_summary

    t0 = time.perf_counter()
    device = device_summary()
    print(f"[device] {json.dumps(device)} jax={jax.__version__}")
    if device["platform"] != "tpu" and not args.rehearse:
        raise SmokeFailure(
            f"JAX found no TPU (platform {device['platform']!r})")
    print(f"[device] wall_s={time.perf_counter() - t0:.1f}", flush=True)

    counters = CompileCounters()
    cache_dir = enable_compile_cache()
    before = cache_entries(cache_dir)
    print(f"[cache] dir={cache_dir} entries_before={before}")
    print("[native] loader library: "
          + ("built from native/pva_native.cpp" if native.load() is not None
             else "not built, pure-Python fallback"))

    if args.four_chip:
        with phase("four-chip", counters):
            four_chip_phase(args.rehearse)
    else:
        with tempfile.TemporaryDirectory(prefix="pva_chip_smoke_") as work:
            with phase("kernels", counters):
                kernels_phase(args.rehearse)
            with phase("train", counters):
                artifact = train_phase(work, args.rehearse)
            gc.collect()
            with phase("serve", counters):
                serve_phase(artifact, device)
    print(f"[cache] dir={cache_dir} entries_before={before} "
          f"entries_after={cache_entries(cache_dir)} "
          f"hits={counters.hits} written={counters.misses}")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="only the data=4 train step and its one-chip twin")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, TPU not required (CPU rehearsal)")
    args = ap.parse_args(argv)
    try:
        device = run_smoke(args)
    except BaseException as e:  # noqa: BLE001 - every failure ends the run
        import traceback

        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:500]}))
        return 1
    last = {"ok": True, "device": device}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # loader and HTTP worker threads must not outlive the verdict
    os._exit(code)

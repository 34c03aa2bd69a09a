"""StreamingEngine: device-resident rolling-window rings + incremental
advance steps, compiled once per (bucket, stride, geometry).

The recompute this eliminates (docs/SERVING.md § streaming): one-shot
clip classification re-ships and re-embeds the whole ``(T, H, W, C)``
window per emitted label, so a live stream scored at stride *s* pays
``T/s``x redundant H2D and patch-embed work. Here a session's window
lives ON DEVICE in a slot of a pre-allocated ring pool; an advance ships
only the *s* new frames, writes them into the ring in place (jitted,
pool donated — graphcheck-style zero double-buffering), and re-scores
the cached window.

Ring families, chosen by the served model:

- **frame ring** (conv families — tiny3d/x3d/resnet/csn/r2plus1d/c2d):
  the ring holds raw frames in the engine's input dtype; the advance
  saves H2D + host staging and the full trunk re-runs over the cached
  window (3-D convs mix time globally — no exact partial re-use seam).
- **token ring** (`VideoMAEClassifier`): the cube embedding is a VALID
  conv with kernel == stride, so each tubelet's token depends only on
  its own pixels — the ring caches PRE-positional patch tokens per
  temporal slot, the advance embeds just the new frames, and the trunk
  runs over cached tokens (positional embeddings are added at trunk
  time in window order, so the rotating ring start is invisible to the
  model). A raw-frame ring is kept alongside as the weight-independent
  carry substrate: across a blue/green hot-swap the green engine
  re-embeds every live ring from raw frames under ITS weights at
  cutover (`carry_state_from`, compiled in advance by
  `prepare_carry_from`), so cached tokens can never go stale against
  swapped weights.
- **KV rings** (`VideoMAEClassifier` + ``trunk="causal"|"windowed"``,
  docs/SERVING.md § trunk-reuse): beyond the embed, the TRUNK itself is
  reused. The served backbone runs a banded temporal attention mask
  (0 <= q_slot - k_slot < W; models/videomae.py `attn_mask`), under
  which each slot's per-layer K/V and final hidden state is a pure
  function of its trailing window — so they are cacheable. Per-layer
  K/V rings and a per-slot hidden ring ride alongside the raw/token
  rings; the advance embeds the new tubelets, attends ONLY their
  queries against the cached window K/V (the band is computed on
  ABSOLUTE slot indices from a traced position counter, so ring
  wraparound can never alias a future slot), writes the new K/V/hidden
  back, and reads the label from the hidden ring — O(s·T) attention
  instead of O(T^2) trunk recompute, zero steady-state recompiles.
  Positional codes are RING-SLOT-stable ((abs_slot mod T')·hw +
  spatial), which at establish coincides with ordinary window order.
  ``trunk="full"`` (the default) is byte-for-byte today's token-ring
  graph. With ``serve.quantization=int8`` the K/V rings are stored
  int8 with per-token-row scales (serving/quantize.quantize_kv).
- **stem ring** (`MViT`): a true token seam for the overlapping
  (3,7,7)/(2,4,4) patch stem — its temporal receptive field is one
  frame of left halo, which the raw ring supplies. The advance writes
  the new frames, gathers the halo frame from the ring, runs the stem
  conv VALID-in-time over [halo, new frames], caches the resulting
  pre-positional stem-token slots, and re-enters the trunk via
  ``MViT.apply(..., from_stem=True)`` (learned pos_embed added at
  trunk time in window order). Steady-state advances see the REAL
  halo frame where one-shot `predict` zero-pads the window edge, so
  the parity oracle is `full_recompute_history` (replay over the
  whole stream), not the one-shot window. causal/windowed trunks are
  refused for MViT: its pooling attention mixes time through (3,·,·)
  conv kernels at every stage — there is no causal KV seam.
- **dual-rate rings** (`SlowFast`): two coupled rings — the fast ring
  holds every frame, the slow ring every alpha-th. Validation pins
  ``stride % alpha == 0`` so both rings advance in lock-step and the
  slow window is always the phase-0 subsample ``window[::alpha]`` of
  the fast window (slide-stable under streaming; this is the serving
  convention — PackPathway's truncated-linspace train-time sampling
  does not slide). Both rings are raw frames, hence weight-independent
  and adopted as-is across a hot-swap.

Parity contract: the incremental logits match `InferenceEngine.predict`
over the assembled host window (`full_recompute`) for the exact-window
families (frames / tokens-full / dual), and match the masked replay
over the whole stream history (`full_recompute_history`) for the
KV-trunk and stem families — gated in tests/test_zstream.py +
tests/test_zkvcache.py.

Compile discipline: advance/establish functions are jitted per
(kind, geometry, stride, bucket) and cached forever; session slots,
write offsets and the KV position counter are TRACED arguments, so
steady-state streaming touches zero new executables
(`compiled_stream_cache_sizes` is the RecompileGuard-style probe
tests/test_zstream.py and tests/test_zkvcache.py assert flat).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from pytorchvideo_accelerate_tpu.obs import memory as obs_memory
from pytorchvideo_accelerate_tpu.streaming.session import (
    SessionAdmissionError,
    SessionError,
    SessionTable,
    SessionUnknownError,
)
from pytorchvideo_accelerate_tpu.utils.logging import get_logger
from pytorchvideo_accelerate_tpu.utils.sync import make_lock, shared_state

logger = get_logger("pva_tpu")

# compiled stream-executable bound, same rationale as the engine's
# MAX_COMPILED_KEYS: every (geometry, stride, bucket) costs a synchronous
# compile + permanent executable memory
MAX_STREAM_KEYS = 64

TRUNK_MODES = ("full", "causal", "windowed")


def _np_dtype(name: str):
    return np.dtype(name)


@shared_state("_pools", "_fns", "_committed", benign={
    "_tok_meta": "written once at construction, read-only afterwards"})
class StreamingEngine:
    """Session-stateful wrapper around one `InferenceEngine`.

    Presents the engine surface the scheduler/hot-swap stack already
    speaks (`predict`/`buckets`/`warmup`/`compiled_keys` delegate to the
    wrapped engine) plus the session surface (`advance_batch`,
    `end_session`, `carry_state_from`). `supports_sessions` is the
    capability flag the scheduler/server check before routing session
    traffic."""

    supports_sessions = True

    def __init__(self, engine, *, session_budget_mb: float = 256.0,
                 session_ttl_s: float = 120.0, retry_after_s: float = 1.0,
                 registry=None, name: str = "stream",
                 trunk: str = "full", attn_window: int = 0):
        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.models import VideoMAEClassifier
        from pytorchvideo_accelerate_tpu.models.mvit import MViT
        from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast

        self.engine = engine
        self.name = name
        self.session_budget_bytes = int(session_budget_mb * 1e6)
        self.table = SessionTable(ttl_s=session_ttl_s,
                                  retry_after_s=retry_after_s,
                                  registry=registry, name=name)
        self._lock = make_lock("StreamingEngine._lock")
        # pool_key -> {ring name: device array, "cap": int, "bytes": int}
        self._pools: Dict[tuple, Dict[str, Any]] = {}
        self._committed = 0  # declared ring-pool bytes against the budget
        # MemoryLedger component for this engine's ring pools
        # (docs/OBSERVABILITY.md § memory ledger)
        self._mem_component = f"stream_rings:{name}"
        self._fns: Dict[tuple, Any] = {}  # (op, kind, geom, stride, bucket)
        model = engine.model
        if isinstance(model, VideoMAEClassifier):
            self.kind = "tokens"
            tt, p, _ = model.tubelet
            self._tok_meta = {"tt": int(tt), "p": int(p),
                              "dim": int(model.dim),
                              "dtype": model.dtype}
        elif isinstance(model, MViT):
            self.kind = "stem"
            kt = int(model.patch_kernel[0])
            if kt % 2 == 0:
                raise SessionError(
                    "stem-ring streaming needs an odd temporal patch "
                    f"kernel (halo = kt//2 frames each side); got {kt}")
            self._tok_meta = {"ts": int(model.patch_stride[0]),
                              "halo": kt // 2,
                              "kernel": tuple(int(k) for k in model.patch_kernel),
                              "stride_sp": tuple(int(s) for s in model.patch_stride),
                              "dim": int(model.embed_dim),
                              "dtype": model.dtype}
        elif isinstance(model, SlowFast) \
                or engine.model_name.startswith("slowfast"):
            self.kind = "dual"
            self._tok_meta = {"alpha": int(getattr(model, "alpha", 4))}
        else:
            self.kind = "frames"
            self._tok_meta = None

        self.trunk = str(trunk)
        self.attn_window = int(attn_window)
        self._kv_meta: Optional[dict] = None
        if self.trunk not in TRUNK_MODES:
            raise SessionError(
                f"unknown stream trunk {trunk!r}; expected one of "
                f"{TRUNK_MODES} (serve.stream_trunk)")
        if self.trunk != "full":
            if self.kind != "tokens":
                reason = {
                    "stem": "MViT's pooling attention mixes time through "
                            "(3,·,·) conv kernels at every stage — there "
                            "is no causal KV seam",
                    "dual": "slowfast's lateral time-strided fusion convs "
                            "mix time globally",
                    "frames": "3-D conv trunks mix time globally",
                }[self.kind]
                raise SessionError(
                    f"stream trunk {self.trunk!r} needs a "
                    "VideoMAEClassifier token seam; "
                    f"{engine.model_name!r} does not have one ({reason}) "
                    "— serve stream_trunk=full "
                    "(docs/SERVING.md § trunk-reuse)")
            if model.attention_backend != "dense":
                raise SessionError(
                    f"stream trunk {self.trunk!r} runs banded-mask "
                    "attention, which only the 'dense' backend lowers "
                    f"(model.attention={model.attention_backend!r}) — "
                    "see ops/attention.dot_product_attention")
            if self.trunk == "windowed" and self.attn_window < 1:
                # default the band width from the served model's own
                # finetune knob (the recipe: finetune with
                # --model.attn_mask windowed --model.attn_window W, then
                # serve --serve.stream_trunk windowed)
                self.attn_window = int(getattr(model, "attn_window", 0))
            if self.trunk == "windowed" and self.attn_window < 1:
                raise SessionError(
                    "stream trunk 'windowed' needs attn_window >= 1 "
                    "(temporal slots; pass attn_window= or serve a model "
                    "finetuned with --model.attn_window)")
            self._kv_meta = {"depth": int(model.depth),
                             "heads": int(model.num_heads)}

        names = ["raw"]
        if self.kind == "tokens":
            names.append("tok")
            if self.trunk != "full":
                names.append("kv")
                if self.quantization == "int8":
                    names.append("kv_scale")
                names.append("hid")
        elif self.kind == "stem":
            names.append("stem")
        elif self.kind == "dual":
            names.append("slow")
        self._ring_names = tuple(names)
        self._jnp = jnp

    # --- delegated engine surface ----------------------------------------

    @property
    def buckets(self):
        return self.engine.buckets

    @property
    def mesh(self):
        return self.engine.mesh

    @property
    def model(self):
        return self.engine.model

    @property
    def model_name(self):
        return self.engine.model_name

    @property
    def num_classes(self):
        return self.engine.num_classes

    @property
    def input_dtype(self):
        return self.engine.input_dtype

    @property
    def stats(self):
        return self.engine.stats

    @property
    def quantization(self):
        return getattr(self.engine, "quantization", "off")

    @property
    def compiled_keys(self):
        return self.engine.compiled_keys

    def bucket_for(self, n: int) -> int:
        return self.engine.bucket_for(n)

    def predict(self, batch):
        return self.engine.predict(batch)

    def warmup(self, sample_clip) -> None:
        self.engine.warmup(sample_clip)

    # --- geometry ---------------------------------------------------------

    @staticmethod
    def geom_key(window: int, h: int, w: int, c: int, dtype: str) -> tuple:
        return (int(window), int(h), int(w), int(c), str(dtype))

    def _band_width(self, geom: tuple) -> int:
        """Temporal band width W in token slots: T' for causal (plain
        causality), the model's attn_window for windowed."""
        m = self._tok_meta
        return (geom[0] // m["tt"]) if self.trunk == "causal" \
            else self.attn_window

    def _stem_hw(self, geom: tuple) -> tuple:
        """Stem-token spatial grid (h', w') for one geometry — the SAME
        padded-conv arithmetic the model's patch_embed performs."""
        m = self._tok_meta
        _, kh, kw = m["kernel"]
        _, sh, sw = m["stride_sp"]
        _, h, w, _, _ = geom
        hh = (h + 2 * (kh // 2) - kh) // sh + 1
        ww = (w + 2 * (kw // 2) - kw) // sw + 1
        return hh, ww

    def ring_bytes(self, geom: tuple) -> int:
        """Device bytes ONE session's ring(s) cost — the unit of the HBM
        session budget."""
        t, h, w, c, dtype = geom
        total = t * h * w * c * _np_dtype(dtype).itemsize
        if self.kind == "tokens":
            m = self._tok_meta
            itemsize = np.dtype(
                self._jnp.zeros((), m["dtype"]).dtype).itemsize
            tn = t // m["tt"]
            hw = (h // m["p"]) * (w // m["p"])
            total += tn * hw * m["dim"] * itemsize
            if self.trunk != "full":
                kv_elems = self._kv_meta["depth"] * 2 * tn * hw
                if self.quantization == "int8":
                    total += kv_elems * m["dim"] + kv_elems * 4  # q8 + scale
                else:
                    total += kv_elems * m["dim"] * itemsize
                total += tn * m["dim"] * itemsize  # hidden ring
        elif self.kind == "stem":
            m = self._tok_meta
            itemsize = np.dtype(
                self._jnp.zeros((), m["dtype"]).dtype).itemsize
            hh, ww = self._stem_hw(geom)
            total += (t // m["ts"]) * hh * ww * m["dim"] * itemsize
        elif self.kind == "dual":
            total += (t // self._tok_meta["alpha"]) * h * w * c \
                * _np_dtype(dtype).itemsize
        return total

    def advance_h2d_bytes(self, geom: tuple, stride: int) -> int:
        """Host->device payload bytes per incremental advance (exact)."""
        _, h, w, c, dtype = geom
        return stride * h * w * c * _np_dtype(dtype).itemsize

    def full_h2d_bytes(self, geom: tuple) -> int:
        """Host->device payload bytes per full-window recompute (exact)."""
        t, h, w, c, dtype = geom
        return t * h * w * c * _np_dtype(dtype).itemsize

    def _validate(self, geom: tuple, stride: int) -> None:
        t, h, w, c, _ = geom
        if stride <= 0 or t % stride != 0:
            raise SessionError(
                f"stride {stride} must divide the window length {t} "
                "(ring writes must never wrap mid-advance)")
        if self.kind == "tokens":
            m = self._tok_meta
            if stride % m["tt"] != 0:
                raise SessionError(
                    f"stride {stride} must be a multiple of the model's "
                    f"temporal tubelet {m['tt']} (token-ring granularity)")
            if t % m["tt"] or h % m["p"] or w % m["p"]:
                raise SessionError(
                    f"window geometry {(t, h, w)} does not tile the "
                    f"tubelet {(m['tt'], m['p'], m['p'])}")
            if self.trunk == "windowed" and self.attn_window > t // m["tt"]:
                raise SessionError(
                    f"attn_window {self.attn_window} exceeds the window's "
                    f"{t // m['tt']} token slots — a band wider than the "
                    "ring would attend evicted state")
        elif self.kind == "stem":
            m = self._tok_meta
            if stride % m["ts"] or t % m["ts"]:
                raise SessionError(
                    f"stride {stride} / window {t} must be multiples of "
                    f"the stem's temporal stride {m['ts']} (stem-ring "
                    "granularity)")
            kt = m["kernel"][0]
            if (m["halo"] + stride - kt) % m["ts"] \
                    or (m["halo"] + stride - kt) // m["ts"] + 1 \
                    != stride // m["ts"]:
                raise SessionError(
                    f"stride {stride} does not align the stem conv "
                    f"(kernel {kt}, stride {m['ts']}, halo {m['halo']})")
        elif self.kind == "dual":
            alpha = self._tok_meta["alpha"]
            if stride % alpha or t % alpha:
                raise SessionError(
                    f"stride {stride} / window {t} must be multiples of "
                    f"the slowfast alpha {alpha} — the slow ring advances "
                    "in lock-step at 1/alpha rate")

    # --- pools ------------------------------------------------------------

    def _pool(self, geom: tuple) -> Dict[str, Any]:
        """Get-or-create the ring pool for `geom` (replicated over the
        engine's mesh — per-replica single-device meshes are the fleet
        pattern, so replication is free there; a multi-device serving
        mesh pays HBM for simplicity, documented).

        The session budget is GLOBAL across pools: each new geometry's
        pool is sized from the budget's REMAINING bytes (first geometry
        gets most of it), and a geometry whose pool would hold zero
        sessions is refused — a client fanning out novel window shapes
        must exhaust the budget into 503s, never allocate
        budget-per-shape until the device OOMs."""
        with self._lock:
            pool = self._pools.get(geom)
            if pool is not None:
                return pool
            ring = max(self.ring_bytes(geom), 1)
            committed, src = self._budget_committed()
            remaining = self.session_budget_bytes - committed
            cap = remaining // ring
            if cap < 1:
                raise SessionAdmissionError(
                    f"session budget exhausted ({self.name}: "
                    f"{committed / 1e6:.0f} MB committed ({src}) of "
                    f"{self.session_budget_bytes / 1e6:.0f} MB; a "
                    f"{ring / 1e6:.1f} MB/session pool for {geom} does "
                    "not fit); retry later",
                    retry_after_s=self.table.retry_after_s)
            # +1 scratch slot: padded launch rows write here, never into a
            # leased ring
            pool = {"cap": int(cap), "bytes": int(cap + 1) * ring}
            for nm in self._ring_names:
                pool[nm] = self._alloc_ring(nm, geom, int(cap) + 1)
            # ledger the ACTUAL device bytes (padding/dtype promotion make
            # them drift from the ring_bytes estimate — the drift gauge's
            # whole point); admission above consumes the measured figure
            # on hosts that measure
            pool["measured_bytes"] = sum(
                int(getattr(pool[nm], "nbytes", 0))
                for nm in self._ring_names)
            obs_memory.register(self._mem_component,
                                pool["measured_bytes"],
                                declared=pool["bytes"])
            self._pools[geom] = pool
            self._committed += pool["bytes"]
            self.table.register_pool(geom, int(cap))
            logger.info(
                "stream: pool %s = %d session slots (+1 scratch), "
                "%.1f MB/session (%s); %.0f/%.0f MB budget committed",
                geom, cap, ring / 1e6, "+".join(self._ring_names),
                self._committed / 1e6, self.session_budget_bytes / 1e6)
            return pool

    def _budget_committed(self) -> tuple:
        """(bytes, source) the admission math diffs against the budget:
        *measured* ledger bytes on a host whose backend exposes
        `memory_stats()`, the declared `ring_bytes` estimates otherwise
        (the documented CPU/test fallback — estimates admit, but they
        never impersonate device bytes)."""
        led = obs_memory.get_ledger()
        if led is not None:
            measured = led.measured_bytes(self._mem_component)
            if measured is not None:
                return measured, "measured"
        return self._committed, "declared"

    def _replicated(self, arr):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(  # pva: disable=ledger-discipline -- generic H2D helper; retained rings are ledgered by their owning scope (_pool_for registers the pool bytes), other callers move transient launch rows
            arr, NamedSharding(self.mesh, P()))

    def _alloc_ring(self, name: str, geom: tuple, rows: int):
        t, h, w, c, dtype = geom
        m = self._tok_meta
        if name == "raw":
            shape, dt = (rows, t, h, w, c), _np_dtype(dtype)
        elif name == "tok":
            shape = (rows, t // m["tt"],
                     (h // m["p"]) * (w // m["p"]), m["dim"])
            dt = np.dtype(self._jnp.zeros((), m["dtype"]).dtype)
        elif name == "kv":
            tn, hw = t // m["tt"], (h // m["p"]) * (w // m["p"])
            shape = (rows, self._kv_meta["depth"], 2, tn, hw, m["dim"])
            dt = (np.int8 if self.quantization == "int8"
                  else np.dtype(self._jnp.zeros((), m["dtype"]).dtype))
        elif name == "kv_scale":
            tn, hw = t // m["tt"], (h // m["p"]) * (w // m["p"])
            shape = (rows, self._kv_meta["depth"], 2, tn, hw)
            dt = np.float32
        elif name == "hid":
            shape = (rows, t // m["tt"], m["dim"])
            dt = np.dtype(self._jnp.zeros((), m["dtype"]).dtype)
        elif name == "stem":
            hh, ww = self._stem_hw(geom)
            shape = (rows, t // m["ts"], hh, ww, m["dim"])
            dt = np.dtype(self._jnp.zeros((), m["dtype"]).dtype)
        elif name == "slow":
            shape = (rows, t // m["alpha"], h, w, c)
            dt = _np_dtype(dtype)
        else:
            raise SessionError(f"unknown ring {name!r}")
        return self._replicated(np.zeros(shape, dt))

    # --- compiled steps ---------------------------------------------------

    def _forward_windows(self, params, bstats, windows):
        """The wrapped engine's exact forward over in-graph windows
        (B, T, H, W, C): constrain -> normalize -> model — the op sequence
        of `InferenceEngine._make_forward`, so incremental logits carry
        serving parity by construction."""
        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.serving.quantize import (
            dequantize_tree,
        )
        from pytorchvideo_accelerate_tpu.trainer.steps import (
            _constrain_batch,
            device_normalize_batch,
            model_inputs,
            multiview_logits,
        )

        eng = self.engine
        if self.quantization == "int8":
            params = dequantize_tree(params, eng._compute_dtype)
        batch = _constrain_batch({"video": windows}, eng.mesh,
                                 leading_micro=False)
        batch = device_normalize_batch(batch, eng._device_normalize)
        logits = multiview_logits(
            lambda x: eng.model.apply(
                {"params": params, "batch_stats": bstats}, x, train=False),
            model_inputs(batch))
        return logits.astype(jnp.float32)

    def _forward_dual(self, params, bstats, slow_w, fast_w):
        """`_forward_windows` for the SlowFast pathway pair — the same
        constrain -> normalize -> model sequence over the {"slow",
        "fast"} batch `InferenceEngine.predict` serves, so dual-ring
        logits carry serving parity by construction."""
        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.serving.quantize import (
            dequantize_tree,
        )
        from pytorchvideo_accelerate_tpu.trainer.steps import (
            _constrain_batch,
            device_normalize_batch,
            model_inputs,
            multiview_logits,
        )

        eng = self.engine
        if self.quantization == "int8":
            params = dequantize_tree(params, eng._compute_dtype)
        batch = _constrain_batch({"slow": slow_w, "fast": fast_w},
                                 eng.mesh, leading_micro=False)
        batch = device_normalize_batch(batch, eng._device_normalize)
        logits = multiview_logits(
            lambda x: eng.model.apply(
                {"params": params, "batch_stats": bstats}, x, train=False),
            model_inputs(batch))
        return logits.astype(jnp.float32)

    def _embed_tokens(self, params, frames):
        """Patch-embed (B, t, H, W, C) frames -> (B, t/tt, hw, dim)
        pre-positional tokens: normalize (u8 engines) then the
        classifier's own CubeEmbed applied from its param subtree — each
        tubelet's token is a pure function of its own pixels, which is
        the whole reason the token ring is exact. `params` must already
        be dequantized (the compiled step dequantizes once at its top)."""
        from pytorchvideo_accelerate_tpu.models.videomae import CubeEmbed
        from pytorchvideo_accelerate_tpu.trainer.steps import (
            device_normalize_batch,
        )

        m = self._tok_meta
        model = self.engine.model
        x = device_normalize_batch({"video": frames},
                                   self.engine._device_normalize)["video"]
        tokens, (t, h, w) = CubeEmbed(
            model.dim, model.tubelet, model.dtype, name="patch_embed",
        ).apply({"params": params["encoder"]["patch_embed"]}, x)
        return tokens.reshape(tokens.shape[0], t, h * w, m["dim"])

    def _forward_tokens(self, params, tok_windows):
        """Trunk from cached tokens: + window-order positional embedding
        -> ViT blocks -> mean-pool -> fc_norm -> head, mirroring
        `VideoMAEClassifier.__call__` op for op (final_norm=False,
        deterministic dropout). `params` arrive dequantized."""
        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.models.videomae import (
            ViTBlock,
            sincos_pos_embed,
        )
        from pytorchvideo_accelerate_tpu.parallel.sharding import (
            constrain_block,
        )

        model = self.engine.model
        b, t, hw, dim = tok_windows.shape
        tokens = tok_windows.reshape(b, t * hw, dim)
        pos = jnp.asarray(sincos_pos_embed(t * hw, dim))[None]
        tokens = tokens + pos.astype(tokens.dtype)
        # a banded-trunk backbone (model.attn_mask, the streaming
        # finetune knob) keeps its band under trunk="full" too —
        # `full` means "recompute the whole trunk", never "drop the
        # mask the model was finetuned with"
        mask = None
        if getattr(model, "attn_mask", "none") != "none":
            from pytorchvideo_accelerate_tpu.ops.attention import (
                temporal_band_mask,
            )

            width = t if model.attn_mask == "causal" else model.attn_window
            mask = temporal_band_mask(t, hw, width)[None, None]
        for i in range(model.depth):
            tokens = ViTBlock(
                dim=model.dim, num_heads=model.num_heads,
                attention_backend=model.attention_backend,
                context_mesh=model.context_mesh, dtype=model.dtype,
            ).apply({"params": params["encoder"][f"block{i}"]}, tokens,
                    mask)
            tokens = constrain_block(tokens,
                                     getattr(model, "shard_mesh", None))
        return self._head_logits(params, tokens.mean(axis=1))

    def _head_logits(self, params, feat):
        """The classifier epilogue — fc_norm -> head in the engine's
        f32-island policy — shared by every token/KV trunk path so the
        full and incremental graphs read one definition of the head."""
        import jax.numpy as jnp
        from flax import linen as nn

        from pytorchvideo_accelerate_tpu.precision import f32_island

        model = self.engine.model
        feat = nn.LayerNorm(dtype=model.dtype).apply(
            {"params": params["fc_norm"]}, feat)
        logits = nn.Dense(model.num_classes, dtype=jnp.float32).apply(
            {"params": params["head"]}, f32_island(feat))
        return logits.astype(jnp.float32)

    # --- KV trunk (causal / windowed) -------------------------------------

    def _block_fwd(self, bp, x, mask, kv_cache=None):
        """One ViT block hand-rolled from its param subtree, exposing the
        per-layer K/V the KV rings cache. Mirrors
        models/videomae.ViTBlock op for op (pre-LN, erf GELU, the same
        `dot_product_attention` router) — the vs-classifier parity test
        in tests/test_zkvcache.py holds this to the serving tolerance.

        `kv_cache=(k, v)` (B, Nc, dim) switches to the INCREMENTAL
        formulation: x's queries attend [cache ++ x's own keys]; `mask`
        must then be the band over that concatenated key order. Returns
        (x_out, k, v) where k/v cover ONLY x's own tokens — exactly what
        gets written back into the ring."""
        import jax.numpy as jnp
        from flax import linen as nn

        from pytorchvideo_accelerate_tpu.ops.attention import (
            dot_product_attention,
        )

        model = self.engine.model
        dim, heads = model.dim, model.num_heads
        hd = dim // heads
        dt = model.dtype
        y = nn.LayerNorm(dtype=dt).apply({"params": bp["norm1"]}, x)
        qkv = nn.Dense(3 * dim, dtype=dt).apply({"params": bp["qkv"]}, y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        kk, vv = k, v
        if kv_cache is not None:
            kk = jnp.concatenate([kv_cache[0].astype(k.dtype), k], axis=1)
            vv = jnp.concatenate([kv_cache[1].astype(v.dtype), v], axis=1)
        b, nq = q.shape[:2]
        nk = kk.shape[1]
        attn = dot_product_attention(
            q.reshape(b, nq, heads, hd), kk.reshape(b, nk, heads, hd),
            vv.reshape(b, nk, heads, hd),
            backend=model.attention_backend, mesh=model.context_mesh,
            mask=mask,
        ).reshape(b, nq, dim)
        x = x + nn.Dense(dim, dtype=dt).apply({"params": bp["proj"]}, attn)
        y = nn.LayerNorm(dtype=dt).apply({"params": bp["norm2"]}, x)
        y = nn.Dense(bp["mlp_fc1"]["kernel"].shape[-1], dtype=dt).apply(
            {"params": bp["mlp_fc1"]}, y)
        y = nn.gelu(y, approximate=False)
        x = x + nn.Dense(dim, dtype=dt).apply({"params": bp["mlp_fc2"]}, y)
        return x, k, v

    def _trunk_kv_full(self, params, tokens, slot_idx, window, ring_slots):
        """Masked trunk over a whole window of tokens in LOGICAL
        (oldest-first) order -> (per-layer KV (B, L, 2, tn, hw, dim) in
        the same logical order, per-slot hidden means (B, tn, dim)).

        `slot_idx` (B, tn) gives each logical slot's RING-SLOT-stable
        position index ((abs_slot mod T')); positional codes are gathered
        from the T'*hw table by that index, so cached K/V stay valid as
        the ring rotates. At establish `slot_idx == arange(T')` — the
        ordinary window positions the finetuned backbone saw."""
        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.models.videomae import (
            sincos_pos_embed,
        )
        from pytorchvideo_accelerate_tpu.ops.attention import (
            temporal_band_mask,
        )
        from pytorchvideo_accelerate_tpu.parallel.sharding import (
            constrain_block,
        )

        model = self.engine.model
        b, tn, hw, dim = tokens.shape
        pos = jnp.asarray(sincos_pos_embed(ring_slots * hw, dim))
        pos_idx = (slot_idx[..., None] * hw
                   + jnp.arange(hw, dtype=jnp.int32)[None, None, :])
        x = tokens.reshape(b, tn * hw, dim) + jnp.take(
            pos, pos_idx.reshape(b, tn * hw), axis=0).astype(tokens.dtype)
        mask = temporal_band_mask(tn, hw, window)[None, None]
        ks, vs = [], []
        for i in range(model.depth):
            x, k, v = self._block_fwd(
                params["encoder"][f"block{i}"], x, mask)
            x = constrain_block(x, getattr(model, "shard_mesh", None))
            ks.append(k)
            vs.append(v)
        depth = model.depth
        kv = jnp.stack([jnp.stack(ks, axis=1), jnp.stack(vs, axis=1)],
                       axis=2).reshape(b, depth, 2, tn, hw, dim)
        hid = x.reshape(b, tn, hw, dim).mean(axis=2)
        return kv, hid

    def _trunk_kv_step(self, params, new_tok, kv_cache, tpos, window,
                       ring_slots):
        """The incremental trunk: the ns NEW slots' queries against the
        cached ring K/V plus their own -> (new per-layer KV
        (B, L, 2, ns, hw, dim), new hidden means (B, ns, dim)).

        `tpos` (B,) int32 TRACED — the absolute index of the first new
        slot. The band mask is computed on absolute indices recovered
        from `tpos` (ring slot j holds abs `newest - ((newest - j) mod
        T')`), so slots being overwritten this advance (abs <= tpos - T')
        fall outside every query's band automatically: wraparound can
        never alias a future slot. `kv_cache` (B, L, 2, T', hw, dim)
        arrives dequantized in compute dtype."""
        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.models.videomae import (
            sincos_pos_embed,
        )
        from pytorchvideo_accelerate_tpu.ops.attention import (
            banded_time_mask,
        )
        from pytorchvideo_accelerate_tpu.parallel.sharding import (
            constrain_block,
        )

        model = self.engine.model
        b, ns, hw, dim = new_tok.shape
        tn = ring_slots
        j = jnp.arange(tn, dtype=jnp.int32)[None, :]
        newest = (tpos - 1)[:, None]
        k_abs = newest - ((newest - j) % tn)                     # (B, tn)
        q_abs = tpos[:, None] + jnp.arange(ns, dtype=jnp.int32)[None, :]
        band = banded_time_mask(
            q_abs, jnp.concatenate([k_abs, q_abs], axis=1), window)
        mask = jnp.repeat(jnp.repeat(band, hw, axis=1), hw, axis=2)[:, None]
        pos = jnp.asarray(sincos_pos_embed(tn * hw, dim))
        pos_idx = ((q_abs % tn)[..., None] * hw
                   + jnp.arange(hw, dtype=jnp.int32)[None, None, :])
        x = new_tok.reshape(b, ns * hw, dim) + jnp.take(
            pos, pos_idx.reshape(b, ns * hw), axis=0).astype(new_tok.dtype)
        ks, vs = [], []
        for i in range(model.depth):
            cache = (kv_cache[:, i, 0].reshape(b, tn * hw, dim),
                     kv_cache[:, i, 1].reshape(b, tn * hw, dim))
            x, k, v = self._block_fwd(
                params["encoder"][f"block{i}"], x, mask, kv_cache=cache)
            x = constrain_block(x, getattr(model, "shard_mesh", None))
            ks.append(k)
            vs.append(v)
        depth = model.depth
        new_kv = jnp.stack([jnp.stack(ks, axis=1), jnp.stack(vs, axis=1)],
                           axis=2).reshape(b, depth, 2, ns, hw, dim)
        new_hid = x.reshape(b, ns, hw, dim).mean(axis=2)
        return new_kv, new_hid

    # --- MViT stem seam ---------------------------------------------------

    def _stem_embed(self, params, frames, temporal_pad):
        """Normalize raw frames and run MViT's patch-embed conv from its
        param subtree -> (B, t', h', w', embed_dim) pre-positional stem
        tokens. `temporal_pad`: the model's own (halo, halo) for
        establish/replay (fresh-stream zero halo at the very first
        frame), (0, 0) for the advance — the REAL halo frames ride at
        the front of `frames` there, gathered from the raw ring."""
        from flax import linen as nn

        from pytorchvideo_accelerate_tpu.trainer.steps import (
            device_normalize_batch,
        )

        m = self._tok_meta
        model = self.engine.model
        x = device_normalize_batch({"video": frames},
                                   self.engine._device_normalize)["video"]
        x = x.astype(model.dtype)
        _, kh, kw = m["kernel"]
        pad = [tuple(temporal_pad), (kh // 2, kh // 2), (kw // 2, kw // 2)]
        return nn.Conv(
            m["dim"], kernel_size=m["kernel"], strides=m["stride_sp"],
            padding=pad, dtype=model.dtype,
        ).apply({"params": params["patch_embed"]}, x)

    def _forward_stem(self, params, bstats, stem_windows):
        """Trunk re-entry from cached stem tokens: `MViT.apply(...,
        from_stem=True)` over the window-ordered (B, T', H', W', dim)
        token grid — pos_embed is added inside, in window order.
        `params` arrive dequantized."""
        import jax.numpy as jnp

        logits = self.engine.model.apply(
            {"params": params, "batch_stats": bstats}, stem_windows,
            train=False, from_stem=True)
        return logits.astype(jnp.float32)

    def _get_fn(self, op: str, geom: tuple, stride: int, bucket: int):
        key = (op, self.kind, geom, int(stride), int(bucket))
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                if len(self._fns) >= MAX_STREAM_KEYS:
                    raise SessionError(
                        f"engine already compiled {len(self._fns)} stream "
                        "geometries; refusing a new one (clients should "
                        "send the serving stream geometry)")
                fn = self._build_fn(op, geom, stride, bucket)
                self._fns[key] = fn
                logger.info("stream: compiling %s for %s stride=%d B=%d",
                            op, geom, stride, bucket)
        return fn

    def _build_fn(self, op: str, geom: tuple, stride: int, bucket: int):
        import jax
        import jax.numpy as jnp

        tokens = self.kind == "tokens"
        m = self._tok_meta
        names = self._ring_names
        nring = len(names)
        donate = tuple(range(2, 2 + nring))

        def dq(params):
            # token/stem-path dequant happens ONCE here: the embed and
            # the trunk both read the same fp view, and XLA fuses q*scale
            # into the weight reads exactly like the engine forward
            if self.quantization == "int8" and self.kind in ("tokens",
                                                             "stem"):
                from pytorchvideo_accelerate_tpu.serving.quantize import (
                    dequantize_tree,
                )

                return dequantize_tree(params, self.engine._compute_dtype)
            return params

        def write(pool, rows, slots, offs):
            """Write per-session rows into the donated pool at traced
            (slot, offset) — a sequential fori_loop of
            dynamic_update_slice, which XLA applies IN PLACE on the
            donated buffer: the update moves only the new rows' bytes,
            never whole rings (the gather-modify-scatter formulation
            copied every ring three times and cost more than the H2D it
            saved). Offsets never wrap because stride divides the
            window; scratch-slot duplicates are benign (sequential)."""
            def body(i, p):
                return jax.lax.dynamic_update_slice(
                    p, rows[i][None].astype(p.dtype),
                    (slots[i], offs[i]) + (0,) * (p.ndim - 2))

            return jax.lax.fori_loop(0, rows.shape[0], body, pool)

        def write_axis(pool, rows, slots, offs, axis):
            """`write` with the rolling offset on an arbitrary pool axis
            — the KV ring keeps its temporal slots at axis 3 of the
            (rows, L, 2, T', hw, dim) pool, so the per-advance write
            lands at (slot, :, :, off_t, ...)."""
            def body(i, p):
                start = [slots[i]] + [0] * (p.ndim - 1)
                start[axis] = offs[i]
                return jax.lax.dynamic_update_slice(
                    p, rows[i][None].astype(p.dtype), tuple(start))

            return jax.lax.fori_loop(0, rows.shape[0], body, pool)

        # --- KV-trunk token ops (causal / windowed) -----------------------
        if tokens and self.trunk != "full":
            from pytorchvideo_accelerate_tpu.serving.quantize import (
                dequantize_kv,
                quantize_kv,
            )

            tt = m["tt"]
            tn = geom[0] // tt
            window = self._band_width(geom)
            int8 = "kv_scale" in names

            def write_kv(rings_out, kv_new, hid_new, slots, toffs):
                """Quantize (int8 engines) and write one advance's new
                K/V + hidden slots into their rings."""
                if int8:
                    q8, sc = quantize_kv(kv_new)
                    rings_out["kv"] = write_axis(
                        rings_out["kv"], q8, slots, toffs, 3)
                    rings_out["kv_scale"] = write_axis(
                        rings_out["kv_scale"], sc, slots, toffs, 3)
                else:
                    rings_out["kv"] = write_axis(
                        rings_out["kv"], kv_new, slots, toffs, 3)
                rings_out["hid"] = write(
                    rings_out["hid"], hid_new, slots, toffs)

            if op == "establish":
                def fn(params, bstats, *args):
                    rings = dict(zip(names, args[:nring]))
                    windows, slots = args[nring], args[nring + 1]
                    params = dq(params)
                    zeros = jnp.zeros_like(slots)
                    rings["raw"] = write(rings["raw"], windows, slots,
                                         zeros)
                    new_tok = self._embed_tokens(params, windows)
                    rings["tok"] = write(rings["tok"], new_tok, slots,
                                         zeros)
                    slot_idx = jnp.broadcast_to(
                        jnp.arange(tn, dtype=jnp.int32),
                        (new_tok.shape[0], tn))
                    kv_new, hid_new = self._trunk_kv_full(
                        params, new_tok, slot_idx, window, tn)
                    write_kv(rings, kv_new, hid_new, slots, zeros)
                    logits = self._head_logits(params, hid_new.mean(axis=1))
                    return tuple(rings[nm] for nm in names) + (logits,)

                return jax.jit(fn, donate_argnums=donate)

            if op == "advance":
                def fn(params, bstats, *args):
                    rings = dict(zip(names, args[:nring]))
                    frames, slots, offs, tpos = args[nring:nring + 4]
                    params = dq(params)
                    rings["raw"] = write(rings["raw"], frames, slots, offs)
                    new_tok = self._embed_tokens(params, frames)
                    toffs = offs // tt
                    rings["tok"] = write(rings["tok"], new_tok, slots,
                                         toffs)
                    kv_rows = rings["kv"][slots]
                    if int8:
                        kv_rows = dequantize_kv(
                            kv_rows, rings["kv_scale"][slots],
                            self.engine.model.dtype)
                    new_kv, new_hid = self._trunk_kv_step(
                        params, new_tok, kv_rows, tpos, window, tn)
                    write_kv(rings, new_kv, new_hid, slots, toffs)
                    feat = rings["hid"][slots].mean(axis=1)
                    logits = self._head_logits(params, feat)
                    return tuple(rings[nm] for nm in names) + (logits,)

                return jax.jit(fn, donate_argnums=donate)

        # --- stem-ring ops (MViT token seam) ------------------------------
        if self.kind == "stem":
            ts, halo = m["ts"], m["halo"]

            if op == "establish":
                def fn(params, bstats, *args):
                    rings = dict(zip(names, args[:nring]))
                    windows, slots = args[nring], args[nring + 1]
                    params = dq(params)
                    zeros = jnp.zeros_like(slots)
                    rings["raw"] = write(rings["raw"], windows, slots,
                                         zeros)
                    new_stem = self._stem_embed(params, windows,
                                                (halo, halo))
                    rings["stem"] = write(rings["stem"], new_stem, slots,
                                          zeros)
                    logits = self._forward_stem(params, bstats, new_stem)
                    return tuple(rings[nm] for nm in names) + (logits,)

                return jax.jit(fn, donate_argnums=donate)

            if op == "advance":
                t = geom[0]
                ss = stride // ts

                def fn(params, bstats, *args):
                    rings = dict(zip(names, args[:nring]))
                    frames, slots, offs = args[nring:nring + 3]
                    params = dq(params)
                    rings["raw"] = write(rings["raw"], frames, slots, offs)
                    # the REAL left halo: the newest frames still in the
                    # ring before this write's offset (never overwritten
                    # by it — the write covers [off, off+stride))
                    halo_idx = (offs[:, None] - halo
                                + jnp.arange(halo, dtype=jnp.int32)[None,
                                                                    :]) % t
                    halo_frames = jax.vmap(
                        lambda r, hi: jnp.take(r, hi, axis=0)
                    )(rings["raw"][slots], halo_idx)
                    x = jnp.concatenate(
                        [halo_frames.astype(frames.dtype), frames], axis=1)
                    new_stem = self._stem_embed(params, x, (0, 0))
                    rings["stem"] = write(rings["stem"], new_stem, slots,
                                          offs // ts)
                    stem_windows = jax.vmap(
                        lambda r, o: jnp.roll(r, -(o // ts + ss), axis=0)
                    )(rings["stem"][slots], offs)
                    logits = self._forward_stem(params, bstats,
                                                stem_windows)
                    return tuple(rings[nm] for nm in names) + (logits,)

                return jax.jit(fn, donate_argnums=donate)

        # --- dual-rate ops (SlowFast) -------------------------------------
        if self.kind == "dual":
            alpha = m["alpha"]

            if op == "establish":
                def fn(params, bstats, *args):
                    rings = dict(zip(names, args[:nring]))
                    windows, slots = args[nring], args[nring + 1]
                    zeros = jnp.zeros_like(slots)
                    rings["raw"] = write(rings["raw"], windows, slots,
                                         zeros)
                    slow_w = windows[:, ::alpha]
                    rings["slow"] = write(rings["slow"], slow_w, slots,
                                          zeros)
                    logits = self._forward_dual(
                        params, bstats,
                        slow_w.astype(rings["slow"].dtype),
                        windows.astype(rings["raw"].dtype))
                    return tuple(rings[nm] for nm in names) + (logits,)

                return jax.jit(fn, donate_argnums=donate)

            if op == "advance":
                sstride = stride // alpha

                def fn(params, bstats, *args):
                    rings = dict(zip(names, args[:nring]))
                    frames, slots, offs = args[nring:nring + 3]
                    rings["raw"] = write(rings["raw"], frames, slots, offs)
                    rings["slow"] = write(rings["slow"], frames[:, ::alpha],
                                          slots, offs // alpha)
                    fast_w = jax.vmap(
                        lambda r, o: jnp.roll(r, -(o + stride), axis=0)
                    )(rings["raw"][slots], offs)
                    slow_w = jax.vmap(
                        lambda r, o: jnp.roll(r, -(o // alpha + sstride),
                                              axis=0)
                    )(rings["slow"][slots], offs)
                    logits = self._forward_dual(params, bstats, slow_w,
                                                fast_w)
                    return tuple(rings[nm] for nm in names) + (logits,)

                return jax.jit(fn, donate_argnums=donate)

        # --- frame-ring and full-trunk token ops (unchanged graphs) -------
        if op == "advance" and not tokens:
            def fn(params, bstats, raw, frames, slots, offs):
                raw = write(raw, frames, slots, offs)
                windows = jax.vmap(
                    lambda r, o: jnp.roll(r, -(o + stride), axis=0)
                )(raw[slots], offs)
                return raw, self._forward_windows(params, bstats, windows)

            return jax.jit(fn, donate_argnums=(2,))

        if op == "advance" and tokens:
            tstride = stride // m["tt"]

            def fn(params, bstats, raw, tok, frames, slots, offs):
                params = dq(params)
                raw = write(raw, frames, slots, offs)
                new_tok = self._embed_tokens(params, frames)
                tok = write(tok, new_tok, slots, offs // m["tt"])
                tok_windows = jax.vmap(
                    lambda r, o: jnp.roll(r, -(o // m["tt"] + tstride),
                                          axis=0))(tok[slots], offs)
                return (raw, tok,
                        self._forward_tokens(params, tok_windows))

            return jax.jit(fn, donate_argnums=(2, 3))

        if op == "establish" and not tokens:
            def fn(params, bstats, raw, windows, slots):
                raw = write(raw, windows, slots, jnp.zeros_like(slots))
                # the freshly-written rings ARE the input windows (offset
                # 0): forward from the input, no gather-back needed
                return raw, self._forward_windows(
                    params, bstats, windows.astype(raw.dtype))

            return jax.jit(fn, donate_argnums=(2,))

        if op == "establish" and tokens:
            def fn(params, bstats, raw, tok, windows, slots):
                params = dq(params)
                zeros = jnp.zeros_like(slots)
                raw = write(raw, windows, slots, zeros)
                new_tok = self._embed_tokens(params, windows)
                tok = write(tok, new_tok, slots, zeros)
                return raw, tok, self._forward_tokens(params, new_tok)

            return jax.jit(fn, donate_argnums=(2, 3))

        raise SessionError(f"unknown stream op {op!r}")

    # --- the session surface ---------------------------------------------

    def advance_batch(self, items: List[dict]) -> List[Any]:
        """Score one launch of session advances. Each item:
        ``{"sid": str, "frames": (s, H, W, C), "window": optional
        (T, H, W, C) resendable window, "end": bool}``.

        Routing per item: a session this replica holds advances
        incrementally; an unknown/mismatched one re-establishes
        DETERMINISTICALLY from the item's resendable window (how replica
        death and affinity re-routes stay client-invisible) or fails
        with `SessionUnknownError` when no window rides along. Items are
        grouped into same-(geometry, stride) compiled launches; duplicate
        sids within one call are serialized into waves (a ring must never
        be read and written by two rows of one launch). Returns one entry
        PER ITEM in order: fp32 logits, or the Exception that item earned
        — a malformed item must fail ITS future, never its co-batched
        neighbours'."""
        self.table.sweep()
        results: List[Any] = [None] * len(items)
        pending = list(enumerate(items))
        while pending:
            wave: List[tuple] = []
            seen: set = set()
            rest: List[tuple] = []
            for idx, item in pending:
                sid = str(item.get("sid", ""))
                if sid in seen:
                    rest.append((idx, item))
                else:
                    seen.add(sid)
                    wave.append((idx, item))
            self._run_wave(wave, results)
            pending = rest
        for item in items:
            if item.get("end"):
                self.table.end(str(item.get("sid", "")))
        return results

    def _classify(self, item: dict) -> tuple:
        """-> (mode, sid, payload np, geom, stride) for one item; decides
        advance vs re-establish and validates against the session/ring
        contract."""
        sid = str(item.get("sid") or "")
        if not sid:
            raise SessionError("stream item carries no session id")
        frames = item.get("frames")
        window = item.get("window")
        if frames is None and window is None:
            raise SessionError(f"stream item for {sid!r} carries neither "
                               "frames nor a window")
        dtype = self.input_dtype
        if window is not None:
            window = np.asarray(window, dtype)
            if window.ndim != 4:
                raise SessionError(
                    f"window for {sid!r} must be (T, H, W, C), got "
                    f"{window.shape}")
        if frames is not None:
            frames = np.asarray(frames, dtype)
            if frames.ndim != 4:
                raise SessionError(
                    f"frames for {sid!r} must be (s, H, W, C), got "
                    f"{frames.shape}")
        state = self.table.get(sid)
        if state is not None and frames is not None:
            geom = state.pool_key
            if (frames.shape[0] == state.stride
                    and tuple(frames.shape[1:]) == tuple(geom[1:4])):
                return ("advance", sid, frames, geom, state.stride)
            # stride/geometry drift: fall through to re-establish (window
            # required — silently writing drifted frames would corrupt
            # the ring)
        if window is None:
            raise SessionUnknownError(
                f"session {sid!r} is not established on this replica and "
                "the request carries no resendable window")
        t, h, w, c = window.shape
        stride = int(item.get("stride") or
                     (frames.shape[0] if frames is not None else 0) or 0)
        if stride <= 0:
            raise SessionError(
                f"establish for {sid!r} needs a stride (frames payload or "
                "explicit 'stride')")
        geom = self.geom_key(t, h, w, c, dtype)
        self._validate(geom, stride)
        return ("establish", sid, window, geom, stride)

    def _run_wave(self, wave: List[tuple], results: List[Any]) -> None:
        """Group one duplicate-free wave by (mode, geom, stride) and run
        each group as one bucketed compiled launch. Per-item
        classification/admission failures land in `results` as
        exceptions; the rest of the wave still launches."""
        groups: Dict[tuple, List[tuple]] = {}
        for idx, item in wave:
            try:
                mode, sid, payload, geom, stride = self._classify(item)
            except Exception as e:  # noqa: BLE001 - per-item verdict
                results[idx] = e
                continue
            groups.setdefault((mode, geom, stride), []).append(
                (idx, sid, payload))
        for (mode, geom, stride), rows in groups.items():
            try:
                if mode == "establish":
                    self._launch_establish(geom, stride, rows, results)
                else:
                    self._launch_advance(geom, stride, rows, results)
            except Exception as e:  # noqa: BLE001 - contain to THIS group
                # a group-level failure (MAX_STREAM_KEYS refusal for a
                # novel geometry, a compile error) must fail the group
                # that caused it — never the other geometries co-batched
                # in the same flush
                for idx, _, _ in rows:
                    if results[idx] is None:
                        results[idx] = e

    def _stack(self, rows, pool) -> tuple:
        """Pad a group to its bucket: payload rows stacked with zero
        rows, slots padded with the pool's scratch row, offsets 0."""
        n = len(rows)
        bucket = self.bucket_for(n)
        payload = np.stack([p for _, _, p in rows])
        if bucket > n:
            pad = np.zeros((bucket - n,) + payload.shape[1:], payload.dtype)
            payload = np.concatenate([payload, pad], axis=0)
        return payload, bucket, pool["cap"]

    def _tpos_of(self, state) -> int:
        """A session's absolute token-slot position counter: the index
        the NEXT advance's first new slot will carry. Establish seeds
        slots 0..T'-1, so tpos == T' there; the `tpos % T' == off//tt`
        invariant is what lets the hot-swap rebuild recover every slot's
        absolute index from the adopted table."""
        tt = self._tok_meta["tt"]
        return (state.window + state.frames_seen) // tt

    def _launch_establish(self, geom, stride, rows, results) -> None:
        pool = self._pool(geom)
        live = []
        states = []
        for idx, sid, payload in rows:
            try:
                # the admission decision (TTL eviction vs 503) happens
                # here, per session, against the HBM budget
                states.append(self.table.establish(
                    sid, geom, stride=stride, window=geom[0]))
                live.append((idx, sid, payload))
            except Exception as e:  # noqa: BLE001 - per-item verdict
                results[idx] = e
        if not live:
            return
        payload, bucket, scratch = self._stack(live, pool)
        slots = np.asarray([s.slot for s in states]
                           + [scratch] * (bucket - len(live)), np.int32)
        fn = self._get_fn("establish", geom, stride, bucket)
        logits = self._guarded_call(fn, geom, pool, payload, slots, None)
        for i, (idx, sid, _) in enumerate(live):
            # establish resets the write offset to 0; the committed
            # position is "window seen, next write at 0"
            results[idx] = np.asarray(logits[i], np.float32)

    def _launch_advance(self, geom, stride, rows, results) -> None:
        pool = self._pool(geom)
        live = []
        states = []
        for idx, sid, payload in rows:
            s = self.table.get(sid)
            if s is None:  # evicted between classify and launch
                results[idx] = SessionUnknownError(
                    f"session {sid!r} evicted mid-launch; resend window")
                continue
            states.append(s)
            live.append((idx, sid, payload))
        if not live:
            return
        payload, bucket, scratch = self._stack(live, pool)
        slots = np.asarray([s.slot for s in states]
                           + [scratch] * (bucket - len(live)), np.int32)
        offs = np.asarray([s.off for s in states]
                          + [0] * (bucket - len(live)), np.int32)
        tpos = None
        if self._kv_meta is not None:
            # scratch rows get the just-established counter (T'), which
            # keeps their band/position arithmetic consistent with their
            # zero offsets
            tn = geom[0] // self._tok_meta["tt"]
            tpos = np.asarray([self._tpos_of(s) for s in states]
                              + [tn] * (bucket - len(live)), np.int32)
        fn = self._get_fn("advance", geom, stride, bucket)
        logits = self._guarded_call(fn, geom, pool, payload, slots, offs,
                                    tpos)
        for i, (idx, sid, _) in enumerate(live):
            self.table.advanced(sid, stride)
            results[idx] = np.asarray(logits[i], np.float32)

    def _guarded_call(self, fn, geom, pool, payload, slots, offs,
                      tpos=None):
        """`_call` with donated-buffer failure recovery: if the compiled
        step raises mid-execution (transient device OOM, XLA runtime
        error), the donated pool buffers are already deleted while the
        pool dict still references them — every later launch on this
        geometry would fail with 'array has been deleted' forever. Drop
        the pool and its sessions instead: clients re-establish from
        their resendable windows (the designed recovery path), and only
        THIS group's futures see the original error."""
        try:
            return self._call(fn, pool, payload, slots, offs, tpos)
        except Exception:
            dropped = self._invalidate_pool(geom)
            logger.exception(
                "stream: launch failed on %s; dropped the pool and its "
                "%d session(s) (donated ring buffers are gone — clients "
                "re-establish from their resendable windows)", geom,
                dropped)
            raise

    def _invalidate_pool(self, geom) -> int:
        """Forget a pool whose device buffers are lost; ends every
        session leased on it (their slots return to the free list, so a
        fresh pool of the same geometry starts clean). Returns the
        number of sessions dropped."""
        with self._lock:
            pool = self._pools.pop(geom, None)
            if pool is not None:
                self._committed -= pool["bytes"]
        if pool is not None:
            obs_memory.release(
                self._mem_component,
                pool.get("measured_bytes", pool["bytes"]),
                declared=pool["bytes"])
        dropped = 0
        for s in self.table.sessions():
            if s.pool_key == geom and self.table.end(s.sid):
                dropped += 1
        return dropped

    def _call(self, fn, pool, payload, slots, offs, tpos=None):
        """Run one compiled stream step, threading the donated ring
        pool(s) through in `_ring_names` order and committing the
        returned buffers."""
        eng = self.engine
        payload = self._replicated(payload)
        slots = self._replicated(slots)
        args = [eng.params, eng.batch_stats]
        args += [pool[nm] for nm in self._ring_names]
        args.append(payload)
        args.append(slots)
        if offs is not None:
            args.append(self._replicated(offs))
        if tpos is not None:
            args.append(self._replicated(tpos))
        out = fn(*args)
        for nm, buf in zip(self._ring_names, out):
            pool[nm] = buf
        return out[-1]

    def end_session(self, sid: str) -> bool:
        return self.table.end(sid)

    def warmup_stream(self, window: int, h: int, w: int, c: int,
                      stride: int) -> int:
        """Pre-compile establish+advance at EVERY bucket for one stream
        geometry (the cold-start analog of `InferenceEngine.warmup`, and
        what `prewarm_from` does for a hot-swap): scratch-slot launches,
        so no session is created and no ring is disturbed. Without this,
        the first lone-session arrival at each bucket size pays a
        synchronous compile on the scheduler's flush thread."""
        geom = self.geom_key(window, h, w, c, self.input_dtype)
        self._validate(geom, stride)
        pool = self._pool(geom)
        t, _, _, _, dtype = geom
        scratch = pool["cap"]
        n = 0
        for b in self.buckets:
            slots = np.full((b,), scratch, np.int32)
            fn = self._get_fn("establish", geom, stride, b)
            self._guarded_call(fn, geom, pool,
                               np.zeros((b, t, h, w, c), _np_dtype(dtype)),
                               slots, None)
            fn = self._get_fn("advance", geom, stride, b)
            tpos = None
            if self._kv_meta is not None:
                tpos = np.full((b,), t // self._tok_meta["tt"], np.int32)
            self._guarded_call(fn, geom, pool,
                               np.zeros((b, stride, h, w, c),
                                        _np_dtype(dtype)),
                               slots, np.zeros((b,), np.int32), tpos)
            n += 2
        return n

    # --- parity + probes --------------------------------------------------

    def full_recompute(self, windows: np.ndarray) -> np.ndarray:
        """The baseline the parity gate compares against: assemble the
        host windows (B, T, H, W, C), pad to the engine bucket, and run
        the ordinary one-shot `predict` — full H2D + full embed + trunk.
        For the dual-rate family the slow pathway is the phase-0
        subsample of the window (the slide-stable serving convention the
        slow ring implements)."""
        n = windows.shape[0]
        bucket = self.bucket_for(n)
        if bucket > n:
            pad = np.zeros((bucket - n,) + windows.shape[1:], windows.dtype)
            windows = np.concatenate([windows, pad], axis=0)
        if self.kind == "dual":
            alpha = self._tok_meta["alpha"]
            return self.engine.predict(
                {"slow": windows[:, ::alpha], "fast": windows})[:n]
        return self.engine.predict({"video": windows})[:n]

    def full_recompute_history(self, histories: np.ndarray,
                               window: int) -> np.ndarray:
        """The parity oracle for the STATEFUL families: recompute what
        the incremental path SHOULD produce from the entire per-session
        frame history since establish (B, F, H, W, C), F >= window.

        - KV trunks: one masked forward over the whole history with the
          band on absolute slot indices and ring-slot-stable positions —
          the cached-state semantics exactly (the last-window one-shot
          recompute is NOT equivalent: cached K/V legitimately attended
          context that has since left the ring).
        - stem ring: the full-history stem conv (real halo everywhere
          the stream had one), last T' stem slots through the trunk —
          where one-shot `predict` zero-pads the window edge.
        - exact-window families (frames / tokens-full / dual): delegates
          to `full_recompute` over the trailing window.

        Jitted per (kind, geometry-ish, shape) under the same `_fns`
        cache (each distinct history length is its own key, so the
        flat-cache probe stays honest)."""
        import jax.numpy as jnp

        histories = np.asarray(histories, _np_dtype(self.input_dtype))
        stateful_kv = self.kind == "tokens" and self.trunk != "full"
        if not (stateful_kv or self.kind == "stem"):
            return np.asarray(
                self.full_recompute(histories[:, -window:]), np.float32)
        key = ("replay", self.kind, int(window),
               tuple(int(s) for s in histories.shape))
        fn = self._fns.get(key)
        if fn is None:
            import jax

            t = int(window)
            if stateful_kv:
                m = self._tok_meta
                tn = t // m["tt"]
                fn_geom = self.geom_key(t, histories.shape[2],
                                        histories.shape[3],
                                        histories.shape[4],
                                        self.input_dtype)
                band = self._band_width(fn_geom)

                def replay(params, hist):
                    if self.quantization == "int8":
                        from pytorchvideo_accelerate_tpu.serving.quantize import (  # noqa: E501
                            dequantize_tree,
                        )

                        params = dequantize_tree(
                            params, self.engine._compute_dtype)
                    tok = self._embed_tokens(params, hist)  # (B, F', hw, d)
                    fslots = tok.shape[1]
                    slot_idx = jnp.broadcast_to(
                        jnp.arange(fslots, dtype=jnp.int32) % tn,
                        (tok.shape[0], fslots))
                    _, hid = self._trunk_kv_full(params, tok, slot_idx,
                                                 band, tn)
                    return self._head_logits(params,
                                             hid[:, -tn:].mean(axis=1))
            else:
                m = self._tok_meta
                tn = t // m["ts"]
                halo = m["halo"]

                def replay(params, hist):
                    if self.quantization == "int8":
                        from pytorchvideo_accelerate_tpu.serving.quantize import (  # noqa: E501
                            dequantize_tree,
                        )

                        params = dequantize_tree(
                            params, self.engine._compute_dtype)
                    stem = self._stem_embed(params, hist, (halo, halo))
                    return self._forward_stem(
                        params, self.engine.batch_stats, stem[:, -tn:])

            with self._lock:
                fn = self._fns.get(key)
                if fn is None:
                    fn = jax.jit(replay)
                    self._fns[key] = fn
        out = fn(self.engine.params, self._replicated(histories))
        return np.asarray(out, np.float32)

    def compiled_stream_keys(self) -> tuple:
        with self._lock:
            return tuple(sorted(self._fns, key=repr))

    def compiled_stream_cache_sizes(self) -> Dict[tuple, Optional[int]]:
        """Per-compiled-function jit cache sizes — the RecompileGuard
        probe: steady-state streaming must keep every entry at 1."""
        from pytorchvideo_accelerate_tpu.analysis.recompile_guard import (
            cache_size,
        )

        with self._lock:
            return {k: cache_size(fn) for k, fn in self._fns.items()}

    # --- hot-swap state carry ---------------------------------------------

    def carry_state_from(self, blue: "StreamingEngine") -> int:
        """Cutover-time state carry (`Scheduler.swap_engine` calls this
        UNDER the launch lock, fleet/hotswap.py): adopt the blue engine's
        session table and RAW-family ring pools (raw/slow frames are
        weight-independent), then re-derive every weight-DERIVED ring
        (tok / kv / hid / stem) under THIS engine's weights — cached
        activations must never outlive the weights that produced them.
        The KV/stem rebuild runs the masked trunk over each adopted raw
        ring with per-row offsets and position counters from the adopted
        table (fresh-establish semantics: the rebuilt state carries the
        current window's context only). Returns the number of carried
        sessions.

        Why cutover and not prewarm: blue keeps LAUNCHING during prewarm,
        and every blue stream advance DONATES its pool buffer — a pool
        adopted early would be a deleted jax array by the time green
        serves it (and sessions established after an early carry would be
        silently lost). Under the launch lock blue is quiesced, so the
        adopt is race-free; `prepare_carry_from` pre-compiles the
        re-derive + stream steps at prewarm time so the only cutover cost
        is bounded execution (measured in swap_blackout_ms, honestly)."""
        from pytorchvideo_accelerate_tpu.obs import trace

        # traced: the carry is the session-state handoff between engines
        # (the swap-timeline hop the trace-propagation rule guards)
        with trace.span("stream_state_carry", engine=self.name):
            self.table.adopt(blue.table)
            carried = len(self.table.sessions())
            with blue._lock:
                blue_pools = dict(blue._pools)
            # re-derive OUTSIDE self._lock: the compiled helpers take the
            # same non-reentrant lock on a compile-cache miss (a geometry
            # blue grew mid-prewarm), and the scheduler's launch lock
            # already serializes this whole carry against launches
            adopted = {}
            for geom, pool in blue_pools.items():
                adopted[geom] = self._derive_rings(geom, pool)
            with self._lock:
                for geom, mine in adopted.items():
                    mine["measured_bytes"] = sum(
                        int(getattr(mine[nm], "nbytes", 0))
                        for nm in self._ring_names if nm in mine)
                    prior = self._pools.pop(geom, None)
                    if prior is not None:
                        self._committed -= prior["bytes"]
                        obs_memory.release(
                            self._mem_component,
                            prior.get("measured_bytes", prior["bytes"]),
                            declared=prior["bytes"])
                    self._pools[geom] = mine
                    self._committed += mine["bytes"]
                    obs_memory.register(self._mem_component,
                                        mine["measured_bytes"],
                                        declared=mine["bytes"])
        # the adopted raw rings (and blue's freed derived rings) now
        # belong to THIS engine's ledger component; blue retires
        obs_memory.release(blue._mem_component)
        logger.info("stream: carried %d session(s), %d pool(s) across "
                    "hot-swap", carried, len(blue_pools))
        return carried

    def _derive_rings(self, geom, blue_pool) -> Dict[str, Any]:
        """Build THIS engine's ring dict for one adopted blue pool. Bytes
        are re-accounted under this engine's own `ring_bytes` (a
        trunk-mode mismatch across the swap changes the ring family —
        carry preserves sessions first; the budget honest-counts the new
        footprint)."""
        raw = blue_pool["raw"]
        rows = raw.shape[0]
        mine: Dict[str, Any] = {
            "cap": blue_pool["cap"],
            "bytes": rows * max(self.ring_bytes(geom), 1),
            "raw": raw,
        }
        if self.kind == "dual":
            # both rings are raw frames — weight-independent; a blue
            # without a slow ring (cross-family swap) gets one rebuilt
            # from the raw ring's phase-0 subsample
            slow = blue_pool.get("slow")
            if slow is None:
                slow = raw[:, ::self._tok_meta["alpha"]]
            mine["slow"] = slow
        elif self.kind == "tokens":
            mine["tok"] = self._reembed_pool(geom, raw)
            if self.trunk != "full":
                offs, tpos = self._pool_positions(geom, rows)
                derived = self._rebuild_fn(geom, rows)(
                    self.engine.params, raw, self._replicated(offs),
                    self._replicated(tpos))
                for nm, buf in zip(("kv", "kv_scale", "hid")
                                   if "kv_scale" in self._ring_names
                                   else ("kv", "hid"), derived):
                    mine[nm] = buf
        elif self.kind == "stem":
            offs, _ = self._pool_positions(geom, rows)
            mine["stem"] = self._rebuild_stem_fn(geom, rows)(
                self.engine.params, raw, self._replicated(offs))
        return mine

    def _pool_positions(self, geom, rows: int):
        """Per-pool-row (off, tpos) host arrays from the (already
        adopted) session table — rows without a live session get the
        just-established values (off 0, tpos T'), keeping their scratch
        content well-formed."""
        gran = self._tok_meta["tt"] if self.kind == "tokens" \
            else self._tok_meta["ts"]
        tn = geom[0] // gran
        offs = np.zeros((rows,), np.int32)
        tpos = np.full((rows,), tn, np.int32)
        for s in self.table.sessions():
            if s.pool_key == geom and s.slot < rows:
                offs[s.slot] = s.off
                tpos[s.slot] = (s.window + s.frames_seen) // gran
        return offs, tpos

    def _reembed_fn(self, rows: int):
        """Jitted whole-pool re-embed, cached per row count (compiled at
        `prepare_carry_from` so the cutover-time carry only executes)."""
        import jax

        key = ("reembed", rows)
        fn = self._fns.get(key)
        if fn is None:
            with self._lock:
                fn = self._fns.get(key)
                if fn is None:
                    def reembed(params, frames):
                        if self.quantization == "int8":
                            from pytorchvideo_accelerate_tpu.serving.quantize import (  # noqa: E501
                                dequantize_tree,
                            )

                            params = dequantize_tree(
                                params, self.engine._compute_dtype)
                        return self._embed_tokens(params, frames)

                    fn = jax.jit(reembed)
                    self._fns[key] = fn
        return fn

    def _reembed_pool(self, geom, raw):
        """Re-embed a whole raw pool ((rows, T, H, W, C)) into a token
        pool under this engine's params — one jitted batch (compiled in
        advance by `prepare_carry_from`)."""
        m = self._tok_meta
        tok = self._reembed_fn(raw.shape[0])(self.engine.params, raw)
        expect = (raw.shape[0], geom[0] // m["tt"],
                  (geom[1] // m["p"]) * (geom[2] // m["p"]), m["dim"])
        assert tuple(tok.shape) == expect, (tok.shape, expect)
        return tok

    def _rebuild_fn(self, geom, rows: int):
        """Jitted whole-pool KV/hidden rebuild under THIS engine's
        weights, cached per (geom, rows): re-embed every raw ring, roll
        each row to logical (oldest-first) order by its token offset,
        run the masked trunk with ring-slot-stable positions recovered
        from the per-row position counter (`tpos % T' == off//tt`), and
        roll the per-layer K/V + hidden results back to ring order."""
        import jax

        key = ("rebuild", geom, rows)
        fn = self._fns.get(key)
        if fn is not None:
            return fn

        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.serving.quantize import (
            dequantize_tree,
            quantize_kv,
        )

        m = self._tok_meta
        tt = m["tt"]
        tn = geom[0] // tt
        window = self._band_width(geom)
        int8 = "kv_scale" in self._ring_names

        def rebuild(params, raw, offs, tpos):
            if self.quantization == "int8":
                params = dequantize_tree(params,
                                         self.engine._compute_dtype)
            tok = self._embed_tokens(params, raw)     # ring order
            toffs = offs // tt
            tok_l = jax.vmap(lambda r, o: jnp.roll(r, -o, axis=0))(
                tok, toffs)
            slot_idx = (tpos[:, None]
                        + jnp.arange(tn, dtype=jnp.int32)[None, :]) % tn
            kv_l, hid_l = self._trunk_kv_full(params, tok_l, slot_idx,
                                              window, tn)
            kv_r = jax.vmap(lambda r, o: jnp.roll(r, o, axis=2))(
                kv_l, toffs)
            hid_r = jax.vmap(lambda r, o: jnp.roll(r, o, axis=0))(
                hid_l, toffs)
            if int8:
                q8, sc = quantize_kv(kv_r)
                return tok, q8, sc, hid_r
            return tok, kv_r, hid_r

        with self._lock:
            fn2 = self._fns.get(key)
            if fn2 is None:
                fn2 = jax.jit(lambda p, r, o, t:
                              rebuild(p, r, o, t)[1:])
                # tok rides the dedicated reembed fn; the rebuild returns
                # only the KV-family rings — but both share the embed
                # subgraph, so re-deriving tok separately costs one more
                # CubeEmbed pass at cutover (bounded, measured in
                # swap_blackout_ms)
                self._fns[key] = fn2
            fn = fn2
        return fn

    def _rebuild_stem_fn(self, geom, rows: int):
        """Jitted whole-pool stem rebuild under THIS engine's weights,
        cached per (geom, rows): roll each raw ring to logical order,
        run the model-padded stem conv (fresh-establish semantics — the
        oldest slot's halo is the stream edge), roll back to ring
        order."""
        import jax

        key = ("rebuild_stem", geom, rows)
        fn = self._fns.get(key)
        if fn is not None:
            return fn

        import jax.numpy as jnp

        from pytorchvideo_accelerate_tpu.serving.quantize import (
            dequantize_tree,
        )

        m = self._tok_meta
        ts, halo = m["ts"], m["halo"]

        def rebuild(params, raw, offs):
            if self.quantization == "int8":
                params = dequantize_tree(params,
                                         self.engine._compute_dtype)
            raw_l = jax.vmap(lambda r, o: jnp.roll(r, -o, axis=0))(
                raw, offs)
            stem_l = self._stem_embed(params, raw_l, (halo, halo))
            return jax.vmap(lambda r, o: jnp.roll(r, o, axis=0))(
                stem_l, offs // ts)

        with self._lock:
            fn2 = self._fns.get(key)
            if fn2 is None:
                fn2 = jax.jit(rebuild)
                self._fns[key] = fn2
            fn = fn2
        return fn

    def prepare_carry_from(self, blue: "StreamingEngine") -> int:
        """Prewarm half of the state carry (fleet/hotswap.prewarm_like):
        COMPILE every stream step the blue engine serves plus the
        whole-pool re-derives, by executing scratch/dummy calls — jax.jit
        is lazy, so merely constructing the wrappers would leave the
        first post-swap advance to compile on the flush thread (the cold
        start `warmup_stream` exists to prevent). Touches no blue
        buffer: blue keeps launching (and donating) during prewarm."""
        n = 0
        seen = set()
        for key in blue.compiled_stream_keys():
            if key[0] not in ("establish", "advance"):
                continue
            _, _, geom, stride, _ = key
            if (geom, stride) in seen:
                continue
            seen.add((geom, stride))
            t, h, w, c, _ = geom
            n += self.warmup_stream(t, h, w, c, stride)
        if self.kind in ("tokens", "stem"):
            with blue._lock:
                shapes = {g: p["raw"].shape for g, p in blue._pools.items()}
            for geom, shape in shapes.items():
                dummy = self._replicated(
                    np.zeros(shape, _np_dtype(geom[4])))
                rows = shape[0]
                if self.kind == "tokens":
                    self._reembed_pool(geom, dummy)
                    if self.trunk != "full":
                        zero = self._replicated(
                            np.zeros((rows,), np.int32))
                        tn = self._replicated(np.full(
                            (rows,), geom[0] // self._tok_meta["tt"],
                            np.int32))
                        self._rebuild_fn(geom, rows)(
                            self.engine.params, dummy, zero, tn)
                else:
                    zero = self._replicated(np.zeros((rows,), np.int32))
                    self._rebuild_stem_fn(geom, rows)(
                        self.engine.params, dummy, zero)
                n += 1
        return n

    def snapshot(self) -> Dict[str, float]:
        snap = self.table.snapshot()
        with self._lock:
            snap["stream_compiled"] = float(len(self._fns))
            snap["stream_pools"] = float(len(self._pools))
        return snap

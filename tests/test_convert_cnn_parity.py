"""Forward-NUMERICS parity for the CNN/MViT weight converters.

The VideoMAE converter is verified against the installed HF implementation
(tests/test_convert_videomae.py). pytorchvideo itself is not installed, so
for slowfast/slow/x3d/mvit this file builds minimal torch modules whose
module trees mirror pytorchvideo's (the exact state_dict names
models/convert.py maps: `blocks.0.multipathway_blocks...`,
`blocks.0.conv.conv_t...`, `cls_positional_encoding.pos_embed_spatial`, ...)
and whose forward math follows the published architectures (Feichtenhofer
2019 arXiv:1812.03982; Feichtenhofer 2020 arXiv:2004.04730; Fan 2021
arXiv:2104.11227) in torch's native NCDHW layout. Converting their
state_dicts and asserting activation parity against the flax models
exercises every layout decision the converter makes — conv OIDHW->DHWIO
transposes, grouped/depthwise channel order, BN param vs running-stat
routing, fusion concat order, SE wiring, MViT pos-embed synthesis and
per-head pool tiling — the failure modes that shape-only round-trips can't
see (a transposed-but-wrong kernel has the right shape).

Reference semantics cited from the call sites: run.py:105-118 (hub model +
head swap); BASELINE configs 2-4 name the x3d/mvit families.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402
from torch import nn  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pytorchvideo_accelerate_tpu.models.convert import (  # noqa: E402
    convert_state_dict,
)
from pytorchvideo_accelerate_tpu.models.mvit import MViT  # noqa: E402
from pytorchvideo_accelerate_tpu.models.resnet3d import SlowR50  # noqa: E402
from pytorchvideo_accelerate_tpu.models.slowfast import SlowFast  # noqa: E402
from pytorchvideo_accelerate_tpu.models.x3d import X3D  # noqa: E402


# --- shared helpers ---------------------------------------------------------

def _randomize(module: nn.Module, seed: int) -> None:
    """Random weights AND random BatchNorm running stats — converted
    running stats must land in flax batch_stats, and an identity
    running-stat (mean 0 / var 1) would hide a params/batch_stats swap."""
    g = torch.Generator().manual_seed(seed)
    with torch.no_grad():
        for p in module.parameters():
            p.copy_(torch.randn(p.shape, generator=g) * 0.1)
        for m in module.modules():
            if isinstance(m, nn.BatchNorm3d):
                m.running_mean.copy_(
                    torch.randn(m.running_mean.shape, generator=g) * 0.2)
                m.running_var.copy_(
                    torch.rand(m.running_var.shape, generator=g) * 0.5 + 0.75)


def _flat_paths(tree, prefix=()):
    out = set()
    for k, v in tree.items():
        if isinstance(v, dict):
            out |= _flat_paths(v, prefix + (k,))
        else:
            out.add("/".join(prefix + (k,)))
    return out


def _convert_and_check_coverage(torch_model, model_name, flax_variables):
    """state_dict -> flax tree; every flax leaf must be produced by the
    converter (no key silently skipped, no flax param left at init)."""
    sd = {k: v.numpy() for k, v in torch_model.state_dict().items()}
    tree = convert_state_dict(sd, model_name)
    assert tree["skipped"] == [], f"unmapped torch keys: {tree['skipped']}"
    for coll in ("params", "batch_stats"):
        want = _flat_paths(flax_variables.get(coll, {}))
        got = _flat_paths(tree.get(coll, {}))
        assert want == got, (
            f"{coll} coverage mismatch:\n missing={sorted(want - got)}\n"
            f" extra={sorted(got - want)}")
    return tree


def _nchw(x):  # (B, T, H, W, C) numpy -> torch NCDHW
    return torch.from_numpy(np.transpose(x, (0, 4, 1, 2, 3)))


# --- torch building blocks (pytorchvideo module-tree mirrors) ---------------

class TConvBN(nn.Module):
    """conv (padding k//2, no bias) + BN — stem/fusion unit; keys conv.*/norm.*"""

    def __init__(self, cin, cout, k, s=(1, 1, 1), groups=1):
        super().__init__()
        self.conv = nn.Conv3d(cin, cout, k, stride=s,
                              padding=tuple(kk // 2 for kk in k),
                              groups=groups, bias=False)
        self.norm = nn.BatchNorm3d(cout)

    def forward(self, x, act=True):
        x = self.norm(self.conv(x))
        return F.relu(x) if act else x


class TBranch2(nn.Module):
    """Bottleneck conv_a/conv_b/conv_c with norms named norm_a/b/c."""

    def __init__(self, cin, inner, cout, tk, stride):
        super().__init__()
        self.conv_a = nn.Conv3d(cin, inner, (tk, 1, 1),
                                padding=(tk // 2, 0, 0), bias=False)
        self.norm_a = nn.BatchNorm3d(inner)
        self.conv_b = nn.Conv3d(inner, inner, (1, 3, 3),
                                stride=(1, stride, stride),
                                padding=(0, 1, 1), bias=False)
        self.norm_b = nn.BatchNorm3d(inner)
        self.conv_c = nn.Conv3d(inner, cout, 1, bias=False)
        self.norm_c = nn.BatchNorm3d(cout)

    def forward(self, x):
        x = F.relu(self.norm_a(self.conv_a(x)))
        x = F.relu(self.norm_b(self.conv_b(x)))
        return self.norm_c(self.conv_c(x))


class TResBlock(nn.Module):
    def __init__(self, cin, inner, cout, tk, stride):
        super().__init__()
        if cin != cout or stride != 1:
            self.branch1_conv = nn.Conv3d(cin, cout, 1,
                                          stride=(1, stride, stride), bias=False)
            self.branch1_norm = nn.BatchNorm3d(cout)
        self.branch2 = TBranch2(cin, inner, cout, tk, stride)

    def forward(self, x):
        res = x
        if hasattr(self, "branch1_conv"):
            res = self.branch1_norm(self.branch1_conv(x))
        return F.relu(res + self.branch2(x))


class TStage(nn.Module):
    def __init__(self, cin, inner, cout, tk, stride, depth):
        super().__init__()
        self.res_blocks = nn.ModuleList(
            [TResBlock(cin if i == 0 else cout, inner, cout, tk,
                       stride if i == 0 else 1) for i in range(depth)])

    def forward(self, x):
        for b in self.res_blocks:
            x = b(x)
        return x


class THead(nn.Module):
    def __init__(self, cin, n):
        super().__init__()
        self.proj = nn.Linear(cin, n)


def _stem_pool(x):
    return F.max_pool3d(x, (1, 3, 3), (1, 2, 2), (0, 1, 1))


# --- Slow-R50 ---------------------------------------------------------------

class TorchSlowTiny(nn.Module):
    """2-stage slow pathway; state_dict names = pytorchvideo create_resnet
    (blocks.0 stem, blocks.N stages, blocks.5 head proj)."""

    def __init__(self, n_classes=5):
        super().__init__()
        self.blocks = nn.ModuleDict({
            "0": TConvBN(3, 8, (1, 7, 7), (1, 2, 2)),
            "1": TStage(8, 8, 32, 1, 1, depth=1),
            "2": TStage(32, 16, 64, 3, 2, depth=1),
            "5": THead(64, n_classes),
        })

    def forward(self, x):
        x = _stem_pool(self.blocks["0"](x))
        x = self.blocks["2"](self.blocks["1"](x))
        x = x.mean(dim=(2, 3, 4))
        return self.blocks["5"].proj(x)


def test_slow_r50_forward_parity():
    tm = TorchSlowTiny().eval()
    _randomize(tm, 0)
    x = np.random.default_rng(0).standard_normal((2, 4, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        theirs = tm(_nchw(x)).numpy()

    fm = SlowR50(num_classes=5, depths=(1, 1), stem_features=8,
                 temporal_kernels=(1, 3), dropout_rate=0.0)
    variables = fm.init(jax.random.key(0), jnp.asarray(x))
    tree = _convert_and_check_coverage(tm, "slow_r50", variables)
    ours = fm.apply({"params": tree["params"],
                     "batch_stats": tree["batch_stats"]}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)


# --- SlowFast ---------------------------------------------------------------

class TFuse(nn.Module):
    """FuseFastToSlow: (7,1,1) conv stride (alpha,1,1) to 2x fast channels;
    keys conv_fast_to_slow.weight + norm.*; cat([slow, lateral])."""

    def __init__(self, fast_ch, alpha, ratio=2):
        super().__init__()
        self.conv_fast_to_slow = nn.Conv3d(
            fast_ch, fast_ch * ratio, (7, 1, 1), stride=(alpha, 1, 1),
            padding=(3, 0, 0), bias=False)
        self.norm = nn.BatchNorm3d(fast_ch * ratio)

    def forward(self, slow, fast):
        lat = F.relu(self.norm(self.conv_fast_to_slow(fast)))
        return torch.cat([slow, lat], dim=1), fast


class TMultiPath(nn.Module):
    def __init__(self, slow_mod, fast_mod, fusion=None):
        super().__init__()
        self.multipathway_blocks = nn.ModuleList([slow_mod, fast_mod])
        if fusion is not None:
            self.multipathway_fusion = fusion


class TorchSlowFastTiny(nn.Module):
    """depths (1,1), stem 8, beta_inv 4 (fast stem 2), alpha 2. Names =
    pytorchvideo create_slowfast; head at blocks.6 (blocks.5 is the
    parameterless pool block)."""

    def __init__(self, n_classes=5):
        super().__init__()
        self.blocks = nn.ModuleDict({
            "0": TMultiPath(TConvBN(3, 8, (1, 7, 7), (1, 2, 2)),
                            TConvBN(3, 2, (5, 7, 7), (1, 2, 2)),
                            TFuse(2, alpha=2)),
            # slow res2 input: 8 stem + 4 fused lateral = 12
            "1": TMultiPath(TStage(12, 8, 32, 1, 1, depth=1),
                            TStage(2, 2, 8, 3, 1, depth=1),
                            TFuse(8, alpha=2)),
            # slow res3 input: 32 + 16 lateral = 48
            "2": TMultiPath(TStage(48, 16, 64, 3, 2, depth=1),
                            TStage(8, 4, 16, 3, 2, depth=1)),
            "6": THead(64 + 16, n_classes),
        })

    def forward(self, slow, fast):
        b0 = self.blocks["0"]
        slow = _stem_pool(b0.multipathway_blocks[0](slow))
        fast = _stem_pool(b0.multipathway_blocks[1](fast))
        slow, fast = b0.multipathway_fusion(slow, fast)
        for name in ("1", "2"):
            blk = self.blocks[name]
            slow = blk.multipathway_blocks[0](slow)
            fast = blk.multipathway_blocks[1](fast)
            if hasattr(blk, "multipathway_fusion"):
                slow, fast = blk.multipathway_fusion(slow, fast)
        pooled = torch.cat([slow.mean(dim=(2, 3, 4)), fast.mean(dim=(2, 3, 4))],
                           dim=1)
        return self.blocks["6"].proj(pooled)


# the stems' TPU lowering (ops/lane_fold.py) forced on the CPU, at a width
# its shape rule takes (128 = 64 columns x stride 2 of the 2-channel fast
# stem): converted weights load into the same tree and give the same numbers
@pytest.mark.parametrize("stems, width", [("nn_conv", 16), ("lane_fold", 128)])
def test_slowfast_forward_parity(monkeypatch, stems, width):
    from pytorchvideo_accelerate_tpu.ops import lane_fold

    monkeypatch.setattr(lane_fold, "takes_fold", lambda: stems == "lane_fold")
    tm = TorchSlowFastTiny().eval()
    _randomize(tm, 1)
    rng = np.random.default_rng(1)
    fast_np = rng.standard_normal((2, 8, 16, width, 3)).astype(np.float32)
    slow_np = fast_np[:, ::2]  # alpha=2
    with torch.no_grad():
        theirs = tm(_nchw(slow_np), _nchw(fast_np)).numpy()

    fm = SlowFast(num_classes=5, depths=(1, 1), alpha=2, beta_inv=4,
                  stem_features=8, slow_temporal_kernels=(1, 3),
                  dropout_rate=0.0)
    pathways = (jnp.asarray(slow_np), jnp.asarray(fast_np))
    variables = fm.init(jax.random.key(0), pathways)
    tree = _convert_and_check_coverage(tm, "slowfast_r50", variables)
    with lane_fold.count_sites() as folded:
        ours = fm.apply({"params": tree["params"],
                         "batch_stats": tree["batch_stats"]}, pathways)
    assert ({("slow_stem",), ("fast_stem",)} <= folded) == (stems == "lane_fold")
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)


# --- X3D --------------------------------------------------------------------

class TSE(nn.Module):
    def __init__(self, ch, se_ch):
        super().__init__()
        self.fc1 = nn.Conv3d(ch, se_ch, 1)
        self.fc2 = nn.Conv3d(se_ch, ch, 1)

    def forward(self, x):
        s = x.mean(dim=(2, 3, 4), keepdim=True)
        return x * torch.sigmoid(self.fc2(F.relu(self.fc1(s))))


class TX3DBlock(nn.Module):
    """Inverted bottleneck; norm_b = Sequential(BN, SE) on SE blocks (the
    pytorchvideo key quirk: norm_b.0.* / norm_b.1.fc1.*)."""

    def __init__(self, cin, inner, cout, stride, use_se):
        super().__init__()
        if cin != cout or stride != 1:
            self.branch1_conv = nn.Conv3d(cin, cout, 1,
                                          stride=(1, stride, stride), bias=False)
            # pytorchvideo create_x3d_res_block: branch1_norm only on
            # CHANNEL change — the stride-only shortcut (stage-1 block 0 of
            # the hub checkpoints) is a bare conv
            if cin != cout:
                self.branch1_norm = nn.BatchNorm3d(cout)
        self.branch2 = nn.Module()
        self.branch2.conv_a = nn.Conv3d(cin, inner, 1, bias=False)
        self.branch2.norm_a = nn.BatchNorm3d(inner)
        self.branch2.conv_b = nn.Conv3d(inner, inner, 3,
                                        stride=(1, stride, stride),
                                        padding=1, groups=inner, bias=False)
        self.branch2.norm_b = (nn.Sequential(nn.BatchNorm3d(inner), TSE(inner, 8))
                               if use_se else nn.BatchNorm3d(inner))
        self.branch2.conv_c = nn.Conv3d(inner, cout, 1, bias=False)
        self.branch2.norm_c = nn.BatchNorm3d(cout)

    def forward(self, x):
        res = x
        if hasattr(self, "branch1_conv"):
            res = self.branch1_conv(x)
            if hasattr(self, "branch1_norm"):
                res = self.branch1_norm(res)
        b = self.branch2
        y = F.relu(b.norm_a(b.conv_a(x)))
        y = b.norm_b(b.conv_b(y))
        y = F.silu(y)
        y = b.norm_c(b.conv_c(y))
        return F.relu(res + y)


class TX3DStemConv(nn.Module):
    """pytorchvideo Conv2plus1d quirk: conv_t holds the SPATIAL conv,
    conv_xy the depthwise temporal conv (convert.py _X3D_STEM)."""

    def __init__(self, ch):
        super().__init__()
        self.conv_t = nn.Conv3d(3, ch, (1, 3, 3), stride=(1, 2, 2),
                                padding=(0, 1, 1), bias=False)
        self.conv_xy = nn.Conv3d(ch, ch, (5, 1, 1), padding=(2, 0, 0),
                                 groups=ch, bias=False)

    def forward(self, x):
        return self.conv_xy(self.conv_t(x))


class TX3DStem(nn.Module):
    def __init__(self, ch):
        super().__init__()
        self.conv = TX3DStemConv(ch)
        self.norm = nn.BatchNorm3d(ch)

    def forward(self, x):
        return F.relu(self.norm(self.conv(x)))


class TX3DStage(nn.Module):
    def __init__(self, blocks):
        super().__init__()
        self.res_blocks = nn.ModuleList(blocks)

    def forward(self, x):
        for b in self.res_blocks:
            x = b(x)
        return x


class TX3DHead(nn.Module):
    """ProjectedPool order: pre_conv/BN/relu -> GLOBAL POOL -> post_conv ->
    relu -> proj (X3D paper: the 2048-d projection runs on pooled features)."""

    def __init__(self, cin, inner, out, n_classes):
        super().__init__()
        self.pool = nn.Module()
        self.pool.pre_conv = nn.Conv3d(cin, inner, 1, bias=False)
        self.pool.pre_norm = nn.BatchNorm3d(inner)
        self.pool.post_conv = nn.Conv3d(inner, out, 1, bias=False)
        self.proj = nn.Linear(out, n_classes)

    def forward(self, x):
        x = F.relu(self.pool.pre_norm(self.pool.pre_conv(x)))
        x = x.mean(dim=(2, 3, 4), keepdim=True)
        x = F.relu(self.pool.post_conv(x))
        return self.proj(x.flatten(1))


class TorchX3DTiny(nn.Module):
    def __init__(self, n_classes=5):
        super().__init__()
        self.blocks = nn.ModuleDict({
            "0": TX3DStem(8),
            # stage features (8, 16), expansion 2.25 -> inner 18 / 36;
            # SE on even blocks (i % 2 == 0)
            "1": TX3DStage([TX3DBlock(8, 18, 8, 2, True)]),
            "2": TX3DStage([TX3DBlock(8, 36, 16, 2, True),
                            TX3DBlock(16, 36, 16, 1, False)]),
            "5": TX3DHead(16, 36, 32, n_classes),
        })

    def forward(self, x):
        x = self.blocks["0"](x)
        x = self.blocks["2"](self.blocks["1"](x))
        return self.blocks["5"](x)


def test_x3d_forward_parity():
    tm = TorchX3DTiny().eval()
    _randomize(tm, 2)
    x = np.random.default_rng(2).standard_normal((2, 4, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        theirs = tm(_nchw(x)).numpy()

    fm = X3D(num_classes=5, depths=(1, 2), stem_features=8,
             stage_features=(8, 16), head_features=32, dropout_rate=0.0)
    variables = fm.init(jax.random.key(0), jnp.asarray(x))
    tree = _convert_and_check_coverage(tm, "x3d_s", variables)
    ours = fm.apply({"params": tree["params"],
                     "batch_stats": tree["batch_stats"]}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)


# --- MViT -------------------------------------------------------------------

class TMViTAttn(nn.Module):
    """Pooling attention, pytorchvideo MultiScaleAttention semantics: fused
    qkv, per-head depthwise pool conv + LayerNorm(head_dim), residual
    Q-pooling; keys attn.{qkv,proj,pool_q,norm_q,pool_k,norm_k,pool_v,norm_v}."""

    def __init__(self, dim, heads, q_stride, kv_stride):
        super().__init__()
        self.heads, self.hd = heads, dim // heads
        self.q_stride, self.kv_stride = q_stride, kv_stride
        self.qkv = nn.Linear(dim, 3 * dim)
        self.proj = nn.Linear(dim, dim)
        if q_stride != (1, 1, 1):
            self.pool_q = nn.Conv3d(self.hd, self.hd, 3, stride=q_stride,
                                    padding=1, groups=self.hd, bias=False)
            self.norm_q = nn.LayerNorm(self.hd, eps=1e-6)
        # pytorchvideo hands the 3^3 pool_kvq_kernel to every block once
        # adaptive kv pooling is configured: K/V pool convs exist at ALL
        # blocks of the hub MViT-B, stride-1 last-stage blocks included
        self.pool_k = nn.Conv3d(self.hd, self.hd, 3, stride=kv_stride,
                                padding=1, groups=self.hd, bias=False)
        self.norm_k = nn.LayerNorm(self.hd, eps=1e-6)
        self.pool_v = nn.Conv3d(self.hd, self.hd, 3, stride=kv_stride,
                                padding=1, groups=self.hd, bias=False)
        self.norm_v = nn.LayerNorm(self.hd, eps=1e-6)

    def _pool(self, t, conv, norm, thw):
        # (B, h, L, hd) -> fold heads into batch -> conv on the grid -> LN
        if conv is None:
            return t, thw
        B, h, L, hd = t.shape
        T, H, W = thw
        g = t.reshape(B * h, T, H, W, hd).permute(0, 4, 1, 2, 3)
        g = conv(g)
        T2, H2, W2 = g.shape[2:]
        t = g.permute(0, 2, 3, 4, 1).reshape(B, h, T2 * H2 * W2, hd)
        return norm(t), (T2, H2, W2)

    def forward(self, x, thw):
        B, L, C = x.shape
        qkv = (self.qkv(x).reshape(B, L, 3, self.heads, self.hd)
               .permute(2, 0, 3, 1, 4))
        q, k, v = qkv[0], qkv[1], qkv[2]
        q, q_thw = self._pool(q, getattr(self, "pool_q", None),
                              getattr(self, "norm_q", None), thw)
        k, _ = self._pool(k, getattr(self, "pool_k", None),
                          getattr(self, "norm_k", None), thw)
        v, _ = self._pool(v, getattr(self, "pool_v", None),
                          getattr(self, "norm_v", None), thw)
        attn = (q @ k.transpose(-2, -1)) * self.hd ** -0.5
        out = attn.softmax(dim=-1) @ v
        out = out + q  # residual Q-pooling
        out = out.transpose(1, 2).reshape(B, -1, C)
        return self.proj(out), q_thw


class TMlp(nn.Module):
    def __init__(self, dim, hidden, out):
        super().__init__()
        self.fc1 = nn.Linear(dim, hidden)
        self.fc2 = nn.Linear(hidden, out)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x)))


class TMViTBlock(nn.Module):
    """MultiScaleBlock, dim_mul_in_att=False: attention at the input dim,
    channel change in the MLP, skip projected from norm2(x) on dim-change
    blocks, skip max-pool kernel = stride+1."""

    def __init__(self, dim, dim_out, heads, q_stride, kv_stride):
        super().__init__()
        self.q_stride = q_stride
        self.norm1 = nn.LayerNorm(dim, eps=1e-6)
        self.attn = TMViTAttn(dim, heads, q_stride, kv_stride)
        self.norm2 = nn.LayerNorm(dim, eps=1e-6)
        self.mlp = TMlp(dim, int(dim * 4), dim_out)
        if dim != dim_out:
            self.proj = nn.Linear(dim, dim_out)

    def forward(self, x, thw):
        y, new_thw = self.attn(self.norm1(x), thw)
        if self.q_stride != (1, 1, 1):
            B, L, C = x.shape
            T, H, W = thw
            kernel = tuple(s + 1 if s > 1 else s for s in self.q_stride)
            g = x.transpose(1, 2).reshape(B, C, T, H, W)
            g = F.max_pool3d(g, kernel, self.q_stride,
                             tuple(k // 2 for k in kernel))
            x = g.flatten(2).transpose(1, 2)
        x = x + y
        xn = self.norm2(x)
        m = self.mlp(xn)
        if hasattr(self, "proj"):
            x = self.proj(xn)
        return x + m, new_thw


class TorchMViTTiny(nn.Module):
    """depth 3, dim 8->16 entering block 1, heads 1->2, kv stride (1,2,2)
    halving at the stage start; separable pos embeds, no CLS token
    (cls_embed_on=False — head mean-pools)."""

    def __init__(self, n_classes=5, grid=(2, 4, 4)):
        super().__init__()
        self.grid = grid
        T, H, W = grid
        self.patch_embed = nn.Module()
        self.patch_embed.patch_model = nn.Conv3d(
            3, 8, (3, 7, 7), stride=(2, 4, 4), padding=(1, 3, 3))
        self.cls_positional_encoding = nn.Module()
        self.cls_positional_encoding.pos_embed_spatial = nn.Parameter(
            torch.zeros(1, H * W, 8))
        self.cls_positional_encoding.pos_embed_temporal = nn.Parameter(
            torch.zeros(1, T, 8))
        self.blocks = nn.ModuleList([
            TMViTBlock(8, 16, 1, (1, 1, 1), (1, 2, 2)),
            TMViTBlock(16, 16, 2, (1, 2, 2), (1, 1, 1)),
            TMViTBlock(16, 16, 2, (1, 1, 1), (1, 1, 1)),
        ])
        self.norm = nn.LayerNorm(16, eps=1e-6)
        self.head = nn.Module()
        self.head.proj = nn.Linear(16, n_classes)

    def forward(self, x):
        x = self.patch_embed.patch_model(x)  # (B, 8, T, H, W)
        T, H, W = x.shape[2:]
        x = x.flatten(2).transpose(1, 2)  # t-major tokens
        enc = self.cls_positional_encoding
        pos = (enc.pos_embed_spatial.repeat(1, T, 1)
               + torch.repeat_interleave(enc.pos_embed_temporal, H * W, dim=1))
        x = x + pos
        thw = (T, H, W)
        for blk in self.blocks:
            x, thw = blk(x, thw)
        x = self.norm(x).mean(dim=1)
        return self.head.proj(x)


def test_mvit_forward_parity():
    tm = TorchMViTTiny().eval()
    _randomize(tm, 3)
    # give the pos embeds real values (zeros would hide synthesis bugs)
    g = torch.Generator().manual_seed(7)
    with torch.no_grad():
        enc = tm.cls_positional_encoding
        enc.pos_embed_spatial.copy_(
            torch.randn(enc.pos_embed_spatial.shape, generator=g) * 0.1)
        enc.pos_embed_temporal.copy_(
            torch.randn(enc.pos_embed_temporal.shape, generator=g) * 0.1)

    x = np.random.default_rng(3).standard_normal((2, 4, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        theirs = tm(_nchw(x)).numpy()

    fm = MViT(num_classes=5, depth=3, embed_dim=8, num_heads=1,
              stage_starts=(1,), initial_kv_stride=(1, 2, 2),
              drop_path_rate=0.0, dropout_rate=0.0,
              attention_backend="dense")
    variables = fm.init(jax.random.key(0), jnp.asarray(x))
    tree = _convert_and_check_coverage(tm, "mvit_b", variables)
    ours = fm.apply({"params": tree["params"]}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)


def test_mvit_pool_tiling_is_per_head():
    """The tiled depthwise pool kernel must repeat the (head_dim,) torch
    kernel across heads in head-major channel order — a head/dim-transposed
    tile would still have the right shape."""
    tm = TorchMViTTiny().eval()
    _randomize(tm, 4)
    sd = {k: v.numpy() for k, v in tm.state_dict().items()}
    tree = convert_state_dict(sd, "mvit_b")
    k_torch = sd["blocks.1.attn.pool_q.weight"]  # (hd, 1, 3, 3, 3), hd=8
    k_flax = tree["params"]["block1"]["attn"]["pool_q"]["pool"]["kernel"]
    assert k_flax.shape == (3, 3, 3, 1, 16)
    for h in range(2):
        np.testing.assert_array_equal(
            k_flax[..., 0, h * 8:(h + 1) * 8],
            np.transpose(k_torch, (2, 3, 4, 1, 0))[..., 0, :])


def test_mvit_pos_embed_interpolates_across_geometry(tmp_path):
    """Fine-tuning at a different clip length/resolution than the
    checkpoint: the (1,T,H,W,C) pos-embed is trilinear-resized on load, not
    discarded; every other weight loads exactly (shapes are geometry-free)."""
    from pytorchvideo_accelerate_tpu.models.convert import (
        load_pretrained, save_converted,
    )

    tm = TorchMViTTiny().eval()
    _randomize(tm, 5)
    with torch.no_grad():  # constant pos table: interpolation preserves it
        tm.cls_positional_encoding.pos_embed_spatial.fill_(0.25)
        tm.cls_positional_encoding.pos_embed_temporal.fill_(0.5)
    sd = {k: v.numpy() for k, v in tm.state_dict().items()}
    tree = convert_state_dict(sd, "mvit_b")
    npz = str(tmp_path / "mvit.npz")
    save_converted(tree, npz)

    # checkpoint grid (2,4,4); target model sees 8 frames @ 32^2 -> (4,8,8)
    fm = MViT(num_classes=5, depth=3, embed_dim=8, num_heads=1,
              stage_starts=(1,), initial_kv_stride=(1, 2, 2),
              drop_path_rate=0.0, dropout_rate=0.0)
    x = jnp.zeros((1, 8, 32, 32, 3), jnp.float32)
    variables = fm.init(jax.random.key(0), x)
    merged, report = load_pretrained(npz, variables)
    assert any(p.startswith("params/pos_embed") for p in report["interpolated"]), report
    assert "params/pos_embed" not in report["mismatched"]
    assert report["kept"] == [], report["kept"]
    pe = np.asarray(merged["params"]["pos_embed"])
    assert pe.shape == (1, 4, 8, 8, 8)
    # constant table resizes to the same constant (0.25 + 0.5)
    np.testing.assert_allclose(pe, 0.75, rtol=1e-5)
    # and the merged model runs at the new geometry
    out = fm.apply({"params": merged["params"]}, x)
    assert out.shape == (1, 5)


def test_pos_embed_downscale_matches_torch_interpolate():
    """Downscaling must match torch's trilinear F.interpolate (align_corners
    False, NO antialiasing) — the convention ViT-family fine-tune recipes
    were validated with."""
    from pytorchvideo_accelerate_tpu.models.convert import load_pretrained

    rng = np.random.default_rng(9)
    src = rng.standard_normal((1, 4, 8, 8, 8)).astype(np.float32)
    with torch.no_grad():
        ref = F.interpolate(
            torch.from_numpy(src).permute(0, 4, 1, 2, 3), size=(2, 4, 4),
            mode="trilinear", align_corners=False,
        ).permute(0, 2, 3, 4, 1).numpy()

    got = np.asarray(jax.image.resize(
        jnp.asarray(src), (1, 2, 4, 4, 8), "trilinear", antialias=False))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# --- R(2+1)D ----------------------------------------------------------------

class TConv2plus1d(nn.Module):
    """pytorchvideo Conv2plus1d container: conv_t = SPATIAL 1x3x3 factor
    (the swapped slot naming, as in the X3D stem), inner norm + ReLU,
    conv_xy = temporal 3x1x1 factor; spatial stride on the spatial factor,
    temporal stride on the temporal factor."""

    def __init__(self, ch, spatial_stride=1, temporal_stride=1):
        super().__init__()
        self.conv_t = nn.Conv3d(ch, ch, (1, 3, 3),
                                stride=(1, spatial_stride, spatial_stride),
                                padding=(0, 1, 1), bias=False)
        self.norm = nn.BatchNorm3d(ch)
        self.conv_xy = nn.Conv3d(ch, ch, (3, 1, 1),
                                 stride=(temporal_stride, 1, 1),
                                 padding=(1, 0, 0), bias=False)

    def forward(self, x):
        return self.conv_xy(F.relu(self.norm(self.conv_t(x))))


class TR2Branch2(nn.Module):
    """(2+1)D bottleneck branch2: conv_a 1x1x1 / conv_b Conv2plus1d /
    conv_c 1x1x1 with norms named norm_a/b/c."""

    def __init__(self, cin, inner, cout, ts, ss):
        super().__init__()
        self.conv_a = nn.Conv3d(cin, inner, 1, bias=False)
        self.norm_a = nn.BatchNorm3d(inner)
        self.conv_b = TConv2plus1d(inner, spatial_stride=ss, temporal_stride=ts)
        self.norm_b = nn.BatchNorm3d(inner)
        self.conv_c = nn.Conv3d(inner, cout, 1, bias=False)
        self.norm_c = nn.BatchNorm3d(cout)

    def forward(self, x):
        x = F.relu(self.norm_a(self.conv_a(x)))
        x = F.relu(self.norm_b(self.conv_b(x)))
        return self.norm_c(self.conv_c(x))


class TR2Block(nn.Module):
    def __init__(self, cin, inner, cout, ts, ss):
        super().__init__()
        if cin != cout or ss != 1 or ts != 1:
            self.branch1_conv = nn.Conv3d(cin, cout, 1, stride=(ts, ss, ss),
                                          bias=False)
            self.branch1_norm = nn.BatchNorm3d(cout)
        self.branch2 = TR2Branch2(cin, inner, cout, ts, ss)

    def forward(self, x):
        res = x
        if hasattr(self, "branch1_conv"):
            res = self.branch1_norm(self.branch1_conv(x))
        return F.relu(res + self.branch2(x))


class TR2Stage(nn.Module):
    def __init__(self, cin, inner, cout, ts, ss, depth):
        super().__init__()
        self.res_blocks = nn.ModuleList(
            [TR2Block(cin if i == 0 else cout, inner, cout,
                      ts if i == 0 else 1, ss if i == 0 else 1)
             for i in range(depth)])

    def forward(self, x):
        for b in self.res_blocks:
            x = b(x)
        return x


class TorchR2Plus1DTiny(nn.Module):
    """2-stage R(2+1)D; state_dict names = pytorchvideo create_r2plus1d
    (blocks.0 poolless stem, blocks.N stages, blocks.5 head proj). Stage 2
    carries BOTH a temporal and a spatial stride, so the converted branch1
    kernel rides a (2,2,2)-strided shortcut — the geometry the full model's
    res4/res5 entries use."""

    def __init__(self, n_classes=5):
        super().__init__()
        self.blocks = nn.ModuleDict({
            "0": TConvBN(3, 8, (1, 7, 7), (1, 2, 2)),
            "1": TR2Stage(8, 8, 32, 1, 2, depth=1),
            "2": TR2Stage(32, 16, 64, 2, 2, depth=2),
            "5": THead(64, n_classes),
        })

    def forward(self, x):
        x = self.blocks["0"](x)  # no stem pool in r2plus1d
        x = self.blocks["2"](self.blocks["1"](x))
        x = x.mean(dim=(2, 3, 4))
        return self.blocks["5"].proj(x)


def test_r2plus1d_forward_parity():
    from pytorchvideo_accelerate_tpu.models.r2plus1d import R2Plus1D

    tm = TorchR2Plus1DTiny().eval()
    _randomize(tm, 3)
    x = np.random.default_rng(3).standard_normal(
        (2, 4, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        theirs = tm(_nchw(x)).numpy()

    fm = R2Plus1D(num_classes=5, depths=(1, 2), stem_features=8,
                  spatial_strides=(2, 2), temporal_strides=(1, 2),
                  dropout_rate=0.0)
    variables = fm.init(jax.random.key(0), jnp.asarray(x))
    tree = _convert_and_check_coverage(tm, "r2plus1d_r50", variables)
    ours = fm.apply({"params": tree["params"],
                     "batch_stats": tree["batch_stats"]}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)


# --- ir-CSN -----------------------------------------------------------------

class TCSNBranch2(nn.Module):
    """CSN bottleneck branch2: 1x1x1 conv_a, DEPTHWISE 3x3x3 conv_b
    (groups=inner — both strides ride it), 1x1x1 conv_c; same key names
    as the plain resnet blocks."""

    def __init__(self, cin, inner, cout, ts, ss):
        super().__init__()
        self.conv_a = nn.Conv3d(cin, inner, 1, bias=False)
        self.norm_a = nn.BatchNorm3d(inner)
        self.conv_b = nn.Conv3d(inner, inner, 3, stride=(ts, ss, ss),
                                padding=1, groups=inner, bias=False)
        self.norm_b = nn.BatchNorm3d(inner)
        self.conv_c = nn.Conv3d(inner, cout, 1, bias=False)
        self.norm_c = nn.BatchNorm3d(cout)

    def forward(self, x):
        x = F.relu(self.norm_a(self.conv_a(x)))
        x = F.relu(self.norm_b(self.conv_b(x)))
        return self.norm_c(self.conv_c(x))


class TCSNBlock(nn.Module):
    def __init__(self, cin, inner, cout, ts, ss):
        super().__init__()
        if cin != cout or ss != 1 or ts != 1:
            self.branch1_conv = nn.Conv3d(cin, cout, 1, stride=(ts, ss, ss),
                                          bias=False)
            self.branch1_norm = nn.BatchNorm3d(cout)
        self.branch2 = TCSNBranch2(cin, inner, cout, ts, ss)

    def forward(self, x):
        res = x
        if hasattr(self, "branch1_conv"):
            res = self.branch1_norm(self.branch1_conv(x))
        return F.relu(res + self.branch2(x))


class TCSNStage(nn.Module):
    def __init__(self, cin, inner, cout, ts, ss, depth):
        super().__init__()
        self.res_blocks = nn.ModuleList(
            [TCSNBlock(cin if i == 0 else cout, inner, cout,
                       ts if i == 0 else 1, ss if i == 0 else 1)
             for i in range(depth)])

    def forward(self, x):
        for b in self.res_blocks:
            x = b(x)
        return x


class TorchCSNTiny(nn.Module):
    """2-stage ir-CSN; state_dict names = pytorchvideo create_csn =
    create_resnet skeleton ((3,7,7) stem + 1x3x3 maxpool). Stage 2 carries
    the (2,2,2) dual stride of the full model's res3/res4/res5 entries."""

    def __init__(self, n_classes=5):
        super().__init__()
        self.blocks = nn.ModuleDict({
            "0": TConvBN(3, 8, (3, 7, 7), (1, 2, 2)),
            "1": TCSNStage(8, 8, 32, 1, 1, depth=1),
            "2": TCSNStage(32, 16, 64, 2, 2, depth=2),
            "5": THead(64, n_classes),
        })

    def forward(self, x):
        x = _stem_pool(self.blocks["0"](x))
        x = self.blocks["2"](self.blocks["1"](x))
        x = x.mean(dim=(2, 3, 4))
        return self.blocks["5"].proj(x)


@pytest.mark.parametrize("impl", ["conv", "shift"])
def test_csn_forward_parity(impl):
    """Both depthwise lowerings must reproduce the torch grouped conv —
    the converted (kt,kh,kw,1,C) kernel feeds either path unchanged."""
    from pytorchvideo_accelerate_tpu.models.csn import CSN

    tm = TorchCSNTiny().eval()
    _randomize(tm, 7)
    x = np.random.default_rng(7).standard_normal(
        (2, 8, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        theirs = tm(_nchw(x)).numpy()

    fm = CSN(num_classes=5, depths=(1, 2), stem_features=8,
             spatial_strides=(1, 2), temporal_strides=(1, 2),
             dropout_rate=0.0, depthwise_impl=impl)
    variables = fm.init(jax.random.key(0), jnp.asarray(x))
    tree = _convert_and_check_coverage(tm, "csn_r101", variables)
    ours = fm.apply({"params": tree["params"],
                     "batch_stats": tree["batch_stats"]}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)


# --- C2D --------------------------------------------------------------------

class TorchC2DTiny(nn.Module):
    """2-stage c2d: the create_resnet skeleton with kernel-1 conv_a
    everywhere and the builder's parameterless (2,1,1) temporal max-pool
    after stage 1 (hub c2d_r50's stage1_pool)."""

    def __init__(self, n_classes=5):
        super().__init__()
        self.blocks = nn.ModuleDict({
            "0": TConvBN(3, 8, (1, 7, 7), (1, 2, 2)),
            "1": TStage(8, 8, 32, 1, 1, depth=1),
            "2": TStage(32, 16, 64, 1, 2, depth=1),
            "5": THead(64, n_classes),
        })

    def forward(self, x):
        x = _stem_pool(self.blocks["0"](x))
        x = self.blocks["1"](x)
        x = F.max_pool3d(x, (2, 1, 1), (2, 1, 1))
        x = self.blocks["2"](x)
        x = x.mean(dim=(2, 3, 4))
        return self.blocks["5"].proj(x)


def test_c2d_forward_parity():
    tm = TorchC2DTiny().eval()
    _randomize(tm, 11)
    x = np.random.default_rng(11).standard_normal(
        (2, 4, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        theirs = tm(_nchw(x)).numpy()

    fm = SlowR50(num_classes=5, depths=(1, 1), stem_features=8,
                 temporal_kernels=(1, 1), stage1_temporal_pool=True,
                 dropout_rate=0.0)
    variables = fm.init(jax.random.key(0), jnp.asarray(x))
    tree = _convert_and_check_coverage(tm, "c2d_r50", variables)
    ours = fm.apply({"params": tree["params"],
                     "batch_stats": tree["batch_stats"]}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-4)

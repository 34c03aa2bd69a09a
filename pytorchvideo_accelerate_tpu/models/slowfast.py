"""SlowFast networks (R50/R101), TPU-native.

Re-design of the `slowfast_r50` backbone the reference loads from torch.hub
(run.py:107: `make_slowfast_finetuner` -> hub `slowfast_r50`, head swapped to
`create_res_basic_head(in_features=2304, out_features=num_labels, pool=None)`
at run.py:109). Architecture per Feichtenhofer et al. 2019 (arXiv:1812.03982)
with pytorchvideo's instantiation constants:

- two pathways: Slow (T/alpha frames, C channels) and Fast (T frames, C/8
  channels, temporal convs throughout)
- lateral fast->slow fusion after stem, res2, res3, res4: a time-strided
  (7,1,1) conv, stride (alpha,1,1), to 2x fast channels, concatenated onto
  the slow feature
- head: per-pathway global average pool, concat (2048+256=2304) -> dropout
  -> linear

Input: `(slow, fast)` tuple from data.transforms PackPathway —
slow (B, T/alpha, H, W, 3), fast (B, T, H, W, 3), both NDHWC.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax.numpy as jnp
from flax import linen as nn

from pytorchvideo_accelerate_tpu.models.common import (
    ConvBNAct,
    ResStage,
    global_avg_pool,
    max_pool_3d,
)
from pytorchvideo_accelerate_tpu.models.heads import ResBasicHead


class FuseFastToSlow(nn.Module):
    """Time-strided conv lateral connection (paper §3.4; pytorchvideo
    FuseFastToSlow: kernel (7,1,1), stride (alpha,1,1), out 2x fast ch)."""

    fast_features: int
    alpha: int
    fusion_ratio: int = 2
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, slow, fast, train: bool = False):
        lateral = ConvBNAct(
            self.fast_features * self.fusion_ratio,
            kernel=(7, 1, 1),
            stride=(self.alpha, 1, 1),
            dtype=self.dtype,
            name="conv_f2s",
        )(fast, train)
        return jnp.concatenate([slow, lateral], axis=-1), fast


class SlowFast(nn.Module):
    num_classes: int
    depths: Tuple[int, ...] = (3, 4, 6, 3)  # r50; r101 = (3, 4, 23, 3)
    alpha: int = 4
    beta_inv: int = 8  # fast channels = slow / beta_inv
    fusion_ratio: int = 2
    stem_features: int = 64
    slow_temporal_kernels: Tuple[int, ...] = (1, 1, 3, 3)
    dropout_rate: float = 0.5
    # fused conv+BN+act lowering for the stride-1 bottleneck sites
    # (common.FUSED_MODES; ModelConfig.fused_kernels). Stems and lateral
    # fusions are strided and keep the unfused path regardless.
    fused: str = "off"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, pathways, train: bool = False):
        slow, fast = pathways
        slow = slow.astype(self.dtype)
        fast = fast.astype(self.dtype)

        fast_stem = self.stem_features // self.beta_inv  # 8 for r50
        # 3 -> 64 and 3 -> 8 channels: on the TPU ConvBNAct lowers both as
        # a lane-filling contraction (ops/lane_fold.py), by their shapes
        slow = ConvBNAct(
            self.stem_features, kernel=(1, 7, 7), stride=(1, 2, 2),
            dtype=self.dtype, name="slow_stem",
        )(slow, train)
        fast = ConvBNAct(
            fast_stem, kernel=(5, 7, 7), stride=(1, 2, 2),
            dtype=self.dtype, name="fast_stem",
        )(fast, train)
        slow = max_pool_3d(slow, (1, 3, 3), (1, 2, 2))
        fast = max_pool_3d(fast, (1, 3, 3), (1, 2, 2))
        slow, fast = FuseFastToSlow(
            fast_stem, self.alpha, self.fusion_ratio, self.dtype, name="fuse_stem"
        )(slow, fast, train)

        slow_inner, fast_inner = self.stem_features, fast_stem
        for stage_idx, depth in enumerate(self.depths):
            spatial_stride = 1 if stage_idx == 0 else 2
            slow = ResStage(
                depth=depth,
                features_inner=slow_inner,
                features_out=slow_inner * 4,
                temporal_kernel=self.slow_temporal_kernels[stage_idx],
                spatial_stride=spatial_stride,
                fused=self.fused,
                dtype=self.dtype,
                name=f"slow_res{stage_idx + 2}",
            )(slow, train)
            fast = ResStage(
                depth=depth,
                features_inner=fast_inner,
                features_out=fast_inner * 4,
                temporal_kernel=3,  # fast pathway: temporal convs everywhere
                spatial_stride=spatial_stride,
                fused=self.fused,
                dtype=self.dtype,
                name=f"fast_res{stage_idx + 2}",
            )(fast, train)
            if stage_idx < len(self.depths) - 1:  # no fusion after res5
                slow, fast = FuseFastToSlow(
                    fast_inner * 4, self.alpha, self.fusion_ratio, self.dtype,
                    name=f"fuse_res{stage_idx + 2}",
                )(slow, fast, train)
            slow_inner *= 2
            fast_inner *= 2

        # Pool per pathway then concat: 2048 + 256 = 2304, matching the
        # reference head's in_features=2304 with pool=None (run.py:109).
        pooled = jnp.concatenate(
            [global_avg_pool(slow), global_avg_pool(fast)], axis=-1
        )
        return ResBasicHead(
            num_classes=self.num_classes,
            dropout_rate=self.dropout_rate,
            pool=False,
            dtype=self.dtype,
            name="head",
        )(pooled, train)

    @staticmethod
    def backbone_param_filter(path: Tuple[str, ...]) -> bool:
        return path[0] != "head"

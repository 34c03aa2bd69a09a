#!/usr/bin/env bash
# ir-CSN-101 on Kinetics (hub csn_r101 family; Tran 2019 arXiv:1904.02811).
# Sampling per the hub card: 32 frames, stride 2, 224^2 crops. ~98% of
# FLOPs are 1x1x1 MXU matmuls; the depthwise 3x3x3 lowering is selected
# with --model.depthwise_impl shift|conv.
set -euo pipefail

python -m pytorchvideo_accelerate_tpu.run \
  --data_dir "${DATA_DIR:-/data/kinetics}" \
  --output_dir outputs_csn_r101 \
  --model.name csn_r101 \
  --num_frames 32 \
  --sampling_rate 2 \
  --data.crop_size 224 \
  --batch_size 8 \
  --num_workers 8 \
  --checkpointing_steps epoch \
  --with_tracking \
  "$@"

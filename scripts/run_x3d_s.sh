#!/usr/bin/env bash
# X3D-S on Kinetics (BASELINE config 2: single v5e chip, bf16).
# Sampling per the X3D paper's S config: 13 frames, stride 6, 160^2 crops.
# Pass --model.depthwise_impl shift to use the tap-decomposition
# depthwise-conv lowering.
set -euo pipefail

python -m pytorchvideo_accelerate_tpu.run \
  --data_dir "${DATA_DIR:-/data/kinetics}" \
  --output_dir outputs_x3d_s \
  --model.name x3d_s \
  --num_frames 13 \
  --sampling_rate 6 \
  --data.crop_size 160 \
  --data.min_short_side_scale 182 \
  --data.max_short_side_scale 228 \
  --batch_size 8 \
  --num_workers 8 \
  --checkpointing_steps epoch \
  --with_tracking \
  "$@"

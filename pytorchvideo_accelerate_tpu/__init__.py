"""TPU-native video action-recognition training framework.

A from-scratch JAX/XLA re-design of the capability surface of
``nateraw/pytorchvideo-accelerate`` (reference: ``/root/reference/run.py``):
distributed training of video models (SlowFast, Slow-R50, X3D, MViT, VideoMAE)
on Kinetics-style datasets.

Design stance (see SURVEY.md §7): instead of the reference's
Accelerator-object mutation API (``prepare``/``backward``/``gather``), the
framework is built around an explicit state pytree, pure compiled step
functions, and sharding declared on a ``jax.sharding.Mesh``:

- ``Accelerator.prepare``      -> mesh construction + NamedSharding rules
  (``parallel.mesh``, ``parallel.sharding``)
- ``accelerator.backward``+DDP -> ``jax.value_and_grad`` inside a jitted step;
  the gradient all-reduce is implied by sharded autodiff (``trainer.steps``)
- AMP GradScaler               -> bf16 compute / fp32 params, no loss scaling
- ``accelerator.save_state``   -> orbax checkpointing (``trainer.checkpoint``)
- tracker multiplexer          -> host-0 writers (``trainer.tracking``)
- ``accelerate launch``        -> per-host runner + ``jax.distributed``
  (``parallel.distributed``, ``launch.py``)
"""

__version__ = "0.5.0"  # keep in sync with pyproject.toml

from pytorchvideo_accelerate_tpu.config import (  # noqa: F401
    CheckpointConfig,
    DataConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    TrackingConfig,
    TrainConfig,
)

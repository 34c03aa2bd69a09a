"""CLI entrypoint.

Usage (reference-compatible flags, SURVEY §2.1-R1):

    python -m pytorchvideo_accelerate_tpu.run --data_dir /data/kinetics \\
        --is_slowfast --num_frames 32 --sampling_rate 2 --batch_size 8 \\
        --gradient_accumulation_steps 4 --with_tracking \\
        --checkpointing_steps epoch

or with dotted flags (--optim.lr 0.1, --mesh.fsdp 2, ...). Replaces
`accelerate launch run.py <flags>` (run_slowfast_r50.sh): no separate
launcher is needed on TPU — single-host runs start directly; pod runs start
one process per host (the pod scheduler's job) and self-configure via
`jax.distributed` (parallel/distributed.py).

`--write_config out.json` resolves all flags/config files into one JSON and
exits — the `accelerate config` workflow (persist once, reuse via
`--config out.json`, override per run with flags).
"""

from __future__ import annotations

from typing import Optional, Sequence

from pytorchvideo_accelerate_tpu.config import parse_cli
from pytorchvideo_accelerate_tpu.trainer.loop import Trainer
from pytorchvideo_accelerate_tpu.utils.compile_cache import enable_compile_cache


def main(argv: Optional[Sequence[str]] = None) -> dict:
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    write_to = None
    rest = []
    i = 0
    while i < len(argv):  # both --write_config PATH and --write_config=PATH
        tok = argv[i]
        key = tok[2:].split("=", 1)[0].replace("-", "_") if tok.startswith("--") else ""
        if key == "write_config":
            if "=" in tok:
                write_to = tok.split("=", 1)[1]
                i += 1
            else:
                if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
                    raise SystemExit(f"{tok} requires a file path")
                write_to = argv[i + 1]
                i += 2
            if not write_to:
                raise SystemExit(f"{tok} requires a file path")
        else:
            rest.append(tok)
            i += 1
    argv = rest

    cfg = parse_cli(argv)
    if write_to is not None:
        with open(write_to, "w") as f:
            f.write(cfg.to_json() + "\n")
        print(f"wrote resolved config to {write_to} "
              f"(reuse with --config {write_to})")
        return {"config_written": write_to}
    enable_compile_cache()
    trainer = Trainer(cfg)
    if cfg.export_inference:
        # checkpoint -> serving artifact, no training: resume (when
        # configured) then write the params-only EMA-resolved export
        try:
            trainer._maybe_resume()
            out = trainer.export_inference(cfg.export_inference)
        finally:
            trainer.close()
        print(f"wrote inference artifact to {out} "
              f"(serve with pva-tpu-serve --serve.checkpoint {out})")
        return {"exported": out}
    if cfg.eval_only:
        return trainer.evaluate()
    return trainer.fit()


if __name__ == "__main__":
    main()

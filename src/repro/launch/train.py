"""Training launcher.

Reduced config by default, real training loop with checkpoints:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 50

``--full`` trains the architecture at its published widths. The trainer
runs on one device: this launcher builds no mesh.
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=configs.list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced config (CPU demo)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--peak-lr", type=float, default=1e-3)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (FT demo)")
    ap.add_argument("--tile-plans", default=None,
                    help="compiled TilePlan artifact (JSON); corrupt/missing "
                         "degrades to heuristic tiles")
    ap.add_argument("--hardware", default="",
                    help="hardware model to resolve tiles for "
                         "(default: production target)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_arch(args.arch))
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch)
    tcfg = TrainerConfig(
        steps=args.steps, checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir, peak_lr=args.peak_lr,
        microbatches=args.microbatches, log_every=10,
        tile_plans=args.tile_plans, hardware=args.hardware,
    )
    trainer = Trainer(cfg, data_cfg, tcfg,
                      opt_cfg=adamw.AdamWConfig(weight_decay=0.01))
    out = trainer.run(fail_at=args.fail_at)
    print(f"final loss: {out['losses'][-1]:.4f}  "
          f"restarts: {out['restarts']}  "
          f"stragglers: {out['straggler_events']}")


if __name__ == "__main__":
    main()

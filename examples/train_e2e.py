"""End-to-end training driver: a ~100M-parameter dense LM trained on
synthetic data with the full substrate (grad accumulation, AdamW +
warmup-cosine, async checkpointing, fault injection + restart).

Defaults are scaled for CPU smoke execution; pass --full for the
100M x few-hundred-steps configuration the deliverable describes.

    PYTHONPATH=src python examples/train_e2e.py [--full] [--steps N]
"""

import argparse
import dataclasses

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.train.step import TrainConfig
from repro.train.trainer import Trainer, TrainerConfig

LM_100M = ModelConfig(
    name="repro-100m", family="dense",
    n_layers=10, d_model=640, n_heads=10, n_kv_heads=5,
    d_ff=2560, vocab_size=32768, rope_theta=1e4,
).validate()

LM_TINY = dataclasses.replace(
    LM_100M, name="repro-tiny", n_layers=4, d_model=256, n_heads=4,
    n_kv_heads=2, d_ff=1024, vocab_size=2048)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="100M params, batch 16 x 512 tokens")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    cfg = LM_100M if args.full else LM_TINY
    steps = args.steps or (300 if args.full else 30)
    tcfg = TrainerConfig(
        steps=steps,
        global_batch=16 if args.full else 4,
        seq_len=512 if args.full else 128,
        ckpt_dir=args.ckpt, ckpt_every=max(steps // 5, 10),
        train=TrainConfig(accum_steps=2, peak_lr=6e-4,
                          warmup=max(steps // 10, 5), total_steps=steps,
                          dtype=jnp.float32))
    print(f"model: {cfg.name} ({cfg.param_count() / 1e6:.0f}M params), "
          f"{steps} steps")
    trainer = Trainer(cfg, tcfg)
    out = trainer.run()
    losses = out["losses"]
    head = sum(losses[:5]) / min(len(losses), 5)
    tail = sum(losses[-5:]) / min(len(losses), 5)
    print(f"loss: {head:.3f} -> {tail:.3f} "
          f"({out['wall_s']:.0f}s; ckpt at {args.ckpt})")
    if steps >= 30:
        assert tail < head, "training must reduce the loss"
    else:
        print("(fewer than 30 steps: loss-decrease check skipped)")


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()

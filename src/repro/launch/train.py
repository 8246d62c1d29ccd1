"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

Single-host driver wired for the production posture: sharded params/optimizer
under the ambient mesh, deterministic resumable data, async checkpointing,
preemption guard, straggler watch, loss-spike rewind (see train/loop.py).
On this CPU container use --smoke (reduced config); full configs are exercised
via launch.dryrun.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.kernels import backend
from repro.optim import adamw
from repro.train.loop import TrainConfig, TrainLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    backend.enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data_cfg = DataConfig(
        seed=0, global_batch=args.global_batch, seq_len=args.seq_len,
        vocab_size=cfg.vocab_size,
        frontend=cfg.frontend,
        n_extra=(4 if cfg.frontend == "patch"
                 else args.seq_len // cfg.enc_ratio if cfg.frontend == "frame" else 0),
        d_model=cfg.d_model,
    )
    train_cfg = TrainConfig(
        steps=args.steps, ckpt_every=max(args.steps // 4, 1),
        ckpt_dir=args.ckpt_dir, microbatches=args.microbatches,
    )
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                                total_steps=args.steps)
    loop = TrainLoop(cfg, data_cfg, train_cfg, opt_cfg)
    loop.guard.__init__(install=True)  # SIGTERM -> checkpoint + clean exit
    params, _, history = loop.run(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} steps={len(history)} "
          f"first_loss={history[0]['loss']:.3f} last_loss={history[-1]['loss']:.3f}")
    if loop.straggler.flagged_steps:
        print(f"straggler-flagged steps: {loop.straggler.flagged_steps}")


if __name__ == "__main__":
    main()

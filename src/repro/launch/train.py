"""Training launcher.

Reduced/smoke scale (CPU, default):
  python -m repro.launch.train --arch olmo-1b --smoke --steps 50

Production mesh shapes are exercised AOT via repro.launch.dryrun; on a
real TPU pod this same entry point runs them live:
  python -m repro.launch.train --arch gemma2-27b --shape train_4k
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import INPUT_SHAPES, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim import adamw
from repro.sharding.partition import resolve, train_rules
from repro.training.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, host devices")
    ap.add_argument("--shape", default="train_4k", choices=list(INPUT_SHAPES))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        cfg = get_smoke_config(args.arch)
        batch = args.batch or 4
        seq = args.seq or 64
        mesh = None
        rules = None
    else:
        cfg = get_config(args.arch)
        shape = INPUT_SHAPES[args.shape]
        batch = args.batch or shape.global_batch
        seq = args.seq or shape.seq_len
        mesh = make_production_mesh()
        rules = resolve(train_rules(), mesh)

    tcfg = TrainerConfig(steps=args.steps, batch_size=batch, seq_len=seq,
                         ckpt_dir=args.ckpt_dir)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                                total_steps=args.steps)
    trainer = Trainer(cfg, tcfg, opt_cfg, mesh=mesh, rules=rules)
    if mesh is not None:
        with mesh:
            result = trainer.run()
    else:
        result = trainer.run()
    print(f"final loss: {result['final_loss']:.4f}  "
          f"wall: {result['wall_s']:.1f}s")


if __name__ == "__main__":
    main()

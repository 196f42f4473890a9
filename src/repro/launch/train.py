"""Training launcher.

On this CPU container it runs the reduced configs end-to-end (the full configs
are exercised by the dry-run); on a real TPU fleet the same entry point runs
the full configs -- the mesh factory, sharding rules, checkpointing and data
pipeline are identical.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs import get_config
from repro.distributed import for_mesh, single_device_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.config import InputShape, reduced
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.production_mesh:
        rules = for_mesh(make_production_mesh(multi_pod=args.multi_pod))
    else:
        rules = single_device_rules()
    shape = InputShape("cli", args.seq, args.batch, "train")
    tcfg = TrainerConfig(
        steps=args.steps,
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt,
        n_microbatches=args.microbatches,
    )
    trainer = Trainer(cfg, shape, rules, tcfg, AdamWConfig(lr=args.lr, total_steps=args.steps))
    metrics = trainer.run()
    print("final:", metrics)


if __name__ == "__main__":
    main()

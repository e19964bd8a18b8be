"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ck [--compress-dp]

Full-size configs target the production mesh (launch/mesh.py) on real
fleets; on this CPU container use --reduced for runnable examples/tests.
Resumes automatically from the latest committed checkpoint in --ckpt-dir.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.configs import registry
from repro.launch import compile_cache
from repro.launch.steps import TrainHyper
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--attn", default=None, choices=[None, "full", "srf"])
    ap.add_argument("--compress-dp", action="store_true",
                    help="structured-JL compressed cross-pod gradients")
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    compile_cache.enable_compile_cache()
    overrides = {}
    if args.attn:
        overrides["attn_impl"] = args.attn
    cfg = (registry.reduced if args.reduced else registry.get)(
        args.arch, **overrides)
    tcfg = TrainerConfig(
        num_steps=args.steps, batch=args.batch, seq=args.seq, seed=args.seed,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        hyper=TrainHyper(lr=args.lr, warmup=min(50, args.steps // 5 + 1),
                         total_steps=args.steps),
        compress_dp=args.compress_dp)
    trainer = Trainer(cfg, tcfg)
    resumed = trainer.try_resume()
    print(f"arch={args.arch} params={cfg.param_count():,} resumed={resumed} "
          f"start_step={trainer.step}")
    out = trainer.train()
    for rec in out["log"]:
        print(json.dumps(rec))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(out, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())

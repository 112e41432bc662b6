"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ck

``--reduced`` runs the smoke-scale config (CPU container); full configs are
for real accelerator fleets. ``--resume`` restores from the BVLSM store
(params, optimizer, step, data cursor).
"""
from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.training.optimizer import OptimizerConfig
from repro.training.train_step import TrainConfig
from repro.training.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--sync-ckpt", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--d-model", type=int, default=0, help="override reduced d_model")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(**({"d_model": args.d_model} if args.d_model else {}))
    tcfg = TrainerConfig(
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        ckpt_interval=args.ckpt_interval,
        ckpt_async=not args.sync_ckpt,
        train=TrainConfig(
            opt=OptimizerConfig(lr=args.lr, warmup_steps=20, total_steps=max(args.steps, 100)),
            accum_steps=args.accum,
        ),
    )
    trainer = Trainer(cfg, tcfg)
    try:
        result = trainer.run()
        print("result:", {k: v for k, v in result.items() if k != "metrics"})
        if result["metrics"]:
            first, last = result["metrics"][0], result["metrics"][-1]
            print(f"loss: {first.get('loss'):.4f} -> {last.get('loss'):.4f}")
        print("checkpoint engine stats:", trainer.store.stats())
    finally:
        trainer.close()


if __name__ == "__main__":
    main()

"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \\
      --steps 100 --batch 8 --seq 128

Integrates the paper's predictor as a first-class feature: pass
``--predict-on tpu-v5e,tpu-v5p,...`` to trace the *actual* train step and
print predicted step time / throughput / cost-normalized throughput for
every candidate device before (or instead of) running.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro import runtime
from repro.configs import ARCHS, get_config
from repro.core import OperationTracker, cost as cost_mod, default_predictor
from repro.core import devices
from repro.models.config import smoke_config
from repro.train.optim import adamw
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--predict-on", default=None,
                    help="comma-separated device names to cost out "
                         "(e.g. tpu-v5e,tpu-v5p,trainium2)")
    ap.add_argument("--predict-only", action="store_true")
    args = ap.parse_args()
    runtime.use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
        cfg = dataclasses.replace(cfg, use_flash=False)
    optimizer = adamw(lr=args.lr)

    if args.predict_on:
        # The paper's workflow (Listing 1): trace the real step function on
        # the device we have, predict the devices we don't.
        from repro.train.data import SyntheticTokens
        from repro.train.train_step import init_state
        step_fn = make_train_step(cfg, optimizer)
        state = init_state(cfg, jax.random.PRNGKey(0), optimizer)
        batch = jax.tree.map(jax.numpy.asarray,
                             SyntheticTokens(cfg, args.batch,
                                             args.seq).batch_at(0))
        origin = devices.local_device()
        tracker = OperationTracker(origin_device=origin)
        trace = tracker.track(step_fn, state, batch, label=args.arch)
        candidates = args.predict_on.split(",")
        ranking = cost_mod.rank_devices(trace, args.batch, candidates,
                                        predictor=default_predictor())
        print(f"\nPredicted training performance for {cfg.name} "
              f"(batch={args.batch}, seq={args.seq}), traced on {origin}:")
        print(cost_mod.format_ranking(ranking))
        if args.predict_only:
            return

    trainer = Trainer(
        cfg, args.batch, args.seq,
        TrainerConfig(checkpoint_dir=args.checkpoint_dir,
                      checkpoint_every=args.checkpoint_every,
                      max_steps=args.steps),
        optimizer=optimizer)
    stats = trainer.run(args.steps)
    print(f"\ndone: {stats}")


if __name__ == "__main__":
    main()

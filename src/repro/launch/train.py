"""End-to-end training driver.

Single-process CPU: trains a reduced config on host devices with explicit
ring-all-reduce DP (paper-faithful) or GSPMD. Multi-host TPU: the same code
path scales — ``jax.distributed.initialize()`` + the production mesh; per-pod
process groups are wired by the launcher environment (GKE/XPK-style).

Examples:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.train --arch qwen3-0.6b --reduced --steps 50 --dp 8

  # published widths, bf16 params, on one chip
  python -m repro.launch.train --arch qwen3-0.6b --steps 20 --seq 1024
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, list_archs
from repro.data.pipeline import SyntheticTokens
from repro.dist.registry import STEP_MODES
from repro.launch.runtime import device_summary, enable_compile_cache
from repro.models.model import build_model
from repro.training.elastic import ElasticTrainer, SlotPlan
from repro.training.optimizer import make_optimizer


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=list_archs())
    p.add_argument("--reduced", action="store_true",
                   help="train the reduced (CPU-sized) config in float32 "
                        "(default: published widths, bf16 params)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--dp", type=int, default=0,
                   help="DP degree (0 = all devices)")
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--mode", default="ring", choices=list(STEP_MODES))
    p.add_argument("--optimizer", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args()

    enable_compile_cache()
    print(json.dumps({"device": device_summary()}), flush=True)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    data = SyntheticTokens(cfg.vocab, args.seq, args.global_batch)
    opt = make_optimizer(args.optimizer or cfg.optimizer)
    trainer = ElasticTrainer(model, opt, data,
                             global_batch=args.global_batch,
                             base_lr=args.lr, mode=args.mode,
                             checkpoint_dir=args.checkpoint_dir,
                             param_dtype=jnp.float32 if args.reduced
                             else jnp.bfloat16)
    if args.checkpoint_dir:
        trainer.restore()
    dp = args.dp or len(jax.devices())
    t0 = time.time()
    done = 0
    while done < args.steps:
        chunk = min(args.log_every, args.steps - done)
        res = trainer.run_slot(SlotPlan(workers=dp, steps=chunk))
        done += chunk
        dt = time.time() - t0
        print(f"step {trainer.step:5d} loss {res['loss']:.4f} "
              f"dp={res.get('workers', dp)} {done / dt:.2f} steps/s",
              flush=True)
    print(json.dumps({
        "final_step": trainer.step,
        "final_loss": trainer.losses[-1],
        "first_loss": trainer.losses[0],
        "mode": args.mode,
    }))


if __name__ == "__main__":
    main()

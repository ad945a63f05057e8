"""Training launcher.

Examples:
  # CPU-runnable smoke run (reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b --smoke \\
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1

  # Production lowering (the dry-run does the compile-only variant):
  PYTHONPATH=src python -m repro.launch.train --arch deepseek_67b \\
      --shape train_4k --mesh 16x16 --impl pallas ...

On a real TPU pod this script is launched once per host (JAX distributed
initialization via JAX_COORDINATOR/megascale env as usual); on this container
it runs single-process.
"""

from __future__ import annotations

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.attention import IMPLS, default_impl
from repro.data import DataConfig
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.steps import make_train_step
from repro.runtime.trainer import Trainer, TrainerConfig


def parse_mesh(s):
    if not s:
        return None
    dims = [int(x) for x in s.split("x")]
    axes = {1: ("model",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(tuple(dims), axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mesh", default="", help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--impl", default=None, choices=IMPLS,
                    help="attention impl (default: pallas on a TPU, else xla)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    args = ap.parse_args(argv)
    args.impl = args.impl or default_impl()
    enable_compile_cache()

    cfg = (configs.smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = dataclasses.replace(cfg, dtype=getattr(jnp, args.dtype))
    mesh = parse_mesh(args.mesh)
    arts = make_train_step(cfg, mesh=mesh, opt=AdamWConfig(lr=args.lr),
                           impl=args.impl, total_steps=args.steps,
                           warmup_steps=args.warmup,
                           microbatch=args.microbatch,
                           xla_chunk=min(1024, args.seq))
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, frontend=cfg.frontend)
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    batch_shardings = arts.shardings["batch"] if arts.shardings else None
    trainer = Trainer(arts=arts, data_cfg=data_cfg, tcfg=tcfg,
                      batch_shardings=batch_shardings)
    result = trainer.run(args.steps)
    print(f"done at step {result['stop_step']} "
          f"(preempted={result['preempted']}, "
          f"stragglers={len(result['stragglers'])})")


if __name__ == "__main__":
    main()

"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch dbrx-132b --smoke \
      --steps 100 --global-batch 8 --seq 128 --mesh 4x2 --ckpt /tmp/ckpt

--smoke uses the reduced config (CPU-runnable); without it the full published
config is used (needs real accelerators). --resume auto restarts from the
latest checkpoint — the preemption/restart path."""
from __future__ import annotations

import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke
from repro.optim import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def parse_mesh(s: str | None):
    if not s:
        return None
    dims = [int(x) for x in s.split("x")]
    axes = ("data", "model")[:len(dims)] if len(dims) <= 2 else \
        ("pod", "data", "model")
    return jax.make_mesh(tuple(dims), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(dims))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 -> (data,model)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch, "train_4k")
    mesh = parse_mesh(args.mesh)
    t = Trainer(cfg, TrainerConfig(
        steps=args.steps, global_batch=args.global_batch, seq_len=args.seq,
        ckpt_dir=args.ckpt),
        mesh=mesh,
        opt_cfg=AdamWConfig(lr=args.lr, total_steps=args.steps,
                            warmup_steps=max(args.steps // 20, 1)))
    t.run()


if __name__ == "__main__":
    main()

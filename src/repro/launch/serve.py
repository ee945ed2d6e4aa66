"""Serving launcher: batched greedy decode through the LL EP path.

  PYTHONPATH=src python -m repro.launch.serve --arch dbrx-132b --smoke \
      --batch 8 --prompt-len 16 --gen 32 --mesh 8
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke
from repro.launch.train import parse_mesh
from repro.runtime.server import DecodeServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch, "decode_32k")
    mesh = parse_mesh(args.mesh)
    srv = DecodeServer(cfg, batch=args.batch,
                       max_len=args.prompt_len + args.gen + 8, mesh=mesh)
    prompts = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    m = srv.serve(prompts, gen_steps=args.gen)
    print(f"output_tok_s={m.output_tok_s:.1f} ttft_ms={m.ttft_s*1e3:.1f} "
          f"itl_mean_ms={m.itl_mean_s*1e3:.2f} itl_p99_ms={m.itl_p99_s*1e3:.2f}")


if __name__ == "__main__":
    main()

"""End-to-end driver: serve a small MoE model with batched requests through
the LL expert-parallel path on an 8-rank mesh — the paper's vLLM scenario
(§VI-C) in miniature, including the staged double-buffered pipeline variant
and the EPLB adopt-once serving mode (``MoESpec.params_physical``: expert
weights live in the active placement's physical slot order and are rebound
host-side once per rebalance boundary instead of gathered every step).

  PYTHONPATH=src python examples/serve_decode.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.runtime.server import DecodeServer

BATCH, PROMPT, GEN = 16, 8, 48


def run(mode: str, layout: str = "nccl_ep", adopt_once: bool = False,
        trace: bool = False):
    cfg = get_smoke("dbrx-132b")
    moe = dataclasses.replace(cfg.moe, ep_mode=mode, ll_layout=layout)
    kw = {}
    if adopt_once:
        # EPLB adopt-once serving: heat-driven rebalancing every 16 steps
        # with 8 redundant replica slots; params_physical binds the expert
        # weights to each adopted placement's slot order exactly once at the
        # boundary (checkpoint.adopt_expert_params) — no per-step expansion.
        moe = dataclasses.replace(moe, track_expert_heat=True,
                                  params_physical=True)
        kw = dict(rebalance_every=16, num_redundant_experts=8)
    if trace:
        # telemetry (docs/DESIGN.md §11): serve.* spans at the existing
        # host-side step boundaries, in memory and on the profiler's clock
        from repro.runtime.telemetry import TimeSeries, Tracer
        kw.update(tracer=Tracer(), series=TimeSeries())
    cfg = dataclasses.replace(cfg, moe=moe)
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    srv = DecodeServer(cfg, batch=BATCH, max_len=PROMPT + GEN + 8, mesh=mesh,
                       **kw)
    prompts = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (BATCH, PROMPT)), jnp.int32)
    if trace:
        # one profiler trace holds the serve.* spans (host plane) and the
        # device ops on one clock; open the .json.gz in ui.perfetto.dev
        out = pathlib.Path("results") / "serve_decode_trace"
        with jax.profiler.trace(str(out), create_perfetto_trace=True):
            m = srv.serve(prompts, gen_steps=GEN)
    else:
        m = srv.serve(prompts, gen_steps=GEN)
    tag = f"{mode}/{layout}" + ("/adopt-once" if adopt_once else "")
    extra = (f" swaps={len(srv.placements)}" if adopt_once else "")
    print(f"  backend={tag:22s} out_tok/s={m.output_tok_s:8.1f} "
          f"ttft={m.ttft_s*1e3:6.1f}ms itl={m.itl_mean_s*1e3:5.2f}ms "
          f"p99={m.itl_p99_s*1e3:5.2f}ms{extra}")
    if trace:
        spans = sum(r["count"] for r in m.timeline.values()
                    if r["ph"] == "X")
        perfetto = sorted(out.glob("**/perfetto_trace.json.gz"))
        print(f"  wrote {perfetto[-1]} ({spans} serve.* spans; open in "
              "ui.perfetto.dev)")
    return m


if __name__ == "__main__":
    print(f"serving {BATCH} requests, prompt={PROMPT}, gen={GEN} "
          f"(MoE 8e top-2, 8-rank EP):")
    run("ll", "nccl_ep")     # the paper's optimized LL layout
    run("ll", "deepep")      # the DeepEP layout it improves on
    run("baseline")          # Megatron-style AllToAll dispatcher
    # EPLB adopt-once rebalancing, telemetry on -> a profiler trace
    run("ll", "nccl_ep", adopt_once=True, trace=True)

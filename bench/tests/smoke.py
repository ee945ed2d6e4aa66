"""Small cells for the CPU tests: the same drivers at smoke widths, with
the harness's device check skipped (the tests call the drivers directly)."""
from __future__ import annotations

import jax

import run as RUN
from peaks import TABLE

DBRX_SMOKE = {
    "d_model": 64, "n_heads": 4, "n_layers": 1, "vocab_size": 256,
    "attn_config": {"kv_n_heads": 2, "rope_theta": 10000.0},
    "ffn_config": {"ffn_hidden_size": 64, "moe_num_experts": 8, "moe_top_k": 2},
    "norm_eps": 1e-5,
    "program": {"preset": "dbrx_132b", "smoke": True},
}

SERVE_SMOKE = {
    "driver": "serve", "slots": 4, "page_size": 8,
    "prompt_len": {"dist": "uniform", "lo": 4, "hi": 12},
    "output_len": {"dist": "uniform", "lo": 6, "hi": 12},
    "rate": 0.15, "requests": 400, "block": 8, "warm_steps": 20,
    "trace_seconds": 0, "check_requests": 6, "limit_logit_gap": 0.01,
    "route_margin": 0.02,
}

DSV3_SMOKE = {
    "hidden_size": 256, "n_routed_experts": 16, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "program": {"preset": "deepseek_v3_671b", "smoke": True},
}

EP_SMOKE = {
    "driver": "ep_round_trip", "mode": "ll", "tokens_per_rank": 8, "fp8": True,
    "capacity_factor": None, "expert_capacity_factor": 2.0,
    "round_trips_per_chunk": 3, "warm_chunks": 1, "trace_seconds": 0,
    "check_samples": 2, "limit_rel_err": 0.02,
}


def cell(config, traffic, *, chips=1, seed=7, seconds=0.5, hooks=None):
    return RUN.Cell(config=config, traffic=traffic, chips=chips,
                    seed=seed, seconds=seconds, trace_dir=None,
                    devices=jax.devices()[:chips], peaks=TABLE["TPU v5e"],
                    hooks=hooks or {})


def driver(name):
    return RUN.load_module(RUN.BENCH / "drivers" / f"{name}.py")

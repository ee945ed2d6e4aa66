"""Driver ``serve_deepseek``: DeepSeek-V3 (MLA, held experts) served by the
continuous-batching engine under an open loop.

The engine, the step clock, the window and the sampling of the check are
the ``serve`` driver's (``drivers/serve.py``); what differs is the model:

- the configuration file is DeepSeek-V3's ``config.json`` cut to one chip's
  share of an EP deployment (``deployment``: the router's width, the
  ranks, and which rank's contiguous slice of experts this chip holds).
  ``arch_config`` builds the program's config from the preset the file
  names, with the file's depth, leading dense layers and held experts, and
  checks every width against the file;
- weights come from ``weights.py`` (MLA's leaves drawn by fan-in, norm
  gains ones), then the routing correction bias is set to a seeded
  non-zero draw, so that selection (scores + bias) and weighting (scores)
  differ as in the published model;
- ``counters["flops"]`` counts the step's model FLOPs with
  ``costs_mla.step_flops``: the live rows' contexts and the routed rows on
  held experts (the engine's ``held_load``, read after each traced step);
- the check: the sampled requests go once through
  ``reference/deepseek_v3.py`` (plain f32, un-absorbed MLA, YaRN,
  ``noaux_tc`` routing over all experts, the held experts and the shared
  expert); positions whose routing margin (expert choice or group choice)
  lies below the traffic's ``route_margin`` are left out;
- the run fails when the compiled step lacks the Pallas paged decode
  kernel on a TPU: MLA decode may not run the jnp oracle there.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib

import jax
import numpy as np

from repro.core.placement import identity_placement, rank_experts
from repro.models.registry import get_model
from repro.parallel.sharding import ParamSpec
from repro.runtime.server import ContinuousDecodeServer
from repro.runtime.steps import paged_serve_state_specs

import costs_mla
import device
import traffic_gen
import weights
import window as W
from drivers.serve import (StepClock, StopServing, _requests, _token_steps,
                           check_sample)
from reference import deepseek_v3 as REF

SCOPES = ("paged_decode", "moe.experts")
KERNELS = ("paged_decode_stage1", "paged_decode_stage2")
DECODE_KERNEL = "paged_decode_stage1"
SEL_BIAS_STD = 0.1      # the correction bias's draw (configuration "assumed")
MARGIN_CUTS = (0.002, 0.003, 0.004, 0.005, 0.0075, 0.01, 0.02)

# MLA's leaves, drawn as weights.py draws the others: std = fan-in ** -0.5
weights.ROLES.update({
    "wq_a": -2, "wkv_a": -2,                # [..., d, rank]
    "wq_b": -3, "wk_b": -3, "wv_b": -3,     # [..., rank, heads, hd]
    "q_norm": "ones", "kv_norm": "ones",
})


def held_experts(conf: dict) -> tuple[int, ...]:
    """The experts this chip holds: the configured rank's slice of the
    contiguous placement of the router's experts over the ranks."""
    dep = conf["deployment"]
    place = identity_placement(dep["router_experts"], dep["ranks"])
    return rank_experts(place, dep["rank"])


def arch_config(conf: dict):
    """The program's config for a configuration file, checked against the
    file's numbers: what the file says is what runs."""
    prog = conf["program"]
    mod = importlib.import_module(f"repro.configs.{prog['preset']}")
    base = mod.smoke_config() if prog.get("smoke") else mod.full_config(prog["shape"])
    held = held_experts(conf)
    # serving takes no speculative tokens: no MTP layer (a departure)
    cfg = dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"], mtp=False,
        moe=dataclasses.replace(base.moe,
                                first_k_dense=conf["first_k_dense_replace"],
                                held_experts=held))
    a, ml, m, y = cfg.attn, cfg.mla, cfg.moe, cfg.mla.rope_scaling
    have = dict(
        hidden_size=cfg.d_model, num_attention_heads=a.n_heads,
        num_key_value_heads=a.n_kv, num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=m.first_k_dense, vocab_size=cfg.vocab,
        intermediate_size=cfg.d_ff, moe_intermediate_size=m.d_ff_expert,
        n_routed_experts=len(held), router_experts=m.num_experts,
        num_experts_per_tok=m.top_k, n_group=m.n_groups,
        topk_group=m.topk_groups, n_shared_experts=m.shared_experts,
        routed_scaling_factor=m.routed_scaling, norm_topk_prob=m.norm_topk,
        q_lora_rank=ml.q_lora_rank, kv_lora_rank=ml.kv_lora_rank,
        qk_nope_head_dim=ml.qk_nope_dim, qk_rope_head_dim=ml.qk_rope_dim,
        v_head_dim=ml.v_head_dim, rope_theta=a.rope_base,
        rms_norm_eps=cfg.norm_eps, tie_word_embeddings=cfg.tie_embeddings,
        rope_scaling=(None if y is None else dict(
            factor=y.factor,
            original_max_position_embeddings=y.original_max_position,
            beta_fast=y.beta_fast, beta_slow=y.beta_slow, mscale=y.mscale,
            mscale_all_dim=y.mscale_all_dim)))
    want = {k: conf[k] for k in have if k in conf}
    want["router_experts"] = conf["deployment"]["router_experts"]
    if "rope_scaling" in conf:
        want["rope_scaling"] = {k: v for k, v in conf["rope_scaling"].items()
                                if k != "type"}
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    if (m.gating != conf["scoring_func"] or not m.use_selection_bias
            or conf["topk_method"] != "noaux_tc" or cfg.act != "swiglu"
            or conf["hidden_act"] != "silu"):
        raise ValueError("the served MoE must be sigmoid noaux_tc SwiGLU")
    if m.capacity_factor is not None:
        raise ValueError("the served MoE must be drop-free")
    return cfg


def ref_config(conf: dict) -> dict:
    y = conf["rope_scaling"]
    return dict(
        eps=float(conf["rms_norm_eps"]), vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        qk_nope=conf["qk_nope_head_dim"], qk_rope=conf["qk_rope_head_dim"],
        kv_rank=conf["kv_lora_rank"], top_k=conf["num_experts_per_tok"],
        n_group=conf["n_group"], topk_group=conf["topk_group"],
        routed_scaling=float(conf["routed_scaling_factor"]),
        held=held_experts(conf),
        yarn_factor=float(y["factor"]),
        yarn_original=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]))


def make_weights(seed: int, shapes):
    """The served weights of a seed (the program's and the reference's):
    ``weights.make``, then the MoE layers' correction bias drawn
    N(0, SEL_BIAS_STD²) per expert."""
    params = weights.make(seed, shapes)
    moe = params["moe_stack"]["moe"]
    key = weights.leaf_key(seed, "moe_stack/moe/sel_bias")
    moe["sel_bias"] = (jax.random.normal(key, moe["sel_bias"].shape,
                                         moe["sel_bias"].dtype)
                       * SEL_BIAS_STD)
    return params


class LoadClock(StepClock):
    """``StepClock`` counting DeepSeek-V3's model FLOPs of each traced step:
    its contexts, and its routed rows on held experts, read from the step's
    ``held_load`` after the step (the engine reads it back there too)."""

    def __init__(self, *args, conf: dict, **kw):
        super().__init__(*args, **kw)
        self.conf = conf
        self.model_flops = 0.0

    def __call__(self, params, state, feed):
        before = self.traced_steps
        tok, state = super().__call__(params, state, feed)
        if self.traced_steps > before:
            act = np.asarray(feed["active"]) > 0
            ctx = np.asarray(feed["kv_lens"])[act] + 1
            local_rows = float(np.asarray(state["held_load"])[0])
            self.model_flops += costs_mla.step_flops(self.conf, ctx,
                                                     local_rows)
        return tok, state


def by_margin(gaps, ctl, margin, cuts=MARGIN_CUTS) -> list[dict]:
    """For each routing-margin cut: the positions left out, and the widest
    gap of the program (and of the control, when run) over the rest."""
    rows = []
    for c in cuts:
        keep = margin >= c
        row = dict(cut=c, left_out=int((~keep).sum()),
                   program=float(gaps[keep].max(initial=0.0)))
        if ctl is not None:
            row["control"] = float(ctl[keep].max(initial=0.0))
        rows.append(row)
    return rows


# StepClock also counts the DBRX-shaped FLOPs of serve.py's costs; zero
# widths make that count 0, and LoadClock keeps its own
_NO_COST = dict(d_model=0, n_layers=0, n_heads=0, n_kv_heads=0, head_dim=0,
                n_experts=0, top_k=0, d_ff_expert=0, vocab=0)


def run(cell) -> dict:
    conf, tr = cell.config, cell.traffic
    cfg = arch_config(conf)
    max_len = tr["prompt_len"]["hi"] + tr["output_len"]["hi"]
    sched_in = traffic_gen.schedule(tr, cell.seed, conf["vocab_size"])
    requests = _requests(sched_in)

    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                          get_model(cfg).params_spec(cfg),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    params = make_weights(cell.seed, shapes)
    srv = ContinuousDecodeServer(cfg, batch=tr["slots"], max_len=max_len,
                                 params=params, page_size=tr["page_size"],
                                 seed=cell.seed)
    _, feed = paged_serve_state_specs(cfg, srv.batch, srv.num_pages,
                                      srv.page_size, srv.max_pages)
    feed = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in feed.items()}
    compiled = srv.step.lower(srv.params, srv.state, feed).compile()
    hlo = compiled.as_text()
    kernels = device.kernel_counts(hlo)
    if cell.devices[0].platform == "tpu" and DECODE_KERNEL not in kernels:
        raise RuntimeError(
            f"the compiled step lacks the Pallas kernel {DECODE_KERNEL}: "
            f"MLA decode would run the jnp oracle (kernels: {kernels})")
    traced = device.TracedWindow(cell.trace_dir)
    clock = LoadClock(compiled, srv, open_step=tr["warm_steps"],
                      seconds=cell.seconds, traced=traced,
                      trace_seconds=tr["trace_seconds"], cost_cfg=_NO_COST,
                      requests=requests, alter=cell.hooks.get("alter"),
                      conf=conf)
    srv.step = clock
    try:
        srv.serve_requests(requests)
    except StopServing:
        pass
    finally:
        traced.end()
        srv.close()
    if clock.close_step is None:
        raise RuntimeError("the engine finished every request before the "
                           "window closed: the schedule is too short")
    end = np.asarray(clock.end)
    sched = srv.reqsched
    if max(r.arrival_step for r in requests) <= clock.close_step:
        raise RuntimeError("arrivals ran out inside the window: the traffic "
                           "file needs more requests")
    times = []
    for r in requests:
        if r.arrival_step > len(end) - 1:
            continue
        st = _token_steps(sched, r.rid, end)
        times.append(W.RequestTimes(r.arrival_step, end[st].tolist()))
    stats = W.window_stats(times, end, clock.t_begin, clock.open_step,
                           clock.close_step)
    steps_s = np.diff(end[clock.open_step - 1:clock.close_step + 1])
    mem = device.peak_bytes(cell.devices)

    # the check, once the program's state is gone
    finished = {rid: sched.tokens_for(rid) for rid in sched.finished}
    prompts = {r.rid: r.prompt for r in requests}
    del srv, params, clock.srv, sched, compiled
    gc.collect()
    sample = check_sample(finished, tr["check_requests"], cell.seed)
    control = bool(cell.hooks.get("control"))
    ref_params = make_weights(cell.seed, shapes)
    rc = ref_config(conf)
    out = [REF.served_gaps(ref_params, prompts[rid], finished[rid], rc,
                           pad_to=max_len, control=control) for rid in sample]
    del ref_params
    gaps, ctl, margin = (np.concatenate(x) if x[0] is not None else None
                         for x in zip(*out))
    resolved = margin >= tr["route_margin"]
    ctl_out = None
    if ctl is not None:
        ctl_out = dict(logit_gap_max=float(ctl[resolved].max(initial=0.0)),
                       detail=dict(gap=gaps.tolist(), control=ctl.tolist(),
                                   margin=margin.tolist()))

    wsec = stats["window_s"]
    e2e = dict(output_tok_s=stats["output_tokens"] / wsec,
               itl_p95_ms=W.pct_ms(stats["itl_s"], 95))
    checks = [dict(name="logit_gap_max",
                   value=float(gaps[resolved].max(initial=0.0)),
                   limit=float(tr["limit_logit_gap"])),
              dict(name="due_without_first_token",
                   value=stats["missing_first_token"], limit=0)]
    return dict(
        t_open=clock.t_open, e2e=e2e, checks=checks, control=ctl_out,
        attempted=stats["due"], failed=stats["missing_first_token"],
        memory_peak_bytes=mem, kernels=kernels, hlo=hlo,
        info=dict(window_steps=stats["steps"], window_s=wsec,
                  output_tokens=stats["output_tokens"], due=stats["due"],
                  ttft_p50_ms=(W.pct_ms(stats["ttft_s"], 50)
                               if stats["ttft_s"].size else None),
                  ttft_p95_ms=(W.pct_ms(stats["ttft_s"], 95)
                               if stats["ttft_s"].size else None),
                  itl_p50_ms=W.pct_ms(stats["itl_s"], 50),
                  itl_samples=int(stats["itl_s"].size),
                  step_ms_max=float(steps_s.max()) * 1e3,
                  step_ms_max_at=int(clock.open_step + steps_s.argmax()),
                  steps_over_50ms=int((steps_s > 0.05).sum()),
                  held_experts=list(cfg.moe.held_experts),
                  checked_requests=len(sample),
                  checked_tokens=int(gaps.size),
                  near_tie_tokens=int((~resolved).sum()),
                  by_margin=by_margin(gaps, ctl, margin)),
        counters=dict(steps=clock.traced_steps, flops=clock.model_flops,
                      host_gaps_s=clock.host_gaps),
    )

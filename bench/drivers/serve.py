"""Driver ``serve``: the continuous-batching engine under an open loop.

Drives ``ContinuousDecodeServer.serve_requests`` on the traffic file's
requests. The engine admits on its own step clock, so requests are due at
their arrival step. The harness wraps the engine's compiled step
(``srv.step``) in ``StepClock``, which times every step on the host clock,
counts the work, opens the window once the engine has run the traffic's
``warm_steps`` (steady occupancy), closes it after ``--seconds``, and stops
the engine once every request due in the window has its first token.

TTFT runs from the start of the due step (queue wait included) to the end
of the step that emitted the first token. Output tokens and inter-token
gaps count only inside the window.

The check: a sample of the finished requests, drawn from the seed with the
longest among them, goes through the plain float32 reference once over
prompt + served tokens; the number compared is the widest gap by which a
served token's reference logit lies below the reference's best. It covers
attention through the paged pool, the MoE layer and the LM head, as the
engine ran them at the timed shapes.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import time

import jax
import numpy as np

from repro.models.registry import get_model
from repro.parallel.sharding import ParamSpec
from repro.runtime.scheduler import Request
from repro.runtime.server import ContinuousDecodeServer
from repro.runtime.steps import paged_serve_state_specs

import costs
import device
import traffic_gen
import weights
import window as W
from reference import dbrx as REF

FOLLOW_LIMIT_S = 150.0      # longest wait for a due request's first token


class StopServing(Exception):
    """Raised at a step boundary once the window's requests are followed."""


def arch_config(conf: dict):
    """The program's config for a configuration file, checked against the
    file's numbers: what the file says is what runs."""
    prog = conf["program"]
    mod = importlib.import_module(f"repro.configs.{prog['preset']}")
    base = mod.smoke_config() if prog.get("smoke") else mod.full_config(prog["shape"])
    cfg = dataclasses.replace(base, num_layers=conf["n_layers"])
    a, m = cfg.attn, cfg.moe
    have = dict(d_model=cfg.d_model, n_heads=a.n_heads, n_layers=cfg.num_layers,
                vocab_size=cfg.vocab, kv_n_heads=a.n_kv,
                head_dim=a.head_dim, rope_theta=a.rope_base,
                ffn_hidden_size=m.d_ff_expert, moe_num_experts=m.num_experts,
                moe_top_k=m.top_k, eps=cfg.norm_eps)
    want = dict(d_model=conf["d_model"], n_heads=conf["n_heads"],
                n_layers=conf["n_layers"], vocab_size=conf["vocab_size"],
                kv_n_heads=conf["attn_config"]["kv_n_heads"],
                head_dim=conf["d_model"] // conf["n_heads"],
                rope_theta=conf["attn_config"]["rope_theta"],
                ffn_hidden_size=conf["ffn_config"]["ffn_hidden_size"],
                moe_num_experts=conf["ffn_config"]["moe_num_experts"],
                moe_top_k=conf["ffn_config"]["moe_top_k"],
                eps=conf["norm_eps"])
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    if m.capacity_factor is not None or m.gating != "softmax" or not m.norm_topk:
        raise ValueError("the served MoE must be drop-free softmax top-k")
    return cfg


def ref_config(conf: dict) -> dict:
    return dict(n_layers=conf["n_layers"], top_k=conf["ffn_config"]["moe_top_k"],
                rope_theta=float(conf["attn_config"]["rope_theta"]),
                eps=float(conf["norm_eps"]), vocab=conf["vocab_size"])


def cost_config(conf: dict) -> dict:
    return dict(d_model=conf["d_model"], n_layers=conf["n_layers"],
                n_heads=conf["n_heads"],
                n_kv_heads=conf["attn_config"]["kv_n_heads"],
                head_dim=conf["d_model"] // conf["n_heads"],
                n_experts=conf["ffn_config"]["moe_num_experts"],
                top_k=conf["ffn_config"]["moe_top_k"],
                d_ff_expert=conf["ffn_config"]["ffn_hidden_size"],
                vocab=conf["vocab_size"])


class StepClock:
    """The engine's compiled step, timed and counted at every boundary."""

    def __init__(self, step, srv, *, open_step: int, seconds: float,
                 traced: device.TracedWindow, trace_seconds: float,
                 cost_cfg: dict, requests: list, alter=None):
        self.step, self.srv, self.requests = step, srv, requests
        self.open_step, self.seconds = open_step, seconds
        self.traced, self.trace_seconds = traced, trace_seconds
        self.cost_cfg, self.alter = cost_cfg, alter
        self.t_begin = time.perf_counter()
        self.end: list[float] = []
        self.t_open = None
        self.close_step = None
        self.pending: set | None = None
        self.t_close = None
        self.traced_steps = 0
        self.traced_flops = 0.0
        self.host_gaps: list[float] = []
        self._engine_span = None

    def _first_tokens(self) -> set:
        sched = self.srv.reqsched
        have = set(sched.finished)
        have.update(s.req.rid for s in sched.slots if s is not None and s.generated)
        return have

    def __call__(self, params, state, feed):
        i = len(self.end)
        if self._engine_span is not None:
            self._engine_span.__exit__(None, None, None)
            self._engine_span = None
        if self.close_step is not None:
            self.pending -= self._first_tokens()
            if not self.pending:
                raise StopServing
            if time.perf_counter() - self.t_close > FOLLOW_LIMIT_S:
                raise StopServing
        if i == self.open_step:
            self.t_open = self.end[-1] if self.end else self.t_begin
            self.traced.begin()
        elif (self.traced.active
              and self.end[-1] - self.t_open >= self.trace_seconds):
            self.traced.end()
        tracing = self.traced.active
        t_call = time.perf_counter()
        if tracing and i > self.open_step:
            self.host_gaps.append(t_call - self.end[-1])
        with device.span("bench.serve_step", tracing):
            tok, state = self.step(params, state, feed)
            tok = jax.block_until_ready(tok)
        t_end = time.perf_counter()
        self.end.append(t_end)
        if self.alter is not None:
            tok = self.alter(tok, feed)
        if tracing:
            act = np.asarray(feed["active"]) > 0
            ctx = np.asarray(feed["kv_lens"])[act] + 1
            self.traced_steps += 1
            self.traced_flops += costs.tokens_flops(self.cost_cfg, ctx)
            self._engine_span = jax.profiler.TraceAnnotation("bench.engine")
            self._engine_span.__enter__()
        if (self.t_open is not None and self.close_step is None
                and t_end - self.t_open >= self.seconds):
            self.close_step = i
            self.t_close = t_end
            self.pending = {r.rid for r in self.requests
                            if self.open_step <= r.arrival_step <= i}
        return tok, state


def _requests(sched: dict) -> list[Request]:
    return [Request(i, toks, int(n), int(a)) for i, (toks, n, a) in enumerate(
        zip(sched["tokens"], sched["output_len"], sched["arrival"]))]


def _token_steps(sched, rid, end: np.ndarray) -> np.ndarray:
    """Steps that emitted request ``rid``'s tokens so far. The scheduler
    stamps a token just after its step returned, so the step is the last
    one that ended at or before the stamp."""
    s = sched.finished.get(rid)
    if s is None:
        s = next((x for x in sched.slots if x is not None and x.req.rid == rid),
                 None)
    times = np.asarray(s.tok_times if s is not None else [], np.float64)
    return np.searchsorted(end, times, side="right") - 1


def run(cell) -> dict:
    conf, tr = cell.config, cell.traffic
    cfg = arch_config(conf)
    max_len = tr["prompt_len"]["hi"] + tr["output_len"]["hi"]
    sched_in = traffic_gen.schedule(tr, cell.seed, conf["vocab_size"])
    requests = _requests(sched_in)

    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                          get_model(cfg).params_spec(cfg),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    params = weights.make(cell.seed, shapes)
    srv = ContinuousDecodeServer(cfg, batch=tr["slots"], max_len=max_len,
                                 params=params, page_size=tr["page_size"],
                                 seed=cell.seed)
    _, feed = paged_serve_state_specs(cfg, srv.batch, srv.num_pages,
                                      srv.page_size, srv.max_pages)
    feed = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in feed.items()}
    compiled = srv.step.lower(srv.params, srv.state, feed).compile()
    hlo = compiled.as_text()
    traced = device.TracedWindow(cell.trace_dir)
    clock = StepClock(compiled, srv, open_step=tr["warm_steps"],
                      seconds=cell.seconds, traced=traced,
                      trace_seconds=tr["trace_seconds"],
                      cost_cfg=cost_config(conf), requests=requests,
                      alter=cell.hooks.get("alter"))
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
    last_arrival = max(r.arrival_step for r in requests)
    if last_arrival <= clock.close_step:
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
    gaps, ctl, margin = reference_gaps(
        cell, conf, shapes, sample, finished, prompts, max_len,
        control=bool(cell.hooks.get("control")))
    resolved = margin >= tr["route_margin"]
    control = None
    if ctl is not None:
        control = dict(logit_gap_max=float(ctl[resolved].max(initial=0.0)),
                       detail=dict(gap=gaps.tolist(), control=ctl.tolist(),
                                   margin=margin.tolist()))

    wsec = stats["window_s"]
    e2e = dict(output_tok_s=stats["output_tokens"] / wsec,
               itl_p95_ms=W.pct_ms(stats["itl_s"], 95))
    if stats["ttft_s"].size:
        e2e["ttft_p95_ms"] = W.pct_ms(stats["ttft_s"], 95)
    checks = [dict(name="logit_gap_max",
                   value=float(gaps[resolved].max(initial=0.0)),
                   limit=float(tr["limit_logit_gap"])),
              dict(name="due_without_first_token",
                   value=stats["missing_first_token"], limit=0)]
    return dict(
        t_open=clock.t_open, e2e=e2e, checks=checks, control=control,
        attempted=stats["due"], failed=stats["missing_first_token"],
        memory_peak_bytes=mem, kernels=device.kernel_counts(hlo),
        hlo=hlo,
        info=dict(window_steps=stats["steps"], window_s=wsec,
                  output_tokens=stats["output_tokens"], due=stats["due"],
                  ttft_p50_ms=(W.pct_ms(stats["ttft_s"], 50)
                               if stats["ttft_s"].size else None),
                  itl_p50_ms=W.pct_ms(stats["itl_s"], 50),
                  itl_samples=int(stats["itl_s"].size),
                  step_ms_max=float(steps_s.max()) * 1e3,
                  step_ms_max_at=int(clock.open_step + steps_s.argmax()),
                  steps_over_50ms=int((steps_s > 0.05).sum()),
                  checked_requests=len(sample),
                  checked_tokens=int(gaps.size),
                  near_tie_tokens=int((~resolved).sum())),
        counters=dict(steps=clock.traced_steps, flops=clock.traced_flops,
                      host_gaps_s=clock.host_gaps),
    )


def check_sample(finished: dict, n: int, seed: int) -> list[int]:
    """``n`` finished request ids drawn from the seed, the one with the most
    served tokens always among them."""
    rids = sorted(finished)
    if not rids:
        raise RuntimeError("no request finished: nothing to check")
    longest = max(rids, key=lambda r: (finished[r].size, -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(cell, conf, shapes, sample, finished, prompts, max_len,
                   control: bool = False):
    """(gaps, control gaps or None, routing margins) of every served token
    of the sampled requests, the reference run on fresh weights."""
    params = weights.make(cell.seed, shapes)
    rc = ref_config(conf)
    out = [REF.served_gaps(params, prompts[rid], finished[rid], rc,
                           pad_to=max_len, control=control) for rid in sample]
    del params
    gaps, ctl, margin = zip(*out)
    return (np.concatenate(gaps),
            np.concatenate(ctl) if control else None, np.concatenate(margin))

"""MoE block wired to the NCCL-EP core (the paper's §VI "FusedMoE layer").

The block enters a `shard_map` island over the full mesh; inside, tokens are
laid out one-shard-per-EP-rank and the unified ep_dispatch/ep_combine
primitives run over `MoESpec.ep_axis`. Expert weights are block-distributed
over the same axis (rank r hosts experts [r*L, (r+1)*L)), with the expert FFN
optionally tensor-parallel over the model axis when it is not part of the EP
axis (Megatron "ETP": the a2a is then replicated per TP rank — per-chip wire
bytes unchanged).

Deployment presets (mirrors the paper's vLLM/Megatron integrations):
  * training / prefill, many experts (DeepSeek-V3): ep_axis=("data","model"),
    HT mode, optionally hierarchical (outer=data, inner=model);
  * training, few experts (DBRX, E=16): ep_axis=("data",), expert-TP on model;
  * decode (both): ep_axis=("data",), LL mode, B/rank <= 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import (EpGroupConfig, ep_create_group, ep_create_handle,
                        ep_dispatch, ep_combine, ep_complete)
from repro.core.placement import expand_expert_params, collapse_expert_params
from repro.core.routing import RouterConfig, route
from repro.kernels import ops as K
from repro.models.config import ArchConfig
from repro.models.layers import ffn_spec, ffn_apply
from repro.parallel.sharding import ParamSpec

def _num_weight_rows(m) -> int:
    """Leading dim of the expert-stacked weights under the param-layout
    mode: the held experts' count when the chip holds a slice
    (``held_experts``), physical slot count in adopt-once mode (== E when
    placement is None or identity), logical E otherwise."""
    if m.held_experts is not None:
        return len(m.held_experts)
    if m.params_physical and m.placement is not None:
        return m.placement.num_slots
    return m.num_experts


def moe_spec(cfg: ArchConfig, dtype=None):
    """Param specs. The expert-stacked weights (w_gate/w_up/w_down — the
    ``checkpoint.EXPERT_PARAM_KEYS``) follow the layout mode: logical
    [E, ...] by default, physical [N*S, ...] under ``params_physical``
    (router and sel_bias always stay logical — routing is a logical-expert
    concept). NOTE: physical specs describe shapes/sharding only; random
    init must go through the LOGICAL spec + one adoption
    (checkpoint.adopt_expert_params) so replicas hold identical weights."""
    m, d = cfg.moe, cfg.d_model
    dtype = dtype or cfg.dtype
    f = m.d_ff_expert
    P_rows = _num_weight_rows(m)
    sp = dict(
        router=ParamSpec((d, m.num_experts), jnp.float32, ("embed", None)),
        w_gate=ParamSpec((P_rows, d, f), dtype, ("expert", "embed", "expert_ffn")),
        w_up=ParamSpec((P_rows, d, f), dtype, ("expert", "embed", "expert_ffn")),
        w_down=ParamSpec((P_rows, f, d), dtype, ("expert", "expert_ffn", "embed")),
    )
    if m.use_selection_bias:
        sp["sel_bias"] = ParamSpec((m.num_experts,), jnp.float32, (None,), init="zeros")
    if m.shared_experts:
        sp["shared"] = ffn_spec(d, m.shared_experts * f, dtype, cfg.act)
    return sp


def _token_specs(mesh, ep_axis):
    """(batch_axes, seq_axes) for the [B, S, D] token layout inside the MoE
    shard_map.

    The EP rank partition of tokens is carried by the batch dim for every EP
    axis EXCEPT "model", which splits the sequence dim (Megatron
    sequence-parallel style). Keeping B on ("pod","data") in all cases means
    the shard_map boundary only ever *slices S over model* relative to the
    attention layout — a local operation. (The earlier layout moved B off
    "data" onto nothing and S onto ("data","model"): GSPMD cannot reshard
    that transition incrementally and fell back to full replication of
    [B,S,D] per MoE layer — measured 33.5 TiB/dev temps on the deepseek-v3
    prefill cell. See docs/EXPERIMENTS.md §Perf iteration D1.)"""
    present = set(mesh.shape.keys())
    ep = tuple(a for a in ep_axis if a in present)
    b_axes = tuple(a for a in ("pod", "data") if a in present)
    s_axes = tuple(a for a in ep if a == "model")
    return b_axes, s_axes, ep


def _router_cfg(m) -> RouterConfig:
    return RouterConfig(
        num_experts=m.num_experts, top_k=m.top_k, gating=m.gating,
        n_groups=m.n_groups, topk_groups=m.topk_groups,
        use_selection_bias=m.use_selection_bias,
        routed_scaling_factor=m.routed_scaling, norm_topk_prob=m.norm_topk,
        aux_loss_weight=m.aux_loss_weight, z_loss_weight=1e-4,
    )


def _resolve_chunks(nc: int, tokens_per_rank: int) -> int:
    """Chunk count for this cell's per-rank token count. A configured chunk
    count that does not tile the tokens cannot run (group creation would
    raise) — fall back to monolithic, but LOUDLY: a preset that asks for the
    chunked pipeline should never lose it without a trace."""
    if tokens_per_rank % nc == 0:
        return nc
    import warnings
    warnings.warn(
        f"ht_num_chunks={nc} does not divide tokens_per_rank="
        f"{tokens_per_rank} for this cell; running the monolithic (nc=1) "
        "hierarchical path instead", stacklevel=2)
    return 1


def _expert_ffn(group, y3d, counts, w1, w3, w2, act, tp_axis):
    """Grouped SwiGLU over [L, A, D]; counts-masked; optional TP psum."""
    if group.mode == "baseline":
        counts = jnp.full_like(counts, y3d.shape[1])   # padded rows computed
    g = K.grouped_gemm(y3d, w1, counts)
    u = K.grouped_gemm(y3d, w3, counts)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(y3d.dtype)
    out = K.grouped_gemm(h, w2, counts)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)               # expert-TP partials
    return out


def moe_block(p, x, cfg: ArchConfig, mesh, *, with_heat: bool = False,
              live=None):
    """x: [B, S, D] -> (y [B, S, D], aux_loss scalar).

    With ``with_heat=True`` additionally returns the per-logical-expert
    routed-token histogram [E] (replicated), the signal the EPLB rebalancer
    consumes (runtime/server.py folds it into the decode state). With
    ``live`` ([B] 0/1, the rows that carry a request) it last returns the
    held experts' load f32[2] (``_moe_dense_fallback``); that is the
    one-chip path's alone, as is ``MoESpec.held_experts``."""
    m = cfg.moe

    def _fallback():
        y, heat, *load = _moe_dense_fallback(p, x, cfg, with_heat=True,
                                             live=live)
        return ((y, jnp.float32(0)) + ((heat,) if with_heat else ())
                + tuple(load))

    if mesh is None or mesh.empty:
        return _fallback()

    b_axes, s_axes, ep = _token_specs(mesh, m.ep_axis)
    ep_sizes = [mesh.shape[a] for a in ep]
    N = math.prod(ep_sizes) if ep else 1
    if m.placement is not None and N > 1 and m.placement.num_ranks != N:
        raise ValueError(
            f"MoESpec.placement spans {m.placement.num_ranks} ranks but the "
            f"mesh's EP extent is {N}")
    phys = m.placement.num_slots if m.placement is not None else m.num_experts
    if N <= 1 or phys % N != 0:
        return _fallback()
    if m.held_experts is not None or live is not None:
        raise ValueError(
            "held_experts and the held experts' load belong to the one-chip "
            "path; under an EP mesh each rank holds its placement's slice")
    B, S, D = x.shape
    # tokens per EP rank (static)
    b_div = math.prod(mesh.shape[a] for a in b_axes) if b_axes else 1
    s_div = math.prod(mesh.shape[a] for a in s_axes) if s_axes else 1
    T = (B // b_div) * (S // s_div)
    tp_axis = "model" if ("model" in mesh.shape and "model" not in ep) else None

    gcfg = EpGroupConfig(
        num_experts=m.num_experts, max_tokens_per_rank=T, hidden=D,
        top_k=m.top_k, mode=m.ep_mode, ll_layout=m.ll_layout,
        capacity_factor=m.capacity_factor,
        expert_capacity_factor=m.expert_capacity_factor,
        payload_dtype=cfg.dtype, quantize_dispatch=m.quantize_dispatch,
        ep_axis=ep, ht_hierarchical=m.ht_hierarchical,
        ht_num_chunks=_resolve_chunks(m.ht_num_chunks, T),
        placement=m.placement,
    )
    group = ep_create_group(gcfg, ep_size=N, inner_size=ep_sizes[-1])

    tok_spec = P(tuple(b_axes) or None, tuple(s_axes) or None, None)
    ew_spec = P(tuple(ep), None, "model" if tp_axis else None)
    ew_spec_t = P(tuple(ep), "model" if tp_axis else None, None)
    bias = p.get("sel_bias")

    def inner(xs, router_w, w1, w3, w2, sel_bias):
        Bl, Sl, Dl = xs.shape
        xt = xs.reshape(Bl * Sl, Dl)
        logits = xt.astype(jnp.float32) @ router_w
        r = route(logits, _router_cfg(m), sel_bias)
        handle = ep_create_handle(group, r.topk_idx, r.topk_weights)
        # The staged surface is every backend's primitive (eager is defined
        # as send ∘ complete, core/backend.py), so the model layer uses it
        # unconditionally — same trace as the eager calls, no per-mode
        # branching, and the EpPending seam sits where a micro-batching
        # scheduler (runtime/prefill.py's schedule) would interleave expert
        # compute. For HT presets the send half is the whole (chunk-
        # pipelined, when hierarchical) collective stream.
        pend = ep_dispatch(group, handle, xt, send_only=True)
        y3d, counts = ep_complete(group, handle, pend)
        y3d = _expert_ffn(group, y3d, counts, w1, w3, w2, cfg.act, tp_axis)
        pc = ep_combine(group, handle, y3d, send_only=True)
        out = ep_complete(group, handle, pc).astype(xs.dtype)
        # aux losses averaged over the token-carrying axes (the value is
        # invariant along a pure-TP model axis — pmean there is ill-typed)
        aux = r.aux_loss + r.z_loss
        vary = tuple(dict.fromkeys(b_axes + s_axes))
        if vary:
            aux = jax.lax.pmean(aux, vary)
        if not with_heat:
            return out.reshape(Bl, Sl, Dl), aux
        # per-logical-expert routed-token heat (the EPLB rebalance signal);
        # psum over the token-carrying axes makes it the global histogram,
        # and it is invariant along a pure-TP model axis like aux
        heat = jnp.zeros((m.num_experts,), jnp.float32).at[
            r.topk_idx.reshape(-1)].add(1.0, mode="drop")
        if vary:
            heat = jax.lax.psum(heat, vary)
        return out.reshape(Bl, Sl, Dl), aux, heat

    sel = bias if bias is not None else jnp.zeros((m.num_experts,), jnp.float32)
    out_specs = (tok_spec, P(), P(None)) if with_heat else (tok_spec, P())
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(tok_spec, P(None, None), ew_spec, ew_spec, ew_spec_t, P(None)),
        out_specs=out_specs,
    )
    w1, w3, w2 = p["w_gate"], p["w_up"], p["w_down"]
    if m.placement is not None:
        if m.params_physical:
            # adopt-once mode (serving fast path): weights arrive ALREADY in
            # physical [N*S, ...] slot order — rebound host-side at the last
            # placement-adoption boundary (checkpoint.adopt_expert_params) —
            # so the per-step cross-rank gather is skipped entirely and the
            # placed steady state matches placement=None per-step cost.
            if w1.shape[0] != phys:
                raise ValueError(
                    f"params_physical=True: expert weights have "
                    f"{w1.shape[0]} rows but the placement defines {phys} "
                    "physical slots — rebind at adoption via "
                    "checkpoint.adopt_expert_params / rebind_expert_leaves")
        else:
            # logical mode (training default): params stay stored logical
            # [E, ...]; each physical slot gathers its expert's weights
            # (replicas duplicate) before the shard_map splits them over the
            # EP axes — resolved at the same altitude as the plan's slot
            # maps, never inside phase bodies. The gather runs per forward
            # step (cross-rank for moved experts), which keeps checkpoints
            # placement-independent across mid-epoch swaps.
            w1, w3, w2 = (expand_expert_params(w, m.placement)
                          for w in (w1, w3, w2))
    res = fn(x, p["router"], w1, w3, w2, sel)
    y, aux = res[0], res[1]
    if m.shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + ffn_apply(p["shared"], x, cfg.act)
    return (y, aux, res[2]) if with_heat else (y, aux)


def _moe_dense_fallback(p, x, cfg: ArchConfig, *, with_heat: bool = False,
                        live=None):
    """Dense MoE over the experts this chip holds (``MoESpec.held_experts``;
    all E by default), the one-chip path and the meshless reference: the
    router routes over all E experts, each held expert's SwiGLU runs over
    every token (named scope ``moe.experts``), weighted by its gate (zero
    where the token did not choose it), then the shared expert (scope
    ``moe.shared``). Holding all E it is the whole layer, with the EP
    path's semantics (same router, same expert math); holding one rank's
    slice it is that rank's part of the layer, plus the shared expert.

    -> y, or (y, heat [E]) with ``with_heat``; with ``live`` ([B] 0/1) one
    more entry, the held experts' load f32[2]: routed (token, expert)
    pairs of live rows on a held expert, and held experts with >= 1."""
    m = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    r = route(xt.astype(jnp.float32) @ p["router"], _router_cfg(m),
              p.get("sel_bias"))
    w1, w3, w2 = p["w_gate"], p["w_up"], p["w_down"]
    if m.params_physical and m.placement is not None:
        # the dense reference routes by logical expert: collapse physical
        # slot-ordered weights to logical order (primary replica)
        w1, w3, w2 = (collapse_expert_params(w, m.placement)
                      for w in (w1, w3, w2))
    held = (range(m.num_experts) if m.held_experts is None
            else m.held_experts)
    oh = (r.topk_idx[..., None]
          == jnp.asarray(held, jnp.int32)).astype(jnp.float32)  # [T, K, Eh]
    with jax.named_scope("moe.experts"):
        h_g = jnp.einsum("td,edf->tef", xt, w1)
        h_u = jnp.einsum("td,edf->tef", xt, w3)
        h = (jax.nn.silu(h_g.astype(jnp.float32)) * h_u.astype(jnp.float32)).astype(x.dtype)
        y_all = jnp.einsum("tef,efd->ted", h, w2)        # [T, Eh, D]
        gate = jnp.einsum("tk,tke->te", r.topk_weights, oh)  # [T, Eh]
        y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), gate).astype(x.dtype)
    y = y.reshape(B, S, D)
    if m.shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + ffn_apply(p["shared"], x, cfg.act)
    if not with_heat and live is None:
        return y
    out = (y,)
    if with_heat:
        out += (jnp.zeros((m.num_experts,), jnp.float32).at[
            r.topk_idx.reshape(-1)].add(1.0, mode="drop"),)
    if live is not None:
        rows = jnp.einsum("tke,t->e", oh,
                          jnp.repeat(live.astype(jnp.float32), S))
        out += (jnp.stack([rows.sum(), (rows > 0).sum().astype(jnp.float32)]),)
    return out

"""Decoder-LM assembly: scanned homogeneous layer stacks, per-family forward
and decode-step functions. Covers families: "lm" (GQA or MLA, dense or MoE),
"gemma3" (5:1 local:global super-blocks), "vlm" (lm + patch-embedding stub),
"ssm" (pure Mamba2). Hybrid (zamba2) and encdec (seamless) live in their own
modules but reuse the stack machinery here.

Scan-over-layers keeps the HLO O(1) in depth (the production-framework norm);
the dry-run's roofline corrects per-layer cost by trip count (docs/DESIGN.md §6).

Param-layout threading (docs/DESIGN.md §8): expert-stacked MoE weights ride
the scanned ``moe_stack`` as ``[n_moe, R, ...]`` where R follows the layout
mode — logical E by default, physical slot count (E + redundant replicas)
under ``MoESpec.params_physical``. The stack machinery is shape-agnostic, so
a placement adoption that changes the slot count simply retraces the decode
step with the new stacked shapes; everything *routing*-scoped stays logical
regardless of mode: the router/sel_bias specs, and the ``expert_heat``
decode-state counter, which is [E] per-LOGICAL-expert in both layouts (the
EPLB rebalancer consumes logical heat).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as ATT
from repro.models import mla as MLA
from repro.models import mamba2 as SSM
from repro.models import moe as MOE
from repro.models.config import ArchConfig
from repro.models.layers import (rmsnorm, rmsnorm_spec, ffn_spec, ffn_apply,
                                 embed_spec, embed_lookup, logits_out,
                                 cross_entropy)
from repro.parallel.sharding import ParamSpec, constrain


# --------------------------------------------------------------------------
# single decoder layer (dense or MoE FFN; GQA or MLA attention)
# --------------------------------------------------------------------------

def layer_spec(cfg: ArchConfig, *, moe_layer: bool):
    sp = dict(ln1=rmsnorm_spec(cfg.d_model, cfg.dtype),
              ln2=rmsnorm_spec(cfg.d_model, cfg.dtype))
    if cfg.attn and cfg.attn.kind == "mla":
        sp["attn"] = MLA.mla_spec(cfg)
    elif cfg.attn:
        sp["attn"] = ATT.attn_spec(cfg)
    if moe_layer:
        sp["moe"] = MOE.moe_spec(cfg)
    else:
        sp["ffn"] = ffn_spec(cfg.d_model, cfg.d_ff, cfg.dtype, cfg.act)
    return sp


def _ffn_half(p, x, cfg: ArchConfig, mesh, new_cache, with_heat, live=None):
    """The FFN half of a layer: ln2, then the MoE block (named scope
    ``moe``) or the dense FFN, plus the residual. -> (x, new_cache, aux);
    aux is the aux loss, followed by the expert heat with ``with_heat`` and
    the held experts' load with ``live`` (a tuple when either is asked)."""
    extra = with_heat or live is not None
    if "moe" in p:
        with jax.named_scope("moe"):
            h = rmsnorm(x, p["ln2"], cfg.norm_eps)
            f, *aux = MOE.moe_block(p["moe"], h, cfg, mesh,
                                    with_heat=with_heat, live=live)
            return x + f, new_cache, tuple(aux) if extra else aux[0]
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    f, aux = ffn_apply(p["ffn"], h, cfg.act), jnp.float32(0)
    if extra:
        E = cfg.moe.num_experts if cfg.moe else 1
        aux = ((aux,) + ((jnp.zeros((E,), jnp.float32),) if with_heat else ())
               + ((jnp.zeros((2,), jnp.float32),) if live is not None else ()))
    return x + f, new_cache, aux


def layer_apply(p, x, cfg: ArchConfig, mesh, *, cache=None, window="cfg",
                positions=None, with_heat=False):
    """-> (x, new_cache, aux). With ``with_heat=True`` aux is the pair
    (aux_loss, expert_heat [E]) — the per-logical-expert routed-token
    histogram the EPLB serving hook accumulates (runtime/server.py).
    Named scopes ``attn`` and ``moe`` mark the two halves in the compiled
    program's op metadata (the profiler trace's per-layer times)."""
    with jax.named_scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn and cfg.attn.kind == "mla":
            a, new_cache = MLA.mla_attention(p["attn"], h, cfg, mesh,
                                             cache=cache, positions=positions)
        else:
            a, new_cache = ATT.attention(p["attn"], h, cfg, mesh, cache=cache,
                                         window=window, positions=positions)
        x = x + a
    return _ffn_half(p, x, cfg, mesh, new_cache, with_heat)


def paged_layer_apply(p, x, cfg: ArchConfig, mesh, pool, page_tbl, kv_lens,
                      active, *, num_kv_splits: int, with_heat=False,
                      with_load=False):
    """layer_apply's paged-decode twin: attention runs against the paged KV
    pool (kernels/decode_attention via ops); the FFN/MoE half is identical.
    -> (x, new_pool, aux) with the same aux contract and scopes as
    layer_apply; ``with_load`` appends the held experts' load of the
    ``active`` rows to aux (``_ffn_half``)."""
    with jax.named_scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn and cfg.attn.kind == "mla":
            a, new_pool = MLA.paged_mla_attention(
                p["attn"], h, cfg, mesh, pool, page_tbl, kv_lens, active,
                num_kv_splits=num_kv_splits)
        else:
            a, new_pool = ATT.paged_attention(
                p["attn"], h, cfg, mesh, pool, page_tbl, kv_lens, active,
                num_kv_splits=num_kv_splits)
        x = x + a
    return _ffn_half(p, x, cfg, mesh, new_pool, with_heat,
                     live=active if with_load else None)


def _stack(specs, n: int):
    """Stack a layer's ParamSpec tree n times along a leading 'stack' axis."""
    def one(s: ParamSpec):
        return ParamSpec((n,) + s.shape, s.dtype, ("stack",) + (s.axes or (None,) * len(s.shape)),
                         init=s.init, scale=s.scale)
    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def _scan_stack(body, x, stack_params, stack_cache, cfg, *, remat: bool,
                aux0=None):
    """scan over (params, cache) stacks; body(x, p, c) -> (x, c', aux).
    ``aux0`` seeds the aux accumulator (default scalar 0); any pytree of the
    same structure as the body's aux adds leafwise — the decode path uses an
    (aux, expert_heat) pair to surface EPLB heat without changing the
    decode-step signature."""
    if aux0 is None:
        aux0 = jnp.float32(0)

    def f(carry, pc):
        x, aux = carry
        p, c = pc
        x, c2, a = body(x, p, c)
        return (x, jax.tree.map(jnp.add, aux, a)), c2
    if remat:
        f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), new_cache = jax.lax.scan(f, (x, aux0),
                                       (stack_params, stack_cache))
    return x, new_cache, aux


# --------------------------------------------------------------------------
# family: "lm" / "vlm"  (uniform stack, optional dense prefix, optional MTP)
# --------------------------------------------------------------------------

def lm_spec(cfg: ArchConfig):
    n_dense = cfg.moe.first_k_dense if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0
    sp = dict(
        embed=embed_spec(cfg.padded_vocab(), cfg.d_model, cfg.dtype),
        ln_f=rmsnorm_spec(cfg.d_model, cfg.dtype),
    )
    if n_dense:
        sp["dense_stack"] = _stack(layer_spec(cfg, moe_layer=False), n_dense)
    if n_moe:
        sp["moe_stack"] = _stack(layer_spec(cfg, moe_layer=True), n_moe)
    if not cfg.tie_embeddings:
        sp["lm_head"] = embed_spec(cfg.padded_vocab(), cfg.d_model, cfg.dtype)
    if cfg.mtp:  # DeepSeek-V3 multi-token prediction: one extra depth-1 layer
        sp["mtp_layer"] = layer_spec(cfg, moe_layer=bool(cfg.moe))
        sp["mtp_proj"] = ParamSpec((2 * cfg.d_model, cfg.d_model), cfg.dtype,
                                   ("embed", "embed"))
        sp["mtp_ln"] = rmsnorm_spec(cfg.d_model, cfg.dtype)
    return sp


def _empty_caches(n):
    return jnp.zeros((n, 0)) if n else None


def lm_forward(params, batch, cfg: ArchConfig, mesh):
    """Training/prefill forward. batch: {tokens [B,S], (img_embeds [B,P,D])}.
    Returns (loss, aux dict) — loss includes CE + router aux + MTP term."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens)
    if cfg.family == "vlm" and "img_embeds" in batch:
        P_img = batch["img_embeds"].shape[1]
        x = jnp.concatenate([batch["img_embeds"].astype(x.dtype),
                             x[:, P_img:]], axis=1)
    x = constrain(x, mesh, "batch", None, None)
    aux = jnp.float32(0)
    n_dense = cfg.moe.first_k_dense if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0

    def body(x, p, c):
        return layer_apply(p, x, cfg, mesh, cache=None)

    if n_dense:
        x, _, a = _scan_stack(body, x, params["dense_stack"],
                              _empty_caches(n_dense), cfg, remat=cfg.remat)
        aux += a
    if n_moe:
        x, _, a = _scan_stack(body, x, params["moe_stack"],
                              _empty_caches(n_moe), cfg, remat=cfg.remat)
        aux += a
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = logits_out(x, head)
    targets = batch.get("targets")
    if targets is None:
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = batch.get("loss_mask")
    loss = cross_entropy(logits, targets, mask)

    if cfg.mtp:
        # depth-1 MTP: predict t+2 from [h_t ; emb(t+1)]
        nxt = embed_lookup(params["embed"], targets)
        h2 = jnp.concatenate([x, nxt], axis=-1) @ params["mtp_proj"]
        h2 = rmsnorm(h2, params["mtp_ln"], cfg.norm_eps)
        h2, _, a2 = layer_apply(params["mtp_layer"], h2, cfg, mesh)
        aux += a2
        mtp_logits = logits_out(h2, head)
        t2 = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
        loss = loss + 0.3 * cross_entropy(mtp_logits, t2, mask)

    return loss + aux, dict(aux=aux)


def _lm_head(params, x, cfg: ArchConfig):
    """Final norm and LM head of a decode step (named scope ``head``)."""
    with jax.named_scope("head"):
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return logits_out(x, head)


def lm_decode_state_spec(cfg: ArchConfig, batch: int, max_len: int, *, long=False):
    n_dense = cfg.moe.first_k_dense if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0
    mk = (MLA.mla_cache_spec if (cfg.attn and cfg.attn.kind == "mla")
          else ATT.kv_cache_spec)
    st = {}
    if n_dense:
        st["dense"] = _stack(mk(cfg, batch, max_len, long=long), n_dense)
    if n_moe:
        st["moe"] = _stack(mk(cfg, batch, max_len, long=long), n_moe)
        if cfg.moe.track_expert_heat:
            # EPLB heat counters ride the decode state: per-LOGICAL-expert
            # routed tokens summed over MoE layers and steps (replicated).
            # Deliberately [E] in both param-layout modes — heat drives the
            # rebalancer, which reasons about logical experts; a placement
            # adoption therefore never invalidates the decode state.
            st["expert_heat"] = ParamSpec((cfg.moe.num_experts,), jnp.float32,
                                          (None,), init="zeros")
    return st


def lm_decode_step(params, state, batch, cfg: ArchConfig, mesh):
    """One decode step. batch: {tokens [B,1]}. -> (logits [B,1,V], state)."""
    x = embed_lookup(params["embed"], batch["tokens"])
    x = constrain(x, mesh, "batch", None, None)
    new_state = dict(state)

    def body(x, p, c):
        return layer_apply(p, x, cfg, mesh, cache=c)

    if "dense" in state:
        x, new_state["dense"], _ = _scan_stack(
            body, x, params["dense_stack"], state["dense"], cfg, remat=False)
    if "moe" in state:
        if "expert_heat" in state:
            def body_heat(x, p, c):
                return layer_apply(p, x, cfg, mesh, cache=c, with_heat=True)
            aux0 = (jnp.float32(0),
                    jnp.zeros((cfg.moe.num_experts,), jnp.float32))
            x, new_state["moe"], (_, heat) = _scan_stack(
                body_heat, x, params["moe_stack"], state["moe"], cfg,
                remat=False, aux0=aux0)
            new_state["expert_heat"] = state["expert_heat"] + heat
        else:
            x, new_state["moe"], _ = _scan_stack(
                body, x, params["moe_stack"], state["moe"], cfg, remat=False)
    return _lm_head(params, x, cfg), new_state


def lm_paged_decode_state_spec(cfg: ArchConfig, num_pages: int,
                               page_size: int):
    """Paged twin of lm_decode_state_spec: per-layer page POOLS instead of
    dense [B, S_max] caches. The page table / kv_lens / active mask are NOT
    device state — they are host-built per-step batch inputs (jit-stable
    shapes; runtime/scheduler.py owns them), so join/leave/recycle never
    retraces the step."""
    from repro.models import kv_pages as KVP
    n_dense = cfg.moe.first_k_dense if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0
    mk = (KVP.paged_mla_pool_spec if (cfg.attn and cfg.attn.kind == "mla")
          else KVP.paged_kv_pool_spec)
    st = {}
    if n_dense:
        st["dense"] = _stack(mk(cfg, num_pages, page_size), n_dense)
    if n_moe:
        st["moe"] = _stack(mk(cfg, num_pages, page_size), n_moe)
        if cfg.moe.track_expert_heat:
            # same logical-[E] heat contract as the dense decode state
            st["expert_heat"] = ParamSpec((cfg.moe.num_experts,), jnp.float32,
                                          (None,), init="zeros")
        if cfg.moe.held_experts is not None:
            # the last step's held-expert load, summed over the MoE layers:
            # [live routed rows on held experts, held experts hit] — the
            # engine reads it back with the step's tokens
            st["held_load"] = ParamSpec((2,), jnp.float32, (None,),
                                        init="zeros")
    return st


def _decode_splits(cfg: ArchConfig, max_pages: int) -> int:
    """Largest split count <= AttnSpec.decode_kv_splits dividing the page-
    table width (static shapes only — resolved at trace time)."""
    s = max(min(cfg.attn.decode_kv_splits, max_pages), 1)
    while max_pages % s:
        s -= 1
    return s


def lm_paged_decode_step(params, state, batch, cfg: ArchConfig, mesh):
    """One paged decode step. batch: {tokens [B,1], page_tbl [B,max_pages],
    kv_lens [B], active [B]}. -> (logits [B,1,V], state). Idle rows (active
    == 0, all-pad tables) compute deterministic garbage that lands in the
    pad page and zero attention context — the scheduler discards their
    logits, and live rows provably can't see them (exact masking)."""
    x = embed_lookup(params["embed"], batch["tokens"])
    x = constrain(x, mesh, "batch", None, None)
    tbl = batch["page_tbl"].astype(jnp.int32)
    lens = batch["kv_lens"].astype(jnp.int32)
    act = batch["active"].astype(jnp.int32)
    splits = _decode_splits(cfg, tbl.shape[1])
    new_state = dict(state)

    def body(x, p, c):
        return paged_layer_apply(p, x, cfg, mesh, c, tbl, lens, act,
                                 num_kv_splits=splits)

    if "dense" in state:
        x, new_state["dense"], _ = _scan_stack(
            body, x, params["dense_stack"], state["dense"], cfg, remat=False)
    if "moe" in state:
        heat, load = "expert_heat" in state, "held_load" in state
        if heat or load:
            def body_aux(x, p, c):
                return paged_layer_apply(p, x, cfg, mesh, c, tbl, lens, act,
                                         num_kv_splits=splits, with_heat=heat,
                                         with_load=load)
            aux0 = ((jnp.float32(0),)
                    + ((jnp.zeros((cfg.moe.num_experts,), jnp.float32),)
                       if heat else ())
                    + ((jnp.zeros((2,), jnp.float32),) if load else ()))
            x, new_state["moe"], aux = _scan_stack(
                body_aux, x, params["moe_stack"], state["moe"], cfg,
                remat=False, aux0=aux0)
            if heat:
                new_state["expert_heat"] = state["expert_heat"] + aux[1]
            if load:
                new_state["held_load"] = aux[-1]
        else:
            x, new_state["moe"], _ = _scan_stack(
                body, x, params["moe_stack"], state["moe"], cfg, remat=False)
    return _lm_head(params, x, cfg), new_state


# --------------------------------------------------------------------------
# family: "gemma3"  (super-blocks of 5 local + 1 global)
# --------------------------------------------------------------------------

def _g3_counts(cfg):
    loc, glob = cfg.local_global
    per = loc + glob
    n_super = cfg.num_layers // per
    tail = cfg.num_layers - n_super * per
    return loc, glob, n_super, tail


def gemma3_spec(cfg: ArchConfig):
    loc, glob, n_super, tail = _g3_counts(cfg)
    per = loc + glob
    sb = _stack(layer_spec(cfg, moe_layer=False), per)      # [per, ...]
    sp = dict(
        embed=embed_spec(cfg.padded_vocab(), cfg.d_model, cfg.dtype),
        ln_f=rmsnorm_spec(cfg.d_model, cfg.dtype),
        super=_stack(sb, n_super),                          # [n_super, per, ...]
    )
    if tail:
        sp["tail"] = _stack(layer_spec(cfg, moe_layer=False), tail)
    return sp


def gemma3_forward(params, batch, cfg: ArchConfig, mesh):
    loc, glob, n_super, tail = _g3_counts(cfg)
    per = loc + glob
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)        # gemma embed scale

    def super_body(x, p, c):
        aux = jnp.float32(0)
        for j in range(per):
            pj = jax.tree.map(lambda a: a[j], p)
            w = cfg.local_window if j < loc else None
            x, _, a = layer_apply(pj, x, cfg, mesh, window=w)
            aux += a
        return x, c, aux

    x, _, _ = _scan_stack(super_body, x, params["super"],
                          _empty_caches(n_super), cfg, remat=cfg.remat)
    if tail:
        def body(x, p, c):
            return layer_apply(p, x, cfg, mesh, window=cfg.local_window)
        x, _, _ = _scan_stack(body, x, params["tail"], _empty_caches(tail),
                              cfg, remat=cfg.remat)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_out(x, params["embed"])                 # gemma ties embeds
    targets = batch.get("targets")
    if targets is None:
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    return cross_entropy(logits, targets, batch.get("loss_mask")), {}


def gemma3_decode_state_spec(cfg: ArchConfig, batch: int, max_len: int, *, long=False):
    loc, glob, n_super, tail = _g3_counts(cfg)
    wlen = min(cfg.local_window, max_len)
    lc = ATT.kv_cache_spec(cfg, batch, wlen)                # ring, local
    gc = ATT.kv_cache_spec(cfg, batch, max_len, long=long)  # linear, global
    st = dict(
        local=_stack(_stack(lc, loc), n_super),             # [n_super, loc, ...]
        globl=_stack(_stack(gc, glob), n_super),
    )
    if tail:
        st["tail"] = _stack(lc, tail)
    return st


def gemma3_decode_step(params, state, batch, cfg: ArchConfig, mesh):
    loc, glob, n_super, tail = _g3_counts(cfg)
    per = loc + glob
    x = embed_lookup(params["embed"], batch["tokens"])
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    wlen = state["local"]["k"].shape[3] if isinstance(state["local"], dict) \
        else jax.tree.leaves(state["local"])[0].shape[3]

    def super_body(x, pc, cc):
        p, (c_loc, c_glob) = pc, cc
        new_loc, new_glob = [], []
        aux = jnp.float32(0)
        for j in range(per):
            pj = jax.tree.map(lambda a: a[j], p)
            if j < loc:
                cj = jax.tree.map(lambda a: a[j], c_loc)
                x, cj2, _ = _ring_local_decode(pj, x, cfg, mesh, cj, wlen)
                new_loc.append(cj2)
            else:
                cj = jax.tree.map(lambda a: a[j - loc], c_glob)
                x, cj2, _ = layer_apply(pj, x, cfg, mesh, cache=cj, window=None)
                new_glob.append(cj2)
        stk = lambda cs: jax.tree.map(lambda *a: jnp.stack(a), *cs)
        return x, (stk(new_loc), stk(new_glob)), aux

    def f(carry, pc):
        x = carry
        p, c = pc[0], (pc[1], pc[2])
        x, c2, _ = super_body(x, p, c)
        return x, c2
    x, (nl, ng) = jax.lax.scan(f, x, (params["super"], state["local"], state["globl"]))
    new_state = dict(state, local=nl, globl=ng)
    if tail:
        def body(x, p, c):
            return _ring_local_decode(p, x, cfg, mesh, c, wlen)
        x, new_state["tail"], _ = _scan_stack(body, x, params["tail"],
                                              state["tail"], cfg, remat=False)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return logits_out(x, params["embed"]), new_state


def _ring_local_decode(p, x, cfg, mesh, cache, wlen):
    """Sliding-window decode with a ring KV cache of length `wlen`: write at
    length % wlen; key positions reconstructed from the ring arithmetic."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    B, S, _ = x.shape
    pos = cache.length                                       # absolute position
    slot = pos % wlen
    q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
    a = cfg.attn
    pvec = jnp.broadcast_to(pos[None, None], (B, S))
    from repro.models.layers import apply_rope
    q = apply_rope(q, pvec, a.rope_base, a.rope_fraction)
    k = apply_rope(k, pvec, a.rope_base, a.rope_fraction)
    kc = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
    # slot i holds absolute position: the largest p <= pos with p % wlen == i
    idx = jnp.arange(wlen)
    k_pos = pos - ((pos - idx) % wlen)
    mask = (k_pos >= 0) & (k_pos <= pos) & (pos - k_pos < wlen)
    o = ATT._sdpa(q, kc, vc, mask[None, :].repeat(S, 0), a.logit_softcap,
                  a.head_dim ** -0.5)
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + ffn_apply(p["ffn"], h2, cfg.act)
    return x, ATT.KVCache(k=kc, v=vc, length=cache.length + S), jnp.float32(0)


# --------------------------------------------------------------------------
# family: "ssm"  (pure Mamba2)
# --------------------------------------------------------------------------

def ssm_spec(cfg: ArchConfig):
    lay = dict(ln=rmsnorm_spec(cfg.d_model, cfg.dtype),
               mamba=SSM.mamba_spec(cfg))
    return dict(
        embed=embed_spec(cfg.padded_vocab(), cfg.d_model, cfg.dtype),
        ln_f=rmsnorm_spec(cfg.d_model, cfg.dtype),
        stack=_stack(lay, cfg.num_layers),
    )


def ssm_forward(params, batch, cfg: ArchConfig, mesh):
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens)

    def body(x, p, c):
        y, _ = SSM.mamba_block(p["mamba"], rmsnorm(x, p["ln"], cfg.norm_eps),
                               cfg, mesh)
        return x + y, c, jnp.float32(0)

    x, _, _ = _scan_stack(body, x, params["stack"],
                          _empty_caches(cfg.num_layers), cfg, remat=cfg.remat)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_out(x, params["embed"])
    targets = batch.get("targets")
    if targets is None:
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    return cross_entropy(logits, targets, batch.get("loss_mask")), {}


def ssm_decode_state_spec(cfg: ArchConfig, batch: int, max_len: int, *, long=False):
    return dict(stack=_stack(SSM.ssm_cache_spec(cfg, batch), cfg.num_layers))


def ssm_decode_step(params, state, batch, cfg: ArchConfig, mesh):
    x = embed_lookup(params["embed"], batch["tokens"])

    def body(x, p, c):
        y, c2 = SSM.mamba_block(p["mamba"], rmsnorm(x, p["ln"], cfg.norm_eps),
                                cfg, mesh, cache=c)
        return x + y, c2, jnp.float32(0)

    x, new_stack, _ = _scan_stack(body, x, params["stack"], state["stack"],
                                  cfg, remat=False)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return logits_out(x, params["embed"]), dict(stack=new_stack)

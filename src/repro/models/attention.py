"""GQA/MHA attention with RoPE, sliding windows, logit softcap, KV caches.

Train/prefill: full causal (optionally windowed) attention, fp32 scores.
Decode: one-token query against a static-capacity KV cache updated with
``dynamic_update_slice``; the cache's sequence axis carries a logical sharding
axis ("kv_seq" / "kv_seq_long"), so on the production mesh the scores/softmax
reduce over a sharded axis and GSPMD inserts the split-KV all-reduces
(flash-decoding's parallelism, expressed declaratively).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig, AttnSpec
from repro.models.layers import apply_rope, rmsnorm
from repro.parallel.sharding import ParamSpec, constrain


def attn_spec(cfg: ArchConfig, dtype=None):
    a = cfg.attn
    dtype = dtype or cfg.dtype
    d, hq, hkv, hd = cfg.d_model, cfg.padded_heads(), a.n_kv, a.head_dim
    sp = dict(
        wq=ParamSpec((d, hq, hd), dtype, ("embed", "heads", None)),
        wk=ParamSpec((d, hkv, hd), dtype, ("embed", "kv_heads", None)),
        wv=ParamSpec((d, hkv, hd), dtype, ("embed", "kv_heads", None)),
        wo=ParamSpec((hq, hd, d), dtype, ("heads", None, "embed")),
    )
    if a.qk_norm:
        sp["q_norm"] = ParamSpec((hd,), dtype, (None,), init="ones")
        sp["k_norm"] = ParamSpec((hd,), dtype, (None,), init="ones")
    return sp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array          # [B, S_max, n_kv, hd]
    v: jax.Array          # [B, S_max, n_kv, hd]
    length: jax.Array     # [] int32 — filled prefix


def kv_cache_spec(cfg: ArchConfig, batch: int, max_len: int, *,
                  long: bool = False, n_kv: int | None = None,
                  head_dim: int | None = None):
    a = cfg.attn
    seq_ax = "kv_seq_long" if long else "kv_seq"
    n_kv = n_kv or a.n_kv
    hd = head_dim or a.head_dim
    arr = ParamSpec((batch, max_len, n_kv, hd), cfg.dtype,
                    ("batch", seq_ax, "kv_heads", None))
    return KVCache(k=arr, v=arr,
                   length=ParamSpec((), jnp.int32, (), init="zeros"))


def _scores_mask(q_pos, k_pos, window):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


# Sequence length at/above which training/prefill attention switches to the
# chunked online-softmax dataflow (flash attention expressed in XLA): the
# [Sq, Sk] score matrix never materializes to HBM — per-chunk tiles live in
# registers/VMEM after fusion. Dropped the prefill memory roofline term ~9x
# on the minicpm3 prefill_32k cell (docs/EXPERIMENTS.md §Perf M1).
CHUNKED_ATTN_THRESHOLD = 2048


def _sdpa_chunked(q, k, v, softcap, scale, window, chunk=1024):
    """Causal grouped attention with online softmax over KV chunks.

    The chunk width comes from ``AttnSpec.kv_chunk`` at model call sites
    (page-size-aligned in the paged serving engine). Ragged tails
    (Sk % chunk != 0) are zero-padded and masked out exactly."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(B, Sq, Hkv, G, hd)
    q_pos = jnp.arange(Sq)
    n = (Sk + pad) // chunk
    kc = k.reshape(B, n, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m, l, acc = carry
        ci, (k_c, v_c) = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_c,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = ci * chunk + jnp.arange(chunk)
        msk = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < Sk)[None, :]
        if window is not None:
            msk &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(msk[None, None, None], s, -1e30)
        m2 = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m2[..., None])
        corr = jnp.exp(m - m2)
        l2 = l * corr + p.sum(-1)
        acc2 = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(q.dtype), v_c,
            preferred_element_type=jnp.float32)
        return (m2, l2, acc2), None

    m0 = jnp.full((B, Hkv, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    # full unroll: keeps the dry-run cost accounting exact (a while-loop body
    # would be counted once) and matches how flash kernels pipeline chunks.
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(n), (kc, vc)), unroll=True)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hd).astype(q.dtype)


def _sdpa(q, k, v, mask, softcap, scale):
    """q: [B,Sq,Hq,hd], k/v: [B,Sk,Hkv,hd] — grouped attention.

    Scores accumulate in f32 via preferred_element_type (the MXU-native form)
    WITHOUT materializing f32 copies of K/V — casting the cache would double
    decode HBM traffic (measured: 39.6->21GB bytes-accessed on the
    internlm2 decode_32k cell, see docs/EXPERIMENTS.md §Perf)."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, Hq, hd).astype(q.dtype)


def paged_attention(p, x, cfg: ArchConfig, mesh, pool, page_tbl, kv_lens,
                    active, *, num_kv_splits: int = 1,
                    attn: AttnSpec | None = None):
    """One-token decode attention against the paged KV pool.

    x: [B, 1, D]; pool: {"k", "v"} [P+1, page, n_kv, hd] (models/kv_pages);
    page_tbl: [B, max_pages] int32 (pad entries = P); kv_lens: [B] int32
    tokens already held; active: [B] int32 0/1. Writes this token's K/V at
    (tbl[b, len//page], len % page), then runs the split-KV paged decode
    kernel over len + active positions (idle rows attend over nothing and
    return exact zeros). Returns (y [B, 1, D], new_pool)."""
    a = attn or cfg.attn
    if a.window is not None:
        raise NotImplementedError("paged decode attention does not support "
                                  "sliding-window layers")
    if a.logit_softcap is not None:
        raise NotImplementedError("paged decode attention does not support "
                                  "logit softcap")
    from repro.models.kv_pages import decode_attention, write_token
    positions = kv_lens[:, None]                           # [B, 1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if a.rope_fraction > 0:
        q = apply_rope(q, positions, a.rope_base, a.rope_fraction)
        k = apply_rope(k, positions, a.rope_base, a.rope_fraction)
    q = constrain(q, mesh, "batch", None, "heads", None)
    kp = write_token(pool["k"], k[:, 0], page_tbl, kv_lens)
    vp = write_token(pool["v"], v[:, 0], page_tbl, kv_lens)
    eff = kv_lens + active            # just-written token counts iff active
    with jax.named_scope("paged_decode"):
        out = decode_attention(mesh, q[:, 0], kp, vp, page_tbl, eff,
                               scale=a.head_dim ** -0.5,
                               num_kv_splits=num_kv_splits)
    out = out.astype(x.dtype)[:, None]                     # [B, 1, Hq, hd]
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": kp, "v": vp}


def attention(p, x, cfg: ArchConfig, mesh, *, positions=None,
              cache: KVCache | None = None, window: int | None = "cfg",
              attn: AttnSpec | None = None, kv_override=None,
              causal: bool = True):
    """Returns (out [B,S,D], new_cache)."""
    a = attn or cfg.attn
    if window == "cfg":
        window = a.window
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cache is not None:
            positions = positions + cache.length

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    else:  # cross-attention: kv computed from encoder memory by the caller
        k, v = kv_override
    if a.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if a.rope_fraction > 0 and kv_override is None:
        q = apply_rope(q, positions, a.rope_base, a.rope_fraction)
        k = apply_rope(k, positions, a.rope_base, a.rope_fraction)
    q = constrain(q, mesh, "batch", None, "heads", None)
    scale = a.head_dim ** -0.5

    if cache is None and kv_override is None:
        if causal and S >= CHUNKED_ATTN_THRESHOLD:
            if (a.logit_softcap is None and jax.default_backend() == "tpu"
                    and S % 128 == 0):
                from repro.kernels import ops as KOPS
                out = KOPS.flash_attention_bshd(q, k, v, scale=scale,
                                                window=window)
            else:
                out = _sdpa_chunked(q, k, v, a.logit_softcap, scale, window,
                                    chunk=a.kv_chunk)
        else:
            q_pos = jnp.arange(S)
            mask = (_scores_mask(q_pos, q_pos, window) if causal
                    else jnp.ones((S, S), bool))
            out = _sdpa(q, k, v, mask, a.logit_softcap, scale)
        new_cache = None
    elif kv_override is not None:
        Sk = k.shape[1]
        mask = jnp.ones((S, Sk), bool)     # full cross-attention
        out = _sdpa(q, k, v, mask, a.logit_softcap, scale)
        new_cache = None
    else:
        # decode: append to cache, attend over the filled prefix
        kc = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                          (0, cache.length, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                          (0, cache.length, 0, 0))
        new_len = cache.length + S
        k_pos = jnp.arange(kc.shape[1])
        valid = k_pos < new_len
        q_pos = positions[0]               # [S]
        mask = _scores_mask(q_pos, k_pos, window) & valid[None, :]
        out = _sdpa(q, kc, vc, mask, a.logit_softcap, scale)
        new_cache = KVCache(k=kc, v=vc, length=new_len)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache

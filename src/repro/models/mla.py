"""Multi-head Latent Attention (DeepSeek-V2/V3, MiniCPM3).

Train/prefill: queries via low-rank q path; keys/values decompressed from the
shared latent ``c_kv`` plus a single shared RoPE key head.

Decode: the *absorbed* formulation — cache only [c_kv (r_kv) | k_rope] per
token (the whole point of MLA: DeepSeek-V3 caches 512+64 floats/token instead
of 128 heads x 128). W_uk is absorbed into the query and W_uv into the output
projection, so scores are taken directly against the compressed cache.

Rope: with ``MLASpec.rope_scaling`` (DeepSeek-V3's YaRN) the rotary dims take
YaRN's frequencies, and the softmax scale (nope + rope)^-0.5 is multiplied
by mscale(factor, mscale_all_dim)² (``softmax_scale``), in prefill and in
paged decode alike.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, rmsnorm, yarn_mscale
from repro.parallel.sharding import ParamSpec, constrain


def mla_spec(cfg: ArchConfig, dtype=None):
    m, d = cfg.mla, cfg.d_model
    dtype = dtype or cfg.dtype
    h = cfg.padded_heads()
    qk = m.qk_nope_dim + m.qk_rope_dim
    return dict(
        wq_a=ParamSpec((d, m.q_lora_rank), dtype, ("embed", "lora")),
        q_norm=ParamSpec((m.q_lora_rank,), dtype, ("lora",), init="ones"),
        wq_b=ParamSpec((m.q_lora_rank, h, qk), dtype, ("lora", "heads", None)),
        wkv_a=ParamSpec((d, m.kv_lora_rank + m.qk_rope_dim), dtype, ("embed", "lora")),
        kv_norm=ParamSpec((m.kv_lora_rank,), dtype, ("lora",), init="ones"),
        wk_b=ParamSpec((m.kv_lora_rank, h, m.qk_nope_dim), dtype,
                       ("lora", "heads", None)),
        wv_b=ParamSpec((m.kv_lora_rank, h, m.v_head_dim), dtype,
                       ("lora", "heads", None)),
        wo=ParamSpec((h, m.v_head_dim, d), dtype, ("heads", None, "embed")),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    ckv: jax.Array        # [B, S_max, r_kv] compressed latents
    krope: jax.Array      # [B, S_max, rope_dim] shared rope key
    length: jax.Array


def mla_cache_spec(cfg: ArchConfig, batch: int, max_len: int, *, long=False):
    m = cfg.mla
    seq_ax = "kv_seq_long" if long else "kv_seq"
    return MLACache(
        ckv=ParamSpec((batch, max_len, m.kv_lora_rank), cfg.dtype,
                      ("batch", seq_ax, None)),
        krope=ParamSpec((batch, max_len, m.qk_rope_dim), cfg.dtype,
                        ("batch", seq_ax, None)),
        length=ParamSpec((), jnp.int32, (), init="zeros"),
    )


def _dot32(eq, *ops):
    """f32-accumulating einsum. XLA:CPU's DotThunk cannot *execute* some
    bf16xbf16=f32 dots (it compiles them fine), so on CPU we upcast operands;
    on TPU this is the native MXU mixed-precision form."""
    if jax.default_backend() == "cpu":
        return jnp.einsum(eq, *(o.astype(jnp.float32) for o in ops))
    return jnp.einsum(eq, *ops, preferred_element_type=jnp.float32)


def softmax_scale(cfg: ArchConfig) -> float:
    """(nope + rope)^-0.5, times mscale(factor, mscale_all_dim)² under YaRN
    with ``mscale_all_dim`` set (DeepSeek-V3: × 1.3689² at factor 40)."""
    m = cfg.mla
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    y = m.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg):
    """Rotary part [B, S, H, rope] at ``positions``, with the config's rope
    scaling."""
    return apply_rope(x, positions, cfg.attn.rope_base, 1.0,
                      scaling=cfg.mla.rope_scaling)


def _q_proj(p, x, cfg, positions):
    m = cfg.mla
    B, S, _ = x.shape
    q = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])     # [B,S,H,nope+rope]
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    return q_nope, _rope(q_rope, positions, cfg)


def _mla_chunked(p, q_nope, q_rope, ckv, k_rope, scale, out_dtype, chunk=1024):
    """Online-softmax MLA attention; K/V decompressed one chunk at a time.

    Chunk width from ``AttnSpec.kv_chunk`` at call sites; ragged tails
    (S % chunk != 0) are zero-padded and masked out exactly."""
    B, Sq, H, dn = q_nope.shape
    S = ckv.shape[1]
    pad = (-S) % chunk
    if pad:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    n = (S + pad) // chunk
    ckv_c = ckv.reshape(B, n, chunk, -1).transpose(1, 0, 2, 3)
    kr_c = k_rope.reshape(B, n, chunk, -1).transpose(1, 0, 2, 3)
    q_pos = jnp.arange(Sq)
    dv = p["wv_b"].shape[-1]

    def body(carry, xs):
        m, l, acc = carry
        ci, (ck, kr) = xs
        k_nope = jnp.einsum("bsr,rhk->bshk", ck, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", ck, p["wv_b"])
        s = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope,
                        preferred_element_type=jnp.float32) +
             jnp.einsum("bqhk,bsk->bhqs", q_rope, kr,
                        preferred_element_type=jnp.float32)) * scale
        k_pos = ci * chunk + jnp.arange(chunk)
        msk = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < S)[None, :]
        s = jnp.where(msk[None, None], s, -1e30)
        m2 = jnp.maximum(m, s.max(-1))
        pb = jnp.exp(s - m2[..., None])
        corr = jnp.exp(m - m2)
        l2 = l * corr + pb.sum(-1)
        acc2 = acc * corr[..., None] + jnp.einsum(
            "bhqs,bshk->bhqk", pb.astype(out_dtype), v,
            preferred_element_type=jnp.float32)
        return (m2, l2, acc2), None

    m0 = jnp.full((B, H, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, dv), jnp.float32)
    # full unroll: exact dry-run cost accounting (see attention.py)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(n), (ckv_c, kr_c)), unroll=True)
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # [B,H,Sq,dv]
    return out.transpose(0, 2, 1, 3)                   # [B,Sq,H,dv]


def paged_mla_attention(p, x, cfg: ArchConfig, mesh, pool, page_tbl, kv_lens,
                        active, *, num_kv_splits: int = 1):
    """One-token absorbed-MLA decode against the paged latent pools.

    pool: {"ckv"} [P+1, page, 1, r_kv] and {"krope"} [P+1, page, 1, rope]
    (models/kv_pages.paged_mla_pool_spec): the query is [q_absorbed |
    q_rope], scored against c_kv and k_rope, and the values are c_kv, so
    each page's latent row is read from HBM once (share_kv mode of
    kernels/decode_attention). Returns (y, new_pool)."""
    from repro.models.kv_pages import decode_attention, write_token
    m = cfg.mla
    positions = kv_lens[:, None]                           # [B, 1]
    kv = x @ p["wkv_a"]                                    # [B, 1, r_kv+rope]
    ckv = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kv[..., None, m.kv_lora_rank:], positions, cfg)[:, :, 0]
    q_nope, q_rope = _q_proj(p, x, cfg, positions)
    # [B, 1, width] rows are [B, Hkv=1, width] tokens for the scatter
    ckv_p = write_token(pool["ckv"], ckv, page_tbl, kv_lens)
    kr_p = write_token(pool["krope"], k_rope, page_tbl, kv_lens)
    q_abs = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])  # absorb W_uk
    qcat = jnp.concatenate([q_abs, q_rope], axis=-1)[:, 0]   # [B, H, r+rope]
    eff = kv_lens + active
    with jax.named_scope("paged_decode"):
        ctx = decode_attention(mesh, qcat, ckv_p, None, page_tbl, eff,
                               rope_pages=kr_p, scale=softmax_scale(cfg),
                               num_kv_splits=num_kv_splits)  # [B, H, r] f32
    o = jnp.einsum("bhr,rhk->bhk", ctx.astype(x.dtype), p["wv_b"])  # absorb W_uv
    y = jnp.einsum("bqhk,hkd->bqd", o[:, None], p["wo"])
    return y, {"ckv": ckv_p, "krope": kr_p}


def mla_attention(p, x, cfg: ArchConfig, mesh, *, positions=None,
                  cache: MLACache | None = None):
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.padded_heads()
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cache is not None:
            positions = positions + cache.length
    scale = softmax_scale(cfg)

    kv = x @ p["wkv_a"]                                # [B,S,r_kv+rope]
    ckv = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kv[..., None, m.kv_lora_rank:], positions,
                   cfg)[:, :, 0]                       # [B,S,rope]
    q_nope, q_rope = _q_proj(p, x, cfg, positions)

    if cache is None:
        from repro.models.attention import CHUNKED_ATTN_THRESHOLD
        if S >= CHUNKED_ATTN_THRESHOLD:
            # chunked online softmax WITH per-chunk latent decompression:
            # the full per-head K/V ([B,S,H,d]) never materializes — only the
            # compressed ckv ([B,S,r_kv]) is resident, the MLA memory win at
            # prefill (docs/EXPERIMENTS.md §Perf M1).
            o = _mla_chunked(p, q_nope, q_rope, ckv, k_rope, scale, x.dtype,
                             chunk=cfg.attn.kv_chunk)
        else:
            k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
            v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"])
            sn = jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
            sr = jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope,
                            preferred_element_type=jnp.float32)
            s = (sn + sr) * scale
            q_pos = jnp.arange(S)
            mask = q_pos[None, :] <= q_pos[:, None]    # [Sk<=Sq] causal
            s = jnp.where(mask.T[None, None], s, -1e30)
            prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            o = jnp.einsum("bhqs,bshk->bqhk", prob, v,
                           preferred_element_type=jnp.float32)
        new_cache = None
    else:
        # absorbed decode: score against the compressed cache directly
        ckv_c = jax.lax.dynamic_update_slice(
            cache.ckv, ckv.astype(cache.ckv.dtype), (0, cache.length, 0))
        kr_c = jax.lax.dynamic_update_slice(
            cache.krope, k_rope.astype(cache.krope.dtype), (0, cache.length, 0))
        new_len = cache.length + S
        q_abs = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])  # absorb W_uk
        s = (_dot32("bqhr,bsr->bhqs", q_abs, ckv_c) +
             _dot32("bqhk,bsk->bhqs", q_rope, kr_c)) * scale
        k_pos = jnp.arange(ckv_c.shape[1])
        mask = (k_pos[None] <= positions[0][:, None]) & (k_pos < new_len)[None]
        s = jnp.where(mask[None, None], s, -1e30)
        prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        ctx = _dot32("bhqs,bsr->bqhr", prob, ckv_c)
        o = jnp.einsum("bqhr,rhk->bqhk", ctx.astype(x.dtype), p["wv_b"])  # absorb W_uv
        new_cache = MLACache(ckv=ckv_c, krope=kr_c, length=new_len)

    y = jnp.einsum("bqhk,hkd->bqd", o.astype(x.dtype), p["wo"])
    return y, new_cache

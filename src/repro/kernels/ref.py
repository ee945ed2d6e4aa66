"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each Pallas kernel's test sweeps shapes and
dtypes and asserts allclose against the function of the same name here. They
are also the production path on non-TPU backends (interpret-mode Pallas is
orders of magnitude slower on CPU; XLA fuses these fine there).

``positions_by_dest`` is the one exception to the "Pallas oracle" rule: it is
the O(M·D) one-hot-cumsum oracle for the sort-based O(M log M) production
implementation in ``repro.core.slots`` (bitwise-identical by contract).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def positions_by_dest(dest: jax.Array, num_dest: int, valid: jax.Array):
    """One-hot-cumsum slot-position oracle (the seed implementation).

    O(M·D) — kept as the semantics of record for
    ``repro.core.slots.positions_by_dest``; tests assert the sort-based
    production version matches this bit for bit on every entry, including
    invalid and out-of-range destinations."""
    oh = jax.nn.one_hot(dest, num_dest, dtype=jnp.int32) * valid[:, None].astype(jnp.int32)
    incl = jnp.cumsum(oh, axis=0)
    pos = jnp.take_along_axis(incl - oh, dest[:, None].clip(0, num_dest - 1), axis=1)[:, 0]
    counts = incl[-1] if dest.shape[0] > 0 else jnp.zeros((num_dest,), jnp.int32)
    return pos.astype(jnp.int32), counts.astype(jnp.int32)


def combine_reduce(y: jax.Array, w: jax.Array) -> jax.Array:
    """Weighted K-way reduction — paper §IV-C(c) combine/recv.

    y: [T, K, H] expert responses (any float dtype), w: [T, K] gate weights.
    Returns [T, H] in w-independent f32 accumulation, cast to y.dtype's
    "compute" dtype (bf16 stays bf16, matching the paper's BF16 combine)."""
    acc = jnp.einsum("tkh,tk->th", y.astype(jnp.float32), w.astype(jnp.float32))
    out_dt = y.dtype if y.dtype in (jnp.bfloat16, jnp.float32, jnp.float16) else jnp.bfloat16
    return acc.astype(out_dt)


def combine_gather_reduce(recv: jax.Array, rows: jax.Array, w: jax.Array) -> jax.Array:
    """Fused gather + weighted K-way reduction — combine/recv without the
    [T, K, H] materialization.

    recv: [R, H] flat received rows; rows: [T, K] int32 with sentinel == R
    meaning "no contribution"; w: [T, K] gate weights. Returns [T, H] =
    sum_k w[t,k] * recv[rows[t,k]] (sentinel rows contribute zero)."""
    pad = jnp.zeros((1, recv.shape[-1]), recv.dtype)
    y = jnp.concatenate([recv, pad], axis=0)[rows]          # [T, K, H]
    return combine_reduce(y, w)


def quantize_fp8(x: jax.Array, block: int = 128):
    """Block-wise FP8(e4m3) quantization — the paper's in-kernel dispatch
    quantization (§IV-B: token data fp8 + 4-byte scales per 128 elements).

    x: [..., H] with H % block == 0 -> (q [..., H] f8e4m3, scales [..., H/block] f32)."""
    H = x.shape[-1]
    assert H % block == 0, (H, block)
    g = x.reshape(x.shape[:-1] + (H // block, block)).astype(jnp.float32)
    amax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (g / scale).astype(jnp.float8_e4m3fn)
    return q.reshape(x.shape), scale[..., 0].astype(jnp.float32)


def dequantize_fp8(q: jax.Array, scales: jax.Array, out_dtype=jnp.bfloat16):
    """Inverse of quantize_fp8. q: [..., H], scales: [..., H/block]."""
    H = q.shape[-1]
    block = H // scales.shape[-1]
    g = q.reshape(q.shape[:-1] + (H // block, block)).astype(jnp.float32)
    out = g * scales[..., None]
    return out.reshape(q.shape).astype(out_dtype)


def dispatch_pack(x: jax.Array, gmap: jax.Array, quant_block: int | None = None,
                  out_dtype=None):
    """Fused slot-pack (+ optional quantization) — paper §IV-C(a) Send Tokens.

    x: [T, H] tokens; gmap: [N, C] int32 slot->token map with sentinel == T
    meaning empty. Returns packed [N, C, H] (and scales [N, C, H/qb] if
    quantizing). Empty slots are zero. ``out_dtype`` (copy mode only) casts
    the packed payload; None keeps x.dtype."""
    T, H = x.shape
    if quant_block is not None:
        xq, sc = quantize_fp8(x, quant_block)
        xp = jnp.concatenate([xq, jnp.zeros((1, H), xq.dtype)], 0)
        # empty slots: zero payload, unit scale (== quantizing a zero row)
        sp = jnp.concatenate([sc, jnp.ones((1, sc.shape[-1]), sc.dtype)], 0)
        return xp[gmap], sp[gmap]
    xp = jnp.concatenate([x, jnp.zeros((1, H), x.dtype)], 0)
    out = xp[gmap]
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return out, None


def recv_unpack(recv: jax.Array, gmap: jax.Array, scales: jax.Array | None = None,
                out_dtype=None):
    """Fused recv-side unpack — paper §IV-C(b) Recv Tokens (dispatch_pack's
    mirror).

    recv: [R, H] flat received rows; gmap: int32 of any shape with sentinel
    == R meaning "empty slot"; scales: [R, H/block] f32 when the payload is
    fp8-quantized. Returns ``gmap.shape + (H,)``: the gathered rows,
    dequantized when scales are given (out_dtype defaults to bf16 then; in
    copy mode None keeps recv.dtype). Sentinel slots are exactly zero."""
    R, H = recv.shape
    pad = jnp.zeros((1, H), recv.dtype)
    rows = jnp.concatenate([recv, pad], axis=0)[gmap]
    if scales is None:
        return rows if out_dtype is None else rows.astype(out_dtype)
    spad = jnp.zeros((1, scales.shape[-1]), scales.dtype)
    sc = jnp.concatenate([scales, spad], axis=0)[gmap]
    return dequantize_fp8(rows, sc, out_dtype or jnp.bfloat16)


NEG_INF = -1e30


def paged_decode_stage1(q, k_pages, v_pages, kv_indices, kv_lens, *,
                        scale, num_kv_splits, rope_pages=None):
    """Stage 1 of split-KV paged decode attention: per-(request, split)
    partial outputs + log-sum-exp (the aiter ``mla_stage1`` shape).

    q: [B, Hq, dk] one decode query per request. k_pages: [P+1, page, Hkv,
    dk] paged key pool whose LAST row is the zero pad page. v_pages: same
    layout with trailing dv — or None for the absorbed-MLA pools, where the
    key pool (the latent c_kv, Hkv == 1) is the value pool too and
    ``rope_pages`` [P+1, page, 1, rope] holds the rotary keys, scored by the
    query's last ``rope`` columns (q: [B, Hq, dk + rope]).
    kv_indices: [B, max_pages] int32 per-request page table, padded with the
    pad-page index P. kv_lens: [B] int32 valid tokens per request (0 for an
    idle slot). max_pages must divide by num_kv_splits.

    Returns (o [B, S, Hq, dv] f32 split-local softmax outputs, lse [B, S,
    Hq] f32). Empty splits yield o == 0 and lse == NEG_INF exactly; masked
    positions contribute an exact 0 (explicit ``where``, not exp underflow),
    so recycled-page garbage can never leak into a live request."""
    B, max_pages = kv_indices.shape
    Hkv = k_pages.shape[2]
    Hq = q.shape[1]
    G = Hq // Hkv
    S = num_kv_splits
    assert max_pages % S == 0, (max_pages, S)
    if v_pages is None:
        assert Hkv == 1
        v_pages = k_pages
        k_pages = jnp.concatenate([k_pages, rope_pages], axis=-1)
    page, _, dk = k_pages.shape[1:]
    dv = v_pages.shape[-1]
    k = k_pages[kv_indices].reshape(B, max_pages * page, Hkv, dk)
    v = v_pages[kv_indices].reshape(B, max_pages * page, Hkv, dv)
    qg = q.reshape(B, Hkv, G, dk).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)
    valid = pos[None, :] < kv_lens[:, None]                 # [B, Stot]
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    # split the KV axis: [B, Hkv, G, S, pps*page]
    sc = s.reshape(B, Hkv, G, S, -1)
    # values off the live prefix are zeroed too: 0 × inf would be NaN
    vc = jnp.where(valid[:, :, None, None], v.astype(jnp.float32), 0.0)
    vc = vc.reshape(B, S, -1, Hkv, dv)
    mc = valid.reshape(B, 1, 1, S, -1)
    m = sc.max(-1)                                          # [B, Hkv, G, S]
    p = jnp.where(mc, jnp.exp(sc - m[..., None]), 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bhgsk,bskhv->bhgsv", p, vc)
    o = jnp.where((l > 0)[..., None], acc / jnp.where(l > 0, l, 1.0)[..., None], 0.0)
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), NEG_INF)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, dv)
    lse = lse.transpose(0, 3, 1, 2).reshape(B, S, Hq)
    return o, lse


def paged_decode_stage2(o_parts, lse):
    """Stage 2: LSE-weighted reduction across KV splits (the aiter
    ``_fwd_kernel_stage2`` shape). o_parts: [B, S, Hq, dv] f32, lse: [B, S,
    Hq] f32 -> [B, Hq, dv] f32. Splits with lse == NEG_INF (empty) get
    exactly zero weight; a fully-empty request returns exactly zero."""
    mx = lse.max(axis=1)                                    # [B, Hq]
    live = lse > NEG_INF / 2
    w = jnp.where(live, jnp.exp(lse - mx[:, None]), 0.0)    # [B, S, Hq]
    denom = w.sum(axis=1)                                   # [B, Hq]
    out = jnp.einsum("bsh,bshv->bhv", w, o_parts)
    safe = jnp.where(denom > 0, denom, 1.0)
    return jnp.where((denom > 0)[..., None], out / safe[..., None], 0.0)


def paged_decode_attention(q, k_pages, v_pages, kv_indices, kv_lens, *,
                           scale, num_kv_splits=1, rope_pages=None):
    """Two-stage split-KV paged decode attention over a page-table-indexed
    KV pool — the jnp semantics of record for
    ``kernels/decode_attention.py``. Returns [B, Hq, dv] f32."""
    o, lse = paged_decode_stage1(q, k_pages, v_pages, kv_indices, kv_lens,
                                 scale=scale, num_kv_splits=num_kv_splits,
                                 rope_pages=rope_pages)
    return paged_decode_stage2(o, lse)


def grouped_gemm(x: jax.Array, w: jax.Array, counts: jax.Array) -> jax.Array:
    """Expert-major grouped GEMM over the LL 3D layout (§III-E, Fig. 3).

    x: [L, A, H], w: [L, H, F], counts: [L] valid rows per expert.
    Rows >= counts[l] produce zeros (padding is never computed into output)."""
    L, A, H = x.shape
    out = jnp.einsum("lah,lhf->laf", x.astype(jnp.float32), w.astype(jnp.float32))
    mask = jnp.arange(A)[None, :] < counts[:, None]
    return jnp.where(mask[..., None], out, 0.0).astype(x.dtype if x.dtype != jnp.float8_e4m3fn else jnp.bfloat16)

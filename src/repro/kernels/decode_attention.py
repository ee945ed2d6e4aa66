"""Pallas TPU kernel pair: split-KV paged decode attention (flash-decoding).

The decode-side analogue of ``flash_attention.py`` for the paged KV pool
(``models/kv_pages.py``): one query token per request, keys/values scattered
across fixed-size pages addressed by a per-request page table. Shaped like
aiter's ``mla_decode_fwd`` (SNIPPETS.md Snippet 1):

  stage 1 — grid (B, num_kv_splits, pages_per_split), pages innermost. The
    flattened page table is scalar-prefetched into SMEM and drives the K/V
    BlockSpec index_map, so each grid step DMAs exactly one page from HBM
    into VMEM (the dispatch_pack gather idiom). Online softmax over the
    split's pages accumulates in VMEM scratch (the flash_attention m/l/acc
    idiom); the split's locally-normalized output and its log-sum-exp are
    written at the last page.
  stage 2 — grid (B,): LSE-weighted reduction across splits.

A page enters VMEM as one [page*Hkv, d] tile (the pool viewed as
[P+1, page*Hkv, d]), and every query head scores every row of it: one 2-D
matmul, masked so each head keeps only its own kv head's rows. That is
Hkv times the needed MXU work, on a step bound by reading the pages; it
keeps the kernel free of the in-kernel reshapes and batched dots Mosaic
does not lower.

Determinism contract (what makes page recycling safe): masked positions
contribute an EXACT zero — ``p = where(pos < kv_len, exp(s - m), 0)``, never
exp underflow — so garbage in recycled or pad pages cannot perturb a live
request, and an empty split/request yields o == 0, lse == NEG_INF exactly.
Page tables pad unused entries with the pool's zero pad page (index P), so
the index_map stays branch-free.

Absorbed-MLA decode shares the key pool with V (``share_kv``): Hkv == 1, the
key pool holds the latent ``c_kv`` [.., r_kv] and is the value pool too, and
a second pool holds the shared rotary key ``k_rope`` [.., rope]. The query
is [q_absorbed | q_rope]; a page's scores are q_absorbed·c_kvᵀ +
q_rope·k_ropeᵀ over all query heads at once ([Hq, r_kv] × [r_kv, page]), and
its values are c_kv itself — each page's latent row is read from HBM once,
and the 64-wide rope block is legal as its array's full last dimension, so
no pad bytes are read. This mode multiplies in the pools' dtype (bf16 on
the MXU, f32 accumulation; the probabilities are rounded to it for the
value product, as FlashMLA does); the GQA mode multiplies in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct

NEG_INF = -1e30


def _stage1_kernel(tbl_ref, lens_ref, tok_ref, q_ref, k_ref, *rest,
                   page, pps, scale, share_kv):
    if share_kv:
        qr_ref, kr_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    j = pl.program_id(2)
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lens_ref[b]
    base = (s * pps + j) * page

    def scores(q, k):
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    # page-level skip: entirely past the request's live tokens (covers idle
    # slots with kv_len == 0 — their whole walk is skipped and the store
    # emits the exact empty values)
    @pl.when(base < kv_len)
    def _compute():
        if share_kv:
            k = k_ref[0]                                        # [page, r_kv]
            sc = (scores(q_ref[0], k)
                  + scores(qr_ref[0], kr_ref[0])) * scale      # [Hq, page]
            # rows past the request are zeroed, so that whatever a recycled
            # page holds there (an inf too) meets an exact 0 weight as 0
            row = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
            v = jnp.where(base + row < kv_len, k, jnp.zeros_like(k))
        else:
            q = q_ref[0].astype(jnp.float32)                    # [Hq, dk]
            k = k_ref[0].astype(jnp.float32)                    # [page*Hkv, dk]
            v = v_ref[0].astype(jnp.float32)
            sc = scores(q, k) * scale                           # [Hq, page*Hkv]
        # a column is live for a row when it is the row's kv head and its
        # token is inside the request (tok_ref says which token, or a
        # sentinel past every length for another head's column)
        valid = base + tok_ref[...] < kv_len
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_ref[...]                                     # [Hq, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        # exact zero for masked positions — recycled-page garbage and pad
        # pages contribute nothing, not just "something tiny"
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        ctx = jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)       # [Hq, dv]
        acc_ref[...] = acc_ref[...] * corr + ctx
        m_ref[...] = m_new

    @pl.when(j == pps - 1)
    def _store():
        l = l_ref[...]
        live = l > 0
        safe = jnp.where(live, l, 1.0)
        o_ref[0, 0] = jnp.where(live, acc_ref[...] / safe, 0.0)
        lse_ref[0, 0] = jnp.where(live, m_ref[...] + jnp.log(safe), NEG_INF)


def _stage2_kernel(o_ref, lse_ref, out_ref):
    o = o_ref[0]                                                # [S, Hq, dv]
    lse = lse_ref[0]                                            # [S, Hq, 1]
    mx = lse.max(axis=0)                                        # [Hq, 1]
    w = jnp.where(lse > NEG_INF / 2, jnp.exp(lse - mx[None]), 0.0)
    denom = w.sum(axis=0)                                       # [Hq, 1]
    out = (w * o).sum(axis=0)                                   # [Hq, dv]
    safe = jnp.where(denom > 0, denom, 1.0)
    out_ref[0] = jnp.where(denom > 0, out / safe, 0.0)


def _token_of_column(Hq: int, Hkv: int, page: int) -> np.ndarray:
    """[Hq, page*Hkv] int32: for query head r and pool column c (token
    c // Hkv, kv head c % Hkv), the token offset inside the page when c is
    r's kv head, else a sentinel larger than any KV length."""
    G = Hq // Hkv
    c = np.arange(page * Hkv)
    own = (c % Hkv)[None, :] == (np.arange(Hq) // G)[:, None]
    return np.where(own, c // Hkv, 2 ** 30).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("scale", "num_kv_splits",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array | None,
                           kv_indices: jax.Array, kv_lens: jax.Array, *,
                           scale: float, num_kv_splits: int = 1,
                           rope_pages: jax.Array | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: [B, Hq, dk (+ rope)]; k_pages: [P+1, page, Hkv, dk] (last row =
    zero pad page); v_pages: same layout trailing dv, or None for the
    absorbed-MLA pools (values are K itself, Hkv == 1), where then
    ``rope_pages`` [P+1, page, 1, rope] holds the rotary keys that the
    query's last ``rope`` columns score; kv_indices: [B, max_pages] int32
    page table padded with P; kv_lens: [B] int32 live tokens per request.
    Returns [B, Hq, dv] f32."""
    B, max_pages = kv_indices.shape
    page, Hkv, dk = k_pages.shape[1:]
    Hq = q.shape[1]
    S = num_kv_splits
    assert max_pages % S == 0, (max_pages, S)
    pps = max_pages // S
    share_kv = v_pages is None
    assert share_kv == (rope_pages is not None)
    if share_kv:
        assert Hkv == 1
        dv = dk
    else:
        dv = v_pages.shape[-1]

    flat_tbl = kv_indices.reshape(-1).astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    tok = jnp.asarray(_token_of_column(Hq, Hkv, page))

    # pages as [P+1, page*Hkv, width] views: one page is then a block whose
    # trailing dims are the array's, and K for every head is one 2-D tile
    rows = page * Hkv
    kern = functools.partial(_stage1_kernel, page=page, pps=pps,
                             scale=scale, share_kv=share_kv)

    def page_spec(width):
        return pl.BlockSpec(
            (1, rows, width),
            lambda b, s, j, tbl, lens: (tbl[b * max_pages + s * pps + j], 0, 0))

    def row_spec(width):
        return pl.BlockSpec((1, Hq, width),
                            lambda b, s, j, tbl, lens: (b, 0, 0))

    in_specs = [pl.BlockSpec((Hq, rows), lambda b, s, j, tbl, lens: (0, 0)),
                row_spec(dk), page_spec(dk)]
    operands = [tok, q[..., :dk], k_pages.reshape(-1, rows, dk)]
    if share_kv:
        dr = rope_pages.shape[-1]
        assert q.shape[-1] == dk + dr, (q.shape, dk, dr)
        in_specs += [row_spec(dr), page_spec(dr)]
        operands += [q[..., dk:], rope_pages.reshape(-1, rows, dr)]
    else:
        in_specs.append(page_spec(dv))
        operands.append(v_pages.reshape(-1, rows, dv))
    ins = (flat_tbl, lens, *operands)

    o_parts, lse = pl.pallas_call(
        kern,
        name="paged_decode_stage1",
        out_shape=(out_struct((B, S, Hq, dv), jnp.float32, *ins),
                   out_struct((B, S, Hq, 1), jnp.float32, *ins)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, S, pps),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, 1, Hq, dv),
                             lambda b, s, j, tbl, lens: (b, s, 0, 0)),
                pl.BlockSpec((1, 1, Hq, 1),
                             lambda b, s, j, tbl, lens: (b, s, 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, dv), jnp.float32),
            ],
        ),
        interpret=interpret,
    )(*ins)

    return pl.pallas_call(
        _stage2_kernel,
        name="paged_decode_stage2",
        out_shape=out_struct((B, Hq, dv), jnp.float32, o_parts, lse),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S, Hq, dv), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, S, Hq, 1), lambda b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, dv), lambda b: (b, 0, 0)),
        interpret=interpret,
    )(o_parts, lse)

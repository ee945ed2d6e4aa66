"""Operations and bytes the algorithms need, computed from shapes and routing
counts alone — never from an implementation's padded buffers, so a share of
a peak reads the same work whatever implements it. Pure Python/numpy.
"""
from __future__ import annotations

import numpy as np

BF16 = 2


# --------------------------------------------------------------------------
# serving: model FLOPs of one decoder-only MoE layer stack
# --------------------------------------------------------------------------

def token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs to process one token at ``context`` positions of keys
    (itself included): attention projections, scores and values, the router,
    the top-k routed experts (not all of them) and the LM head. Embedding
    lookup and norms are left out."""
    d, L = cfg["d_model"], cfg["n_layers"]
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    E, k, f = cfg["n_experts"], cfg["top_k"], cfg["d_ff_expert"]
    proj = 2 * d * hd * (2 * hq + 2 * hkv)
    attn = 2 * 2 * hq * hd * context
    moe = 2 * d * E + k * 3 * 2 * d * f
    return L * (proj + attn + moe) + 2 * d * cfg["vocab"]


def tokens_flops(cfg: dict, contexts) -> float:
    """Model FLOPs of a batch of tokens with the given contexts."""
    c = np.asarray(contexts, np.float64)
    return c.size * token_flops(cfg, 0) + (
        cfg["n_layers"] * 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * c.sum())


# --------------------------------------------------------------------------
# EP round trip: least bytes
# --------------------------------------------------------------------------

def payload_bytes(hidden: int, fp8: bool, block: int = 128) -> int:
    """Bytes of one dispatched row: fp8 values plus one f32 scale per block,
    or bf16 values."""
    return hidden + 4 * (-(-hidden // block)) if fp8 else BF16 * hidden


def routing_counts(topk_idx: np.ndarray, experts_per_rank: int) -> dict:
    """Per-rank counts from the global routing ``topk_idx`` [N, T, K] under
    the contiguous placement (expert e on rank e // experts_per_rank):
    distinct destination ranks of each rank's tokens (all, and other ranks
    only), the (token, rank) rows each rank receives, and the routed copies
    it receives."""
    idx = np.asarray(topk_idx)
    N, T, K = idx.shape
    dest = idx // experts_per_rank                           # [N, T, K]
    hit = np.zeros((N, T, N), bool)
    hit[np.arange(N)[:, None, None], np.arange(T)[None, :, None], dest] = True
    distinct = hit.sum(axis=(1, 2))                          # [N]
    own = hit[np.arange(N), :, np.arange(N)].sum(axis=1)     # [N]
    received = hit.sum(axis=(0, 1))                          # [N]
    copies = np.bincount(dest.reshape(-1), minlength=N)      # [N]
    return dict(distinct=distinct, remote=distinct - own, received=received,
                copies=copies, tokens=T, ranks=N)


def ep_bytes(counts: dict, hidden: int, fp8: bool) -> dict:
    """Least bytes of one round trip on each rank ([N] arrays).

    hbm: the tokens read (bf16), each token written once per distinct
         destination rank in the payload dtype, each received row read in
         the payload dtype on the receiving side, and one bf16 partial per (token, rank) back in
         combine.
    ici: the off-chip share of those: payload rows to other ranks, and the
         bf16 partials that come back from them.
    dispatch_pack: what the send pack must move: the tokens read and one
         payload row written per (token, destination rank).
    recv_unpack: what the receive unpack must move: the payload rows read
         and one bf16 row written per routed copy (the per-expert layout the
         expert step consumes)."""
    pb = payload_bytes(hidden, fp8)
    T = counts["tokens"]
    D = np.asarray(counts["distinct"], np.float64)
    remote = np.asarray(counts["remote"], np.float64)
    recv = np.asarray(counts["received"], np.float64)
    copies = np.asarray(counts["copies"], np.float64)
    return dict(
        hbm=T * hidden * BF16 + D * pb + recv * pb + D * hidden * BF16,
        ici=remote * pb + remote * hidden * BF16,
        dispatch_pack=T * hidden * BF16 + D * pb,
        recv_unpack=recv * pb + copies * hidden * BF16,
    )


def least_time(hbm_bytes, ici_bytes, peaks) -> float:
    """Least time of one round trip: the busiest rank's larger bound."""
    t = np.maximum(np.asarray(hbm_bytes) / peaks.hbm_bytes,
                   np.asarray(ici_bytes) / peaks.ici_bytes)
    return float(t.max())

"""Pallas TPU kernel: causal (optionally sliding-window) flash attention.

The fused online-softmax pipeline whose HBM traffic is exactly Q+K+V+O — the
[Sq, Sk] score matrix lives only as VMEM tiles. This is the TPU
implementation of record for the attention sublayer; the pure-XLA chunked
formulation in models/attention.py computes the same function (and is what
the CPU-hosted dry-run lowers), but XLA's fusion-blind cost model charges it
full score-matrix traffic — the roofline's kernel-corrected memory term uses
THIS kernel's Q/K/V/O byte count for the attention region (docs/EXPERIMENTS.md
§Roofline notes).

Tiling: grid (B, Hq, Sq/bq, Sk/bk), KV innermost; m/l/acc accumulators in
VMEM scratch persist across the KV walk; GQA is handled in the index_map
(kv head = q head // G — no KV repetition in HBM). Fully-masked KV tiles are
skipped via pl.when (the causal compute saving). MXU-aligned: bq, bk are
128-multiples; hd padded by the caller if needed.

VMEM/invocation ≈ bq*hd + bk*hd (in) + bq*bk (scores) + bq*(hd+2) (scratch)
at f32 ≈ 128*128*4*2 + 128*512*4 + ... ≈ 0.5 MiB — far under budget, so the
pipeline can double-buffer the K/V streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq, bk, nk, scale, window, causal):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    # tile-level skips: entirely-in-the-future (causal) or entirely outside
    # the sliding window — the flash compute saving.
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window is not None:
        live &= (q_start - (k_start + bk - 1)) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "causal",
                                             "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, window: int | None = None,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Hq, Sq, d]; k/v: [B, Hkv, Sk, d] -> [B, Hq, Sq, d]."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nk = Sk // bk
    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                             window=window, causal=causal)
    return pl.pallas_call(
        kern,
        name="flash_attention",
        out_shape=out_struct((B, Hq, Sq, d), q.dtype, q, k, v),
        grid=(B, Hq, Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def hbm_bytes(B, Hq, Hkv, Sq, Sk, d, dtype_bytes=2) -> int:
    """The kernel's definitional HBM traffic: Q + K + V + O, each once."""
    return dtype_bytes * (B * Hq * Sq * d * 2 + B * Hkv * Sk * d * 2)

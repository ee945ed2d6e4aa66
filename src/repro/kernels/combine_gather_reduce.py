"""Pallas TPU kernel: fused slot-gather + K-way weighted combine reduction.

Paper §IV-C(c) combine/recv: responses for token t sit at precomputed slots
of the receive buffer; a TMA warp stages the K rows and reduction warps apply
the gate-weighted sum. The TPU rendering: the slot rows (the EpPlan's
``comb_recv_rows`` — the counter arithmetic's output) are scalar-prefetched
into SMEM and drive the input BlockSpec index_map, so each grid step DMAs
exactly the receive-buffer row the (t, k) entry needs, multiplies by the gate
weight on the VPU, and accumulates into a VMEM fp32 scratch tile; the k
innermost grid dimension revisits the same output tile, which pallas keeps
resident. Sentinel rows (== R) hit a guaranteed-zero pad row, keeping the
index_map branch-free — a dropped entry contributes exactly zero.

This replaces the seed's two-pass gather-then-reduce, which materialized the
full [T, K, H] response tensor in HBM between the passes.

Rows travel as [rows, 1, H] views (weights as [T, 1, K]): a (1, 1, bh)
block's trailing dims are (full, lane-aligned), which Mosaic's (8, 128)
block-tiling rule accepts for any single-row gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct


def _kernel(rows_ref, y_ref, w_ref, o_ref, acc_ref, *, K):
    # y_ref: [1, 1, bh] the gathered recv row for entry (t, k); w_ref: [1, 1, K]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # w[t, k] by a one-hot lane select: Mosaic cannot index lanes dynamically
    w = w_ref[0].astype(jnp.float32)                      # [1, K]
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    wk = jnp.sum(jnp.where(lane == k, w, 0.0), axis=1, keepdims=True)
    acc_ref[...] += y_ref[0].astype(jnp.float32) * wk

    @pl.when(k == K - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def combine_gather_reduce(recv: jax.Array, rows: jax.Array, w: jax.Array, *,
                          bh: int = 512, interpret: bool = False) -> jax.Array:
    """recv: [R, H] flat received rows; rows: [T, K] int32 slot rows with
    sentinel == R meaning "no contribution"; w: [T, K] gate weights.
    Returns [T, H] = sum_k w[t,k] * recv[rows[t,k]] in fp32 accumulation.

    Grid (T, H/bh, K): hidden in lane-aligned bh-wide blocks, K innermost so
    the output tile stays VMEM-resident across the reduction."""
    R, H = recv.shape
    T, K = rows.shape
    bh = min(bh, H)
    while H % bh != 0:        # largest lane-aligned tile dividing H
        bh -= 128
    assert bh > 0 and H % bh == 0, (H, bh)
    # pad row R is zeros => sentinel entries contribute zero
    recv_p = jnp.concatenate([recv, jnp.zeros((1, H), recv.dtype)],
                             axis=0)[:, None]
    out_dt = (recv.dtype if recv.dtype in (jnp.bfloat16, jnp.float32, jnp.float16)
              else jnp.bfloat16)
    kern = functools.partial(_kernel, K=K)
    out = pl.pallas_call(
        kern,
        name="combine_gather_reduce",
        out_shape=out_struct((T, 1, H), out_dt, recv, rows, w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T, H // bh, K),
            in_specs=[
                pl.BlockSpec((1, 1, bh),
                             lambda t, j, k, rows_ref: (rows_ref[t * K + k], 0, j)),
                pl.BlockSpec((1, 1, K), lambda t, j, k, rows_ref: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bh),
                                   lambda t, j, k, rows_ref: (t, 0, j)),
            scratch_shapes=[pltpu.VMEM((1, bh), jnp.float32)],
        ),
        interpret=interpret,
    )(rows.reshape(-1), recv_p, w[:, None])
    return out.reshape(T, H)

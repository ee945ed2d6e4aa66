"""Pallas TPU kernel: fused slot-gather + K-way weighted combine reduction.

Paper §IV-C(c) combine/recv: responses for token t sit at precomputed slots
of the receive buffer; a TMA warp stages the K rows and reduction warps apply
the gate-weighted sum. The TPU rendering gathers a block of ``tb`` tokens
across the full hidden width per grid step:

- Grid ``(ceil(T / tb),)``. ``tb`` follows from the shapes: 16 tokens when
  the double buffer (2 x tb x K rows) fits in a quarter of the default
  scoped VMEM, halved until it does, and ``T`` itself when ``T`` is
  smaller. A ``T`` that is not a multiple of ``tb`` is padded with sentinel
  entries and the output sliced.
- Gather. The received rows stay in HBM, viewed as ``[R, H/128, 128]``: a
  one-row DMA out of a 2-D ``[R, H]`` ref slices the tiled sublane axis off
  its (8, 128) tiling and is refused, while the leading axis of the 3-D view
  is untiled, so any single row is one DMA. The slot rows (the EpPlan's
  ``comb_recv_rows``) and the gate weights are scalar-prefetched into SMEM;
  each step issues ``tb * K`` row copies into a VMEM buffer. The issue and
  wait loops are unrolled: at 14 KB rows, issuing copies, not HBM bytes,
  is what a rolled loop is bound by.
- Double buffer. The buffer has two halves; step i starts the copies of
  step i + 1 into the other half before it waits on its own, so the
  gather of the next block overlaps the reduction of this one. The grid is
  therefore sequential.
- Reduce. Each token sums its K rows times their weights in f32, k
  ascending, and the block leaves as one lane-dense ``(tb, H/128, 128)``
  tile.
- Sentinels. A sentinel row (== R) copies the clamped row R - 1 and is
  dropped by a select, not multiplied by zero, so it contributes exactly
  zero whatever that row holds (inf and NaN included).

This replaces the seed's two-pass gather-then-reduce, which materialized the
full [T, K, H] response tensor in HBM between the passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct

LANES = 128
MAX_TOKEN_BLOCK = 16
BUFFER_BYTES = 4 << 20   # a quarter of v5e's 16 MiB default scoped VMEM


def token_block(T: int, K: int, H: int, itemsize: int) -> int:
    """Tokens per grid step for T tokens of K rows of H items."""
    tb = MAX_TOKEN_BLOCK
    while tb > 1 and 2 * tb * K * H * itemsize > BUFFER_BYTES:
        tb //= 2
    return max(1, min(tb, T))


def _kernel(rows_ref, w_ref, recv_ref, o_ref, buf, sem, *, R, tb, K):
    # recv_ref: [R, H/128, 128] in HBM; buf: [2, tb*K, H/128, 128] VMEM
    i = pl.program_id(0)
    n = tb * K

    def start(blk, slot):
        def body(j, carry):
            r = jnp.minimum(rows_ref[blk * n + j], R - 1)
            pltpu.make_async_copy(recv_ref.at[r], buf.at[slot, j],
                                  sem.at[slot]).start()
            return carry
        jax.lax.fori_loop(0, n, body, 0, unroll=True)

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _prefetch():
        start(i + 1, (i + 1) % 2)

    slot = i % 2

    def wait(j, carry):
        # every copy into this half has one row's size: any row's
        # descriptor waits for one of them
        pltpu.make_async_copy(recv_ref.at[0], buf.at[slot, 0],
                              sem.at[slot]).wait()
        return carry
    jax.lax.fori_loop(0, n, wait, 0, unroll=True)

    def token(t, carry):
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for k in range(K):
            e = (i * tb + t) * K + k
            y = buf[slot, t * K + k].astype(jnp.float32)
            acc = acc + jnp.where(rows_ref[e] < R, y * w_ref[e], 0.0)
        o_ref[t] = acc.astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, tb, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_gather_reduce(recv: jax.Array, rows: jax.Array, w: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """recv: [R, H] flat received rows (H % 128 == 0); rows: [T, K] int32
    slot rows with sentinel == R meaning "no contribution"; w: [T, K] gate
    weights. Returns [T, H] = sum_k w[t,k] * recv[rows[t,k]] in fp32
    accumulation, cast to recv's dtype (bf16 for other input dtypes)."""
    R, H = recv.shape
    T, K = rows.shape
    assert H % LANES == 0, H
    tb = token_block(T, K, H, recv.dtype.itemsize)
    nb = pl.cdiv(T, tb)
    pad = nb * tb - T
    rows_p = jnp.pad(rows.astype(jnp.int32), ((0, pad), (0, 0)),
                     constant_values=R)
    w_p = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0)))
    out_dt = (recv.dtype if recv.dtype in (jnp.bfloat16, jnp.float32, jnp.float16)
              else jnp.bfloat16)
    hl = H // LANES
    kern = functools.partial(_kernel, R=R, tb=tb, K=K)
    out = pl.pallas_call(
        kern,
        name="combine_gather_reduce",
        out_shape=out_struct((nb * tb, hl, LANES), out_dt, recv, rows, w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb, hl, LANES), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, tb * K, hl, LANES), recv.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rows_p.reshape(-1), w_p.reshape(-1), recv.reshape(R, hl, LANES))
    return out[:T].reshape(T, H)

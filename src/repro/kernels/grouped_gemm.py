"""Pallas TPU kernel: expert-major grouped GEMM over the LL/HT 3D layout.

Consumes the dispatch output [L, A, H] (tokens grouped by local expert,
padded to capacity A) against per-expert weights [L, H, F]. Per-expert valid
row counts are scalar-prefetched; tiles that lie entirely beyond an expert's
count are *skipped* (output zeroed, no MXU work) — the static-shape analogue
of DeepEP's grouped GEMM consuming only m(e,r) valid rows.

Tiling: (expert, A/bm, F/bn, H/bk) grid, MXU-aligned 128x128 output tiles with
a bk-deep reduction loop accumulating in fp32 VMEM scratch. The weight tile
[bk, bn] is revisited across the A dimension (standard output-stationary
schedule); XLA's grid pipeliner double-buffers the HBM->VMEM streams.

VMEM/invocation ≈ bm*bk + bk*bn (bf16) + bm*bn (f32) = 128*512*2*2 + 128*128*4
≈ 320 KiB — well within budget, sized so the MXU sees 128-multiples always.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct


def _kernel(counts_ref, x_ref, w_ref, o_ref, acc_ref, *, bm, bk, nk):
    l = pl.program_id(0)
    i = pl.program_id(1)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip MXU work for tiles fully beyond this expert's valid rows.
    live = (i * bm) < counts_ref[l]

    @pl.when(live)
    def _compute():
        acc_ref[0] += jnp.dot(
            x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        # zero rows beyond the count inside a partially-live tile
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, acc_ref[0].shape, 0)
        o_ref[0] = jnp.where(row < counts_ref[l], acc_ref[0], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def grouped_gemm(x: jax.Array, w: jax.Array, counts: jax.Array, *,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 interpret: bool = False) -> jax.Array:
    """x: [L, A, H] @ w: [L, H, F] -> [L, A, F], rows >= counts[l] zeroed."""
    L, A, H = x.shape
    _, _, F = w.shape
    bm, bn, bk = min(bm, A), min(bn, F), min(bk, H)
    assert A % bm == 0 and F % bn == 0 and H % bk == 0, (x.shape, w.shape, bm, bn, bk)
    nk = H // bk
    out_dt = x.dtype if x.dtype in (jnp.bfloat16, jnp.float32) else jnp.bfloat16
    kern = functools.partial(_kernel, bm=bm, bk=bk, nk=nk)
    return pl.pallas_call(
        kern,
        name="grouped_gemm",
        out_shape=out_struct((L, A, F), out_dt, x, w, counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, A // bm, F // bn, nk),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda l, i, j, k, c: (l, i, k)),
                pl.BlockSpec((1, bk, bn), lambda l, i, j, k, c: (l, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda l, i, j, k, c: (l, i, j)),
            scratch_shapes=[pltpu.VMEM((1, bm, bn), jnp.float32)],
        ),
        interpret=interpret,
    )(counts, x, w)

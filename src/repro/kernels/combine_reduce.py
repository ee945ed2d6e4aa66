"""Pallas TPU kernel: fused K-way weighted combine reduction.

Paper §IV-C(c): combine/recv splits warps into reduction groups; a TMA warp
stages K expert responses into shared memory and the rest perform the weighted
reduction as a pipeline. The TPU rendering: the grid walks (token-block,
hidden-block) tiles; each invocation holds a [bt, K, bh] VMEM tile of
responses plus the [bt, K] weights and reduces over K on the VPU in fp32.
Pipelining HBM->VMEM staging against compute is what `pallas_call`'s grid
machinery does natively (the TMA-warp analogue).

VMEM budget per invocation: bt*K*bh*2B (bf16 responses) + bt*bh*4B (f32 acc)
≈ 8*8*512*2 + 8*512*4 = 80 KiB at the default tiling — comfortably inside
the ~16 MiB VMEM of a TPU core, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.shapes import out_struct


def _kernel(y_ref, w_ref, o_ref):
    # y_ref: [bt, K, bh]; w_ref: [bt, K]; o_ref: [bt, bh]
    y = y_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(y * w[:, :, None], axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "bh", "interpret"))
def combine_reduce(y: jax.Array, w: jax.Array, *, bt: int = 8, bh: int = 512,
                   interpret: bool = False) -> jax.Array:
    """y: [T, K, H], w: [T, K] -> [T, H] = sum_k w[t,k] * y[t,k,:].

    Tiling: hidden in lane-aligned bh-wide blocks (bh % 128 == 0), tokens in
    bt-tall blocks (sublane-aligned). K is kept whole inside the tile — K <= 16
    for every assigned architecture, so the tile stays small."""
    T, K, H = y.shape
    bt = min(bt, T)
    bh = min(bh, H)
    assert T % bt == 0 and H % bh == 0, (T, K, H, bt, bh)
    out_dt = y.dtype if y.dtype in (jnp.bfloat16, jnp.float32, jnp.float16) else jnp.bfloat16
    return pl.pallas_call(
        _kernel,
        name="combine_reduce",
        out_shape=out_struct((T, H), out_dt, y, w),
        grid=(T // bt, H // bh),
        in_specs=[
            pl.BlockSpec((bt, K, bh), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bt, K), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bh), lambda i, j: (i, j)),
        interpret=interpret,
    )(y, w)

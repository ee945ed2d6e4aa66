"""Pallas TPU kernel: fused recv-side slot unpack + FP8 dequantization.

Paper §IV-C(b) "Recv Tokens", the mirror of ``dispatch_pack``: received
payload rows sit at precomputed (pair, slot) coordinates of the receive
buffer; the destination's unpack walks the expert-region map and lands each
row in the 3D expert-major layout, dequantizing FP8 payloads in the same
pass. The TPU rendering: a scalar-prefetched gather — the plan's
``disp_recv_gmap`` (expert slot -> flat receive row) is prefetched into SMEM
and drives the BlockSpec index_map, so each grid step DMAs exactly the
receive-buffer row (and, when quantized, its scale row) that the output slot
needs from HBM into VMEM, dequantizes on the VPU, and writes the unpacked
tile. Empty slots (sentinel == R) map to guaranteed-zero pad rows (zero
payload, zero scales), keeping the index_map branch-free.

This closes the recv half of the one-pass-per-phase invariant: the seed's
unpack was an XLA gather followed by a separate ``dequantize_fp8`` pass,
materializing the full gathered fp8 copy in HBM in between. The fused
version touches each received row exactly once.

Rows travel as [rows, 1, H] views (scales as [rows, 1, H/block]): a
(1, 1, H) block equals the full trailing dims, which Mosaic's (8, 128)
block-tiling rule accepts for any single-row gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct


def _kernel_copy(gmap_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def _kernel_dequant(gmap_ref, q_ref, s_ref, o_ref, *, block):
    # q_ref: [1, 1, H] gathered fp8 row; s_ref: [1, 1, H/block] its scales
    q = q_ref[0].astype(jnp.float32)
    H = q.shape[-1]
    g = q.reshape(1, H // block, block)
    o_ref[0] = (g * s_ref[0][..., None]).reshape(1, H).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def recv_unpack(recv: jax.Array, gmap: jax.Array, scales: jax.Array | None = None,
                *, out_dtype=None, interpret: bool = False):
    """recv: [R, H] flat received rows; gmap: int32 (any shape, sentinel == R).

    Returns the unpacked rows with shape ``gmap.shape + (H,)``. With
    ``scales`` ([R, H/block] f32) the gathered fp8 payload is dequantized in
    the same pass (``out_dtype`` defaults to bf16); without, rows are gathered
    and cast to ``out_dtype`` (None keeps recv.dtype). Sentinel slots are
    exactly zero either way.
    """
    R, H = recv.shape
    M = gmap.size
    flat_map = gmap.reshape(-1)
    grid = (M,)

    if scales is None:
        if out_dtype is None:
            out_dtype = recv.dtype
        # pad row R is zeros => sentinel slots come out zero
        xp = jnp.concatenate([recv, jnp.zeros((1, H), recv.dtype)],
                             axis=0)[:, None]
        out = pl.pallas_call(
            _kernel_copy,
            name="recv_unpack",
            out_shape=out_struct((M, 1, H), out_dtype, recv, gmap),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[pl.BlockSpec((1, 1, H),
                                       lambda i, m_ref: (m_ref[i], 0, 0))],
                out_specs=pl.BlockSpec((1, 1, H), lambda i, m_ref: (i, 0, 0)),
            ),
            interpret=interpret,
        )(flat_map, xp)
        return out.reshape(gmap.shape + (H,))

    if out_dtype is None:
        out_dtype = jnp.bfloat16
    block = H // scales.shape[-1]
    # zero pad rows for payload AND scales: a sentinel slot dequantizes to
    # exactly 0 * 0 = 0, matching the two-pass reference (gathers fill=0)
    qp = jnp.concatenate([recv, jnp.zeros((1, H), recv.dtype)], axis=0)[:, None]
    sp = jnp.concatenate([scales, jnp.zeros((1, H // block), scales.dtype)],
                         axis=0)[:, None]
    kern = functools.partial(_kernel_dequant, block=block)
    out = pl.pallas_call(
        kern,
        name="recv_unpack",
        out_shape=out_struct((M, 1, H), out_dtype, recv, gmap, scales),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, H), lambda i, m_ref: (m_ref[i], 0, 0)),
                pl.BlockSpec((1, 1, H // block),
                             lambda i, m_ref: (m_ref[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, H), lambda i, m_ref: (i, 0, 0)),
        ),
        interpret=interpret,
    )(flat_map, qp, sp)
    return out.reshape(gmap.shape + (H,))

"""Pallas TPU kernel: fused dispatch slot-pack + FP8 quantization.

Paper §IV-C(a) "Send Tokens": payload messages are packed into the send
region and (optionally) quantized to FP8 in-kernel, by dedicated warps, before
the RDMA write. The TPU rendering: a scalar-prefetched gather — the slot->token
map (computed by slots.py, the counter analogue) is prefetched into SMEM and
drives the BlockSpec index_map, so each grid step DMAs exactly the token row
its slot needs from HBM into VMEM, quantizes on the VPU, and writes the packed
send-buffer tile. Empty slots (sentinel) are zero-filled — they map to a
guaranteed-zero pad row, keeping the index_map branch-free.

Rows travel as [rows, 1, H] views: a (1, 1, H) block equals the full
trailing dims, which Mosaic's (8, 128) block-tiling rule accepts for any
single-row gather.

This is the data-movement hot spot of LL dispatch: the fused version touches
each token row exactly (#destination ranks) times with no intermediate
materialization of the [T, H] quantized copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct


def _kernel_quant(gmap_ref, x_ref, q_ref, s_ref, *, block):
    # x_ref: [1, 1, H] the gathered token row; q [1, 1, H] fp8, s [1, 1, H/block]
    x = x_ref[0].astype(jnp.float32)
    H = x.shape[-1]
    g = x.reshape(1, H // block, block)
    amax = jnp.max(jnp.abs(g), axis=-1)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q_ref[0] = (g / scale[..., None]).reshape(1, H).astype(q_ref.dtype)
    s_ref[0] = scale


def _kernel_copy(gmap_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("quant_block", "out_dtype", "interpret"))
def dispatch_pack(x: jax.Array, gmap: jax.Array, *, quant_block: int | None = None,
                  out_dtype=None, interpret: bool = False):
    """x: [T, H]; gmap: [N, C] int32 (sentinel == T -> empty slot).

    Returns packed [N, C, H] (+ scales [N, C, H//quant_block] if quantizing).
    ``out_dtype`` (copy mode) casts the packed payload; None keeps x.dtype.
    """
    T, H = x.shape
    if out_dtype is None:
        out_dtype = x.dtype
    N, C = gmap.shape
    # pad row T is zeros => sentinel slots come out zero
    xp = jnp.concatenate([x, jnp.zeros((1, H), x.dtype)], axis=0)[:, None]
    flat_map = gmap.reshape(-1)

    grid = (N * C,)
    in_specs = [pl.BlockSpec((1, 1, H), lambda i, m_ref: (m_ref[i], 0, 0))]

    if quant_block is None:
        out = pl.pallas_call(
            _kernel_copy,
            name="dispatch_pack",
            out_shape=out_struct((N * C, 1, H), out_dtype, x, gmap),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec((1, 1, H), lambda i, m_ref: (i, 0, 0)),
            ),
            interpret=interpret,
        )(flat_map, xp)
        return out.reshape(N, C, H), None

    kern = functools.partial(_kernel_quant, block=quant_block)
    q, s = pl.pallas_call(
        kern,
        name="dispatch_pack",
        out_shape=(
            out_struct((N * C, 1, H), jnp.float8_e4m3fn, x, gmap),
            out_struct((N * C, 1, H // quant_block), jnp.float32,
                       x, gmap),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, 1, H), lambda i, m_ref: (i, 0, 0)),
                pl.BlockSpec((1, 1, H // quant_block),
                             lambda i, m_ref: (i, 0, 0)),
            ),
        ),
        interpret=interpret,
    )(flat_map, xp)
    return q.reshape(N, C, H), s.reshape(N, C, H // quant_block)

"""Pallas TPU kernels: block-wise FP8(e4m3) quantize / dequantize.

Paper §IV-B: dispatch payloads travel as fp8 token data plus one 4-byte scale
per 128 elements, computed in-kernel. Standalone quantize/dequantize passes
are still needed off the fused-pack path (dequantization of received rows,
re-quantization of expert outputs), and previously always fell back to the
pure-jnp oracle; these kernels close that gap. The grid walks row blocks of
whole rows, so each invocation computes whole scale groups on the VPU: amax
over each ``block``-wide group, scale = amax/448 (e4m3 max normal), payload
= value / scale. Zero groups get unit scale, matching the oracle bit for
bit. A tile spans the full row so that its scale tile spans the full
scale row: Mosaic takes a block's last dim only when it is a multiple of
128 or the whole dim, and H/block scales (56 at H = 7168) is rarely the
former.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.shapes import out_struct


def _quant_kernel(x_ref, q_ref, s_ref, *, block):
    x = x_ref[...].astype(jnp.float32)                  # [bm, H]
    bm, H = x.shape
    g = x.reshape(bm, H // block, block)
    amax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q_ref[...] = (g / scale).reshape(bm, H).astype(q_ref.dtype)
    s_ref[...] = scale[..., 0].astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref, *, block):
    q = q_ref[...].astype(jnp.float32)                  # [bm, H]
    bm, H = q.shape
    g = q.reshape(bm, H // block, block)
    o_ref[...] = (g * s_ref[...][..., None]).reshape(bm, H).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "bm", "interpret"))
def quantize_fp8(x: jax.Array, block: int = 128, *, bm: int = 8,
                 interpret: bool = False):
    """x: [M, H] with H % block == 0 and M % bm == 0 ->
    (q [M, H] f8e4m3, scales [M, H/block] f32)."""
    M, H = x.shape
    bm = min(bm, M)
    assert M % bm == 0 and H % block == 0, (M, H, bm, block)
    kern = functools.partial(_quant_kernel, block=block)
    return pl.pallas_call(
        kern,
        name="quantize_fp8",
        out_shape=(
            out_struct((M, H), jnp.float8_e4m3fn, x),
            out_struct((M, H // block), jnp.float32, x),
        ),
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, H), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((bm, H), lambda i: (i, 0)),
            pl.BlockSpec((bm, H // block), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("out_dtype", "bm", "interpret"))
def dequantize_fp8(q: jax.Array, scales: jax.Array, out_dtype=jnp.bfloat16, *,
                   bm: int = 8, interpret: bool = False):
    """Inverse of quantize_fp8. q: [M, H], scales: [M, H/block] -> [M, H]."""
    M, H = q.shape
    block = H // scales.shape[-1]
    bm = min(bm, M)
    assert M % bm == 0 and H % block == 0, (M, H, bm, block)
    kern = functools.partial(_dequant_kernel, block=block)
    return pl.pallas_call(
        kern,
        name="dequantize_fp8",
        out_shape=out_struct((M, H), out_dtype, q, scales),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, H), lambda i: (i, 0)),
            pl.BlockSpec((bm, H // block), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, H), lambda i: (i, 0)),
        interpret=interpret,
    )(q, scales)

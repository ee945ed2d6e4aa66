"""Pallas TPU kernel: fused recv-side slot unpack + FP8 dequantization.

Paper §IV-C(b) "Recv Tokens", the mirror of ``dispatch_pack``: received
payload rows sit at precomputed (pair, slot) coordinates of the receive
buffer; the destination's unpack walks the expert-region map (the plan's
``disp_recv_gmap``, expert slot -> flat receive row, sentinel == R for an
empty slot) and lands each row in the expert-major layout, dequantizing FP8
payloads in the same pass. The TPU rendering gathers a block of ``rb``
output slots per grid step:

- Grid ``(ceil(M / rb),)`` over the flat slot map. ``rb`` follows from the
  shapes: 16 slots when the gather's double buffer and the output's
  (2 x rb rows in, 2 x rb rows out) fit ``combine_gather_reduce``'s budget
  of a quarter of the default scoped VMEM, halved until they do, and ``M``
  itself when ``M`` is smaller. On a v5e at H = 7168, fp8 in and bf16 out,
  16 beat 8, 32 and 64 through both EP cells' slot maps (HT [256, 256],
  half the slots live; LL [64, 128], an eighth live). A map that is not a
  multiple of ``rb`` is padded with sentinel entries and the output
  sliced.
- Gather. The received rows stay in HBM, viewed as ``[R, H/128, 128]``: a
  one-row DMA out of a 2-D ``[R, H]`` ref slices the tiled sublane axis off
  its (8, 128) tiling and is refused, while the leading axis of the 3-D view
  is untiled, so any single row is one DMA. The slot map is
  scalar-prefetched into SMEM; step i starts the row copies of step i + 1
  into the other half of a VMEM double buffer before it waits on its own,
  so the grid is sequential. The issue and wait loops are unrolled, as in
  ``combine_gather_reduce``.
- Sentinels. A slot whose map entry is the sentinel issues no DMA; the wait
  loop reads the same prefetched entries under the same predicate, so every
  started copy is waited on once. Its output row is written as exactly zero
  by a select, so nothing its stale buffer row holds (inf and NaN included)
  reaches it. An expert region's empty tail therefore moves no bytes in,
  and a block of nothing but sentinels skips the dequant and writes zeros:
  the plan fills each expert's region from its front, so such blocks are
  common (47% of HT's blocks, 82% of LL's), and dequantizing them anyway
  made the kernel 1.3x (HT) and 2.2x (LL) slower on a v5e.
- Dequant. FP8 rows are widened to f32 in VMEM, multiplied by their block
  scales and cast to the output dtype: the elementwise math of
  ``ref.dequantize_fp8``. The scales (H/block f32 per row, ≈ 3% of a fp8
  row) are gathered through the same map by one small XLA gather ahead of
  the kernel, repeated to one per 128 lanes and passed as a blocked
  ``[M/rb, rb, H/128]`` input: a per-row scale DMA would need its own
  untiled view, padded to 128 lanes per 56 scales, and twice the copies.
- Output. Each step writes one lane-dense ``(rb, H/128, 128)`` tile; the
  result is returned as ``gmap.shape + (H,)``.

The payload rows are read once, inside the kernel: there is no gathered fp8
copy in HBM between a gather and a dequant pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.combine_gather_reduce import BUFFER_BYTES, LANES
from repro.kernels.shapes import out_struct

MAX_ROW_BLOCK = 16


def row_block(M: int, H: int, in_itemsize: int, out_itemsize: int) -> int:
    """Output slots per grid step for M slots of H items: the largest power
    of two up to MAX_ROW_BLOCK whose double-buffered rows in and out fit
    BUFFER_BYTES, and never more than M."""
    rb = MAX_ROW_BLOCK
    while rb > 1 and 2 * rb * H * (in_itemsize + out_itemsize) > BUFFER_BYTES:
        rb //= 2
    return max(1, min(rb, M))


def _kernel(map_ref, recv_ref, *refs, R, rb, dequant):
    # recv_ref: [R, H/128, 128] in HBM; buf: [2, rb, H/128, 128] VMEM;
    # with dequant, s_ref: [1, rb, H/128] this block's gathered scales
    if dequant:
        s_ref, o_ref, buf, sem = refs
    else:
        o_ref, buf, sem = refs
    i = pl.program_id(0)

    def each_row_copy(blk, slot, act):
        def body(j, carry):
            r = map_ref[blk * rb + j]

            @pl.when(r < R)
            def _():
                act(pltpu.make_async_copy(recv_ref.at[r], buf.at[slot, j],
                                          sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, rb, body, 0, unroll=True)

    def start(blk, slot):
        each_row_copy(blk, slot, lambda c: c.start())

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _prefetch():
        start(i + 1, (i + 1) % 2)

    slot = i % 2
    each_row_copy(i, slot, lambda c: c.wait())

    live = [map_ref[i * rb + t] < R for t in range(rb)]
    any_live = functools.reduce(jnp.logical_or, live)

    @pl.when(any_live)
    def _unpack():
        s = s_ref[0][:, :, None] if dequant else None
        for t in range(rb):
            x = buf[slot, t]
            if dequant:
                x = x.astype(jnp.float32) * s[t]
            x = x.astype(o_ref.dtype)
            o_ref[t] = jnp.where(live[t], x, jnp.zeros_like(x))

    @pl.when(jnp.logical_not(any_live))
    def _empty():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def recv_unpack(recv: jax.Array, gmap: jax.Array, scales: jax.Array | None = None,
                *, out_dtype=None, interpret: bool = False):
    """recv: [R, H] flat received rows (H % 128 == 0); gmap: int32 (any
    shape, sentinel == R).

    Returns the unpacked rows with shape ``gmap.shape + (H,)``. With
    ``scales`` ([R, H/block] f32, block % 128 == 0) the gathered fp8 payload
    is dequantized in the same pass (``out_dtype`` defaults to bf16);
    without, rows are gathered and cast to ``out_dtype`` (None keeps
    recv.dtype). Sentinel slots are exactly zero either way.
    """
    R, H = recv.shape
    assert H % LANES == 0, H
    M = gmap.size
    hl = H // LANES
    dequant = scales is not None
    if out_dtype is None:
        out_dtype = jnp.bfloat16 if dequant else recv.dtype
    rb = row_block(M, H, recv.dtype.itemsize, jnp.dtype(out_dtype).itemsize)
    nb = pl.cdiv(M, rb)
    flat_map = jnp.pad(gmap.reshape(-1).astype(jnp.int32), (0, nb * rb - M),
                       constant_values=R)

    operands = [flat_map, recv.reshape(R, hl, LANES)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if dequant:
        # one scale per 128 lanes; a sentinel gathers a clamped row, which
        # the kernel's select drops
        per_lane = jnp.repeat(scales.astype(jnp.float32),
                              H // scales.shape[-1] // LANES, axis=-1)
        operands.append(jnp.take(per_lane, flat_map, axis=0, mode="clip")
                        .reshape(nb, rb, hl))
        in_specs.append(pl.BlockSpec((1, rb, hl), lambda i, *_: (i, 0, 0)))

    kern = functools.partial(_kernel, R=R, rb=rb, dequant=dequant)
    out = pl.pallas_call(
        kern,
        name="recv_unpack",
        out_shape=out_struct((nb * rb, hl, LANES), out_dtype, recv, gmap,
                             *([scales] if dequant else [])),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((rb, hl, LANES), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, rb, hl, LANES), recv.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    return out[:M].reshape(gmap.shape + (H,))

"""Jit'd public wrappers for the kernels package.

Backend selection: on TPU the Pallas kernels run compiled; elsewhere the
pure-jnp oracles from ref.py are used (bitwise-identical semantics — the test
suite asserts so under interpret mode). `REPRO_FORCE_PALLAS=interpret` forces
interpret-mode Pallas everywhere (slow; used by kernel tests and debugging).

Every EP hot-path op is fused single-pass on TPU: dispatch_pack (slot gather
+ fp8 quant), recv_unpack (slot gather + fp8 dequant, its recv-side mirror),
combine_gather_reduce (token-blocked slot gather + K-way weighted reduce),
combine_reduce, quantize/dequantize_fp8, grouped_gemm, flash attention.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import combine_reduce as _cr
from repro.kernels import combine_gather_reduce as _cgr
from repro.kernels import dispatch_pack as _dp
from repro.kernels import fp8 as _fp8
from repro.kernels import grouped_gemm as _gg
from repro.kernels import recv_unpack as _ru


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    force = os.environ.get("REPRO_FORCE_PALLAS", "")
    if force == "interpret":
        return True, True
    if force == "off":
        return False, False
    on_tpu = jax.default_backend() == "tpu"
    return on_tpu, False


def combine_reduce(y: jax.Array, w: jax.Array) -> jax.Array:
    use, interp = _use_pallas()
    T, K, H = y.shape
    if use and T % 8 == 0 and H % 128 == 0:
        return _cr.combine_reduce(y, w, interpret=interp)
    return _ref.combine_reduce(y, w)


def combine_gather_reduce(recv: jax.Array, rows: jax.Array, w: jax.Array) -> jax.Array:
    """Fused gather-through-slot-rows + weighted top-k reduction.

    recv: [R, H] flat received rows; rows: [T, K] int32 (sentinel == R);
    w: [T, K] -> [T, H]. One pass; no [T, K, H] materialization on TPU."""
    use, interp = _use_pallas()
    H = recv.shape[-1]
    if use and H % 128 == 0:
        return _cgr.combine_gather_reduce(recv, rows, w, interpret=interp)
    return _ref.combine_gather_reduce(recv, rows, w)


def quantize_fp8(x: jax.Array, block: int = 128):
    use, interp = _use_pallas()
    H = x.shape[-1]
    M = math.prod(x.shape[:-1])
    if use and H % block == 0 and block % 128 == 0 and M > 0 and M % 8 == 0:
        q, s = _fp8.quantize_fp8(x.reshape(M, H), block, interpret=interp)
        return q.reshape(x.shape), s.reshape(x.shape[:-1] + (H // block,))
    return _ref.quantize_fp8(x, block)


def dequantize_fp8(q: jax.Array, scales: jax.Array, out_dtype=jnp.bfloat16):
    use, interp = _use_pallas()
    H = q.shape[-1]
    M = math.prod(q.shape[:-1])
    block = H // scales.shape[-1] if scales.shape[-1] else 0
    if (use and block and H % block == 0 and block % 128 == 0
            and M > 0 and M % 8 == 0):
        out = _fp8.dequantize_fp8(q.reshape(M, H), scales.reshape(M, H // block),
                                  out_dtype, interpret=interp)
        return out.reshape(q.shape)
    return _ref.dequantize_fp8(q, scales, out_dtype)


def dispatch_pack(x: jax.Array, gmap: jax.Array, quant_block: int | None = None,
                  out_dtype=None):
    """Fused slot-pack (+ optional fp8 quantization) over a [N, C] slot map.

    ``out_dtype`` (copy mode only) casts the packed payload; None keeps
    x.dtype. Quantizing always yields (f8e4m3 payload, f32 scales)."""
    use, interp = _use_pallas()
    if use and x.shape[-1] % 128 == 0:
        return _dp.dispatch_pack(x, gmap, quant_block=quant_block,
                                 out_dtype=out_dtype, interpret=interp)
    return _ref.dispatch_pack(x, gmap, quant_block, out_dtype)


def recv_unpack(recv: jax.Array, gmap: jax.Array, scales: jax.Array | None = None,
                out_dtype=None):
    """Fused recv-side slot unpack (+ optional fp8 dequantization) — the
    mirror of dispatch_pack. recv: [R, H] flat received rows; gmap: int32
    slot map of any shape (sentinel == R); scales: [R, H/block] f32 when the
    payload is quantized. One pass; no intermediate gathered-fp8 copy."""
    use, interp = _use_pallas()
    H = recv.shape[-1]
    if scales is not None:
        block = H // scales.shape[-1] if scales.shape[-1] else 0
        ok = bool(block) and H % block == 0 and block % 128 == 0
    else:
        ok = H % 128 == 0
    if use and ok:
        return _ru.recv_unpack(recv, gmap, scales, out_dtype=out_dtype,
                               interpret=interp)
    return _ref.recv_unpack(recv, gmap, scales, out_dtype)


def flash_attention_bshd(q, k, v, *, scale, window=None, causal=True):
    """[B,S,H,d]-layout wrapper over the flash-attention kernel (TPU) with
    the chunked-XLA formulation as the portable fallback (same math)."""
    use, interp = _use_pallas()
    hd = q.shape[-1]
    if use and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0:
        from repro.kernels import flash_attention as _fa
        out = _fa.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=scale, window=window,
            causal=causal, interpret=interp)
        return out.transpose(0, 2, 1, 3)
    from repro.models.attention import _sdpa_chunked
    return _sdpa_chunked(q, k, v, None, scale, window)


_FALLBACK_WARNED: set = set()


def paged_decode_attention(q, k_pages, v_pages, kv_indices, kv_lens, *,
                           scale, num_kv_splits=1, rope_pages=None):
    """Split-KV paged decode attention over a page-table-indexed KV pool.

    q: [B, Hq, dk (+ rope)]; k_pages: [P+1, page, Hkv, dk] (last row =
    zero pad page); v_pages: same layout with trailing dv, or None for the
    absorbed-MLA pools (values are the latent key pool itself, and
    ``rope_pages`` [P+1, page, 1, rope] holds the rotary keys);
    kv_indices: [B, max_pages] int32 padded with P; kv_lens: [B] int32.
    Returns [B, Hq, dv] f32. Two-stage flash-decoding on TPU; jnp oracle
    elsewhere (identical masking semantics — exact zeros off the live
    prefix, so both backends are safe over recycled pages). A shape the
    kernel does not take falls back to the oracle, with a warning once per
    shape on a TPU."""
    use, interp = _use_pallas()
    page = k_pages.shape[1]
    dk = k_pages.shape[-1]
    dv = dk if v_pages is None else v_pages.shape[-1]
    kw = dict(scale=scale, num_kv_splits=num_kv_splits, rope_pages=rope_pages)
    if use and dk % 128 == 0 and dv % 128 == 0 and page % 8 == 0:
        from repro.kernels import decode_attention as _da
        return _da.paged_decode_attention(
            q, k_pages, v_pages, kv_indices, kv_lens, interpret=interp, **kw)
    if use and not interp:
        shape = (q.shape, k_pages.shape, None if v_pages is None
                 else v_pages.shape,
                 None if rope_pages is None else rope_pages.shape)
        if shape not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(shape)
            import warnings
            warnings.warn(
                f"paged decode attention falls back to the jnp oracle for "
                f"q {q.shape}, pools {shape[1:]}: the kernel needs key and "
                f"value widths % 128 == 0 and page % 8 == 0", stacklevel=2)
    return _ref.paged_decode_attention(
        q, k_pages, v_pages, kv_indices, kv_lens, **kw)


def grouped_gemm(x: jax.Array, w: jax.Array, counts: jax.Array) -> jax.Array:
    use, interp = _use_pallas()
    L, A, H = x.shape
    F = w.shape[-1]
    if use and A % 128 == 0 and F % 128 == 0 and H % 128 == 0:
        return _gg.grouped_gemm(x, w, counts, interpret=interp)
    return _ref.grouped_gemm(x, w, counts)

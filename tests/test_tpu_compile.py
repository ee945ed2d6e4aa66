"""Compile the main-path Pallas kernels for a described TPU v5e at real widths.

Nothing runs: the TPU compiler that ships with JAX lowers each kernel for a
chip that is described, not attached, and raises what Mosaic would raise on
the chip (block shapes off the (8, 128) tiling, VMEM overflow). Interpret
mode cannot catch those. Each test asserts that the compiled program holds
the kernel (``tpu_custom_call``), so a kernel that silently fell back to XLA
fails too.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and xdist workers must
all collect the same tests.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import combine_gather_reduce as cgr
from repro.kernels import combine_reduce as cr
from repro.kernels import decode_attention as da
from repro.kernels import dispatch_pack as dp
from repro.kernels import flash_attention as fa
from repro.kernels import fp8
from repro.kernels import grouped_gemm as gg
from repro.kernels import recv_unpack as ru

H = 7168           # the paper's hidden size
QB = 128           # fp8 quantization block (one scale per 128 values)
BF16, F32, I32, F8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("quant", [None, QB])
def test_dispatch_pack(one_chip, quant):
    fn = functools.partial(dp.dispatch_pack, quant_block=quant)
    _compile(fn, one_chip, ((128, H), BF16), ((4, 256), I32))


@pytest.mark.parametrize("quant", [False, True])
def test_recv_unpack(one_chip, quant):
    shapes = [((1024, H), F8 if quant else BF16), ((8, 128), I32)]
    if quant:
        shapes.append(((1024, H // QB), F32))
    _compile(ru.recv_unpack, one_chip, *shapes)


@pytest.mark.parametrize("R,gmap", [(32768, (256, 256)), (512, (64, 128))],
                         ids=["ht", "ll"])
def test_recv_unpack_cells(one_chip, R, gmap):
    # the EP cells' dispatch-recv unpack: fp8 rows and their scales into a
    # [L, A] expert-major map, HT on one chip, LL on each chip of four
    _compile(ru.recv_unpack, one_chip,
             ((R, H), F8), (gmap, I32), ((R, H // QB), F32))


@pytest.mark.parametrize("R,T", [(32768, 4096), (4096, 128)], ids=["ht", "ll"])
def test_combine_gather_reduce(one_chip, R, T):
    # the EP cells' combine: HT 4096 tokens x top-8 on one chip, LL 128
    _compile(cgr.combine_gather_reduce, one_chip,
             ((R, H), BF16), ((T, 8), I32), ((T, 8), F32))


def test_combine_reduce(one_chip):
    _compile(cr.combine_reduce, one_chip, ((128, 8, H), BF16), ((128, 8), F32))


def test_fp8_quantize_dequantize(one_chip):
    M = 256
    _compile(functools.partial(fp8.quantize_fp8, block=QB), one_chip,
             ((M, H), BF16))
    _compile(fp8.dequantize_fp8, one_chip, ((M, H), F8), ((M, H // QB), F32))


def test_grouped_gemm_dbrx(one_chip):
    # DBRX expert FFN: d_model 6144 -> d_ff_expert 10752, 4 local experts
    _compile(gg.grouped_gemm, one_chip,
             ((4, 128, 6144), BF16), ((4, 6144, 10752), BF16), ((4,), I32))


def test_paged_decode_gqa(one_chip):
    # DBRX attention: 48 query / 8 kv heads of 128; page 8, 4 KV splits
    B, P, page, max_pages = 8, 256, 8, 32
    fn = functools.partial(da.paged_decode_attention, scale=128 ** -0.5,
                           num_kv_splits=4)
    _compile(fn, one_chip, ((B, 48, 128), BF16),
             ((P + 1, page, 8, 128), BF16), ((P + 1, page, 8, 128), BF16),
             ((B, max_pages), I32), ((B,), I32))


def test_paged_decode_shared_pool(one_chip):
    # absorbed-MLA form: latent pool (keys and values) + 64-wide rope pool
    B, P, page, max_pages = 8, 256, 8, 32
    fn = functools.partial(da.paged_decode_attention, v_pages=None,
                           scale=192 ** -0.5, num_kv_splits=4)
    _compile(lambda q, c, r, t, n: fn(q, c, kv_indices=t, kv_lens=n,
                                      rope_pages=r), one_chip,
             ((B, 16, 576), BF16), ((P + 1, page, 1, 512), BF16),
             ((P + 1, page, 1, 64), BF16), ((B, max_pages), I32), ((B,), I32))


def test_paged_decode_mla_serving_shape(one_chip):
    # the dsv3-longgen cell: 128 slots, DeepSeek-V3's 128 heads over the
    # 576-wide latent row (512 + 64), page 64, max_len 2304 -> 36 pages
    B, page, max_pages = 128, 64, 36
    P = B * max_pages
    fn = functools.partial(da.paged_decode_attention, v_pages=None,
                           scale=192 ** -0.5, num_kv_splits=4)
    text = _compile(lambda q, c, r, t, n: fn(q, c, kv_indices=t, kv_lens=n,
                                             rope_pages=r), one_chip,
                    ((B, 128, 576), BF16), ((P + 1, page, 1, 512), BF16),
                    ((P + 1, page, 1, 64), BF16), ((B, max_pages), I32),
                    ((B,), I32))
    assert "paged_decode_stage1" in text and "paged_decode_stage2" in text


def test_flash_attention(one_chip):
    fn = functools.partial(fa.flash_attention, scale=128 ** -0.5)
    _compile(fn, one_chip, ((1, 48, 1024, 128), BF16),
             ((1, 8, 1024, 128), BF16), ((1, 8, 1024, 128), BF16))

"""Per-kernel validation: Pallas (interpret=True — executes the kernel body on
CPU) against the pure-jnp oracle in kernels/ref.py, swept over shapes and
dtypes. interpret mode is slow on this 1-core host, so sweeps are compact but
cover the alignment-relevant boundaries (128-lane tiles, K extremes, dtypes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.combine_reduce import combine_reduce as cr_pallas
from repro.kernels.combine_gather_reduce import combine_gather_reduce as cgr_pallas
from repro.kernels.combine_gather_reduce import token_block as cgr_token_block
from repro.kernels.dispatch_pack import dispatch_pack as dp_pallas
from repro.kernels.fp8 import quantize_fp8 as qfp8_pallas
from repro.kernels.fp8 import dequantize_fp8 as dqfp8_pallas
from repro.kernels.grouped_gemm import grouped_gemm as gg_pallas
from repro.kernels.recv_unpack import recv_unpack as ru_pallas
from repro.kernels.recv_unpack import row_block as ru_row_block


def tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,K,H", [(8, 2, 128), (16, 8, 256), (32, 4, 512), (8, 16, 128)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_combine_reduce(T, K, H, dt):
    rng = np.random.RandomState(0)
    y = jnp.asarray(rng.randn(T, K, H), dt)
    w = jax.nn.softmax(jnp.asarray(rng.randn(T, K), jnp.float32), -1)
    got = cr_pallas(y, w, interpret=True)
    want = ref.combine_reduce(y, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dt))


@pytest.mark.parametrize("bt,bh", [(4, 128), (8, 256)])
def test_combine_reduce_tilings(bt, bh):
    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.randn(16, 4, 256), jnp.float32)
    w = jnp.asarray(rng.rand(16, 4), jnp.float32)
    got = cr_pallas(y, w, bt=bt, bh=bh, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.combine_reduce(y, w)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,H,N,C", [(16, 128, 4, 8), (8, 256, 8, 4)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_dispatch_pack_copy(T, H, N, C, dt):
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(T, H), dt)
    gmap = jnp.asarray(rng.randint(0, T + 1, (N, C)), jnp.int32)  # T == sentinel
    got, _ = dp_pallas(x, gmap, out_dtype=dt, interpret=True)
    want, _ = ref.dispatch_pack(x, gmap)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want.astype(dt), np.float32), **tol(dt))


@pytest.mark.parametrize("T,H,qb", [(8, 256, 128), (16, 128, 128)])
def test_dispatch_pack_quantized(T, H, qb):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(T, H) * 3, jnp.float32)
    gmap = jnp.asarray(rng.randint(0, T + 1, (4, 8)), jnp.int32)
    q, s = dp_pallas(x, gmap, quant_block=qb, interpret=True)
    qr, sr = ref.dispatch_pack(x, gmap, quant_block=qb)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6, atol=1e-6)
    got = ref.dequantize_fp8(q.reshape(-1, H), s.reshape(-1, H // qb))
    want = ref.dequantize_fp8(qr.reshape(-1, H), sr.reshape(-1, H // qb))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("L,A,H,F", [(2, 128, 128, 128), (4, 256, 256, 128)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm(L, A, H, F, dt):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(L, A, H) * 0.1, dt)
    w = jnp.asarray(rng.randn(L, H, F) * 0.1, dt)
    counts = jnp.asarray(rng.randint(0, A + 1, (L,)), jnp.int32)
    got = gg_pallas(x, w, counts, interpret=True)
    want = ref.grouped_gemm(x, w, counts)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dt == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dt == jnp.bfloat16 else 1e-4)


def test_grouped_gemm_count_masking():
    """Rows at/beyond counts must be exactly zero; rows below must be exact."""
    L, A, H, F = 2, 256, 128, 128
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(L, A, H), jnp.float32)
    w = jnp.asarray(rng.randn(L, H, F), jnp.float32)
    counts = jnp.asarray([100, 0], jnp.int32)
    got = np.asarray(gg_pallas(x, w, counts, interpret=True))
    assert np.all(got[0, 100:] == 0) and np.all(got[1] == 0)
    want = np.einsum("ah,hf->af", np.asarray(x[0]), np.asarray(w[0]))[:100]
    np.testing.assert_allclose(got[0, :100], want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("R,T,K,H", [
    (32, 8, 2, 128), (16, 8, 4, 256), (64, 4, 1, 128), (16, 4, 2, 640),
    (64, 20, 8, 256),      # T not a multiple of the token block (16)
    (96, 36, 8, 128),      # three blocks, the last partly padding
    (128, 16, 8, 256),     # K = 8, one full block
    (64, 12, 16, 4096),    # f32: the double buffer halves the block to 8
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_combine_gather_reduce(R, T, K, H, dt):
    """Fused gather+reduce vs the two-pass oracle, sentinel rows included;
    token blocks that do not divide T, and T below the block (T = 4)."""
    rng = np.random.RandomState(7)
    recv = jnp.asarray(rng.randn(R, H), dt)
    rows = jnp.asarray(rng.randint(0, R + 1, (T, K)), jnp.int32)  # R == sentinel
    w = jax.nn.softmax(jnp.asarray(rng.randn(T, K), jnp.float32), -1)
    got = cgr_pallas(recv, rows, w, interpret=True)
    want = ref.combine_gather_reduce(recv, rows, w)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dt))


def test_combine_gather_reduce_all_sentinel():
    recv = jnp.asarray(np.random.RandomState(8).randn(8, 128), jnp.float32)
    rows = jnp.full((4, 2), 8, jnp.int32)
    w = jnp.ones((4, 2), jnp.float32)
    got = np.asarray(cgr_pallas(recv, rows, w, interpret=True))
    assert np.all(got == 0)


@pytest.mark.parametrize("bad", [np.inf, np.nan])
def test_combine_gather_reduce_sentinel_beside_nonfinite(bad):
    """A sentinel entry gathers the clamped last row; with that row inf or
    NaN it must still contribute exactly zero."""
    R, T, K, H = 16, 12, 4, 256
    rng = np.random.RandomState(10)
    recv = rng.randn(R, H).astype(np.float32)
    recv[R - 1] = bad
    rows = rng.randint(0, R - 1, (T, K))
    rows[::2, 1] = R                                  # sentinels in every other token
    rows[3] = R                                       # and one token of nothing but
    w = jax.nn.softmax(jnp.asarray(rng.randn(T, K), jnp.float32), -1)
    recv, rows = jnp.asarray(recv), jnp.asarray(rows, jnp.int32)
    got = np.asarray(cgr_pallas(recv, rows, w, interpret=True))
    want = np.asarray(ref.combine_gather_reduce(recv, rows, w))
    assert np.all(np.isfinite(got)) and np.all(got[3] == 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,K,H,itemsize,tb", [
    (4096, 8, 7168, 2, 16),     # dsv3-ep-ht
    (128, 8, 7168, 2, 16),      # dsv3-ep-ll-4chip
    (4, 8, 7168, 2, 4),         # fewer tokens than a block
    (4096, 8, 7168, 4, 8),      # f32 rows: 16 would overflow the buffer
])
def test_combine_gather_reduce_token_block_size(T, K, H, itemsize, tb):
    assert cgr_token_block(T, K, H, itemsize) == tb


@pytest.mark.parametrize("R,H,D,C", [(32, 128, 2, 8), (16, 256, 4, 4),
                                     (64, 640, 3, 8)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_recv_unpack_copy_bitwise(R, H, D, C, dt):
    """Fused recv unpack (copy mode) vs the gather reference — bitwise,
    sentinel slots included."""
    rng = np.random.RandomState(11)
    recv = jnp.asarray(rng.randn(R, H), dt)
    gmap = jnp.asarray(rng.randint(0, R + 1, (D, C)), jnp.int32)  # R == sentinel
    got = ru_pallas(recv, gmap, interpret=True)
    want = ref.recv_unpack(recv, gmap)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("R,H,D,C", [(32, 256, 2, 8), (16, 128, 4, 4)])
def test_recv_unpack_dequant_bitwise(R, H, D, C):
    """Fused recv unpack (fp8 dequant mode) vs the two-pass gather+dequant
    reference — bitwise (same f32 math elementwise)."""
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(R, H) * 4, jnp.float32)
    q, s = ref.quantize_fp8(x, 128)
    gmap = jnp.asarray(rng.randint(0, R + 1, (D, C)), jnp.int32)
    got = ru_pallas(q, gmap, s, interpret=True)
    want = ref.recv_unpack(q, gmap, s)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_recv_unpack_ref_matches_two_pass():
    """The recv_unpack reference IS the seed's two-pass semantics: gather
    with zero fill, then block dequant over zero-filled scales."""
    from repro.core import slots as S
    rng = np.random.RandomState(13)
    R, H = 24, 256
    x = jnp.asarray(rng.randn(R, H) * 2, jnp.float32)
    q, s = ref.quantize_fp8(x, 128)
    gmap = jnp.asarray(rng.randint(0, R + 1, (4, 8)), jnp.int32)
    want = ref.dequantize_fp8(S.gather_rows(q, gmap),
                              S.gather_rows(s, gmap, fill=0))
    got = ref.recv_unpack(q, gmap, s)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_recv_unpack_all_sentinel_and_cast():
    recv = jnp.asarray(np.random.RandomState(14).randn(8, 128), jnp.bfloat16)
    gmap = jnp.full((2, 4), 8, jnp.int32)
    got = np.asarray(ru_pallas(recv, gmap, interpret=True), np.float32)
    assert np.all(got == 0)
    # out_dtype cast in copy mode
    got32 = ru_pallas(recv, gmap, out_dtype=jnp.float32, interpret=True)
    assert got32.dtype == jnp.float32


def _recv_unpack_pair(recv, gmap, quant):
    """(kernel, reference) unpack of f32 rows, fp8-quantized when quant."""
    if quant:
        q, s = ref.quantize_fp8(recv, 128)
        return (ru_pallas(q, gmap, s, interpret=True), ref.recv_unpack(q, gmap, s))
    return ru_pallas(recv, gmap, interpret=True), ref.recv_unpack(recv, gmap)


def _assert_bitwise(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("R,H,shape", [
    (64, 256, (5, 13)),     # 65 slots: four blocks of 16 and one of a single slot
    (48, 128, (3, 33)),     # 99 slots: the last block mostly padding
])
def test_recv_unpack_ragged_tail(R, H, shape, quant):
    """A slot count that is not a multiple of the row block: the padded
    tail is sliced off and the rest matches the reference bitwise."""
    rng = np.random.RandomState(15)
    recv = jnp.asarray(rng.randn(R, H) * 3, jnp.float32)
    gmap = jnp.asarray(rng.randint(0, R + 1, shape), jnp.int32)
    assert np.prod(shape) % ru_row_block(np.prod(shape), H, 4, 4) != 0
    _assert_bitwise(*_recv_unpack_pair(recv, gmap, quant))


@pytest.mark.parametrize("quant", [False, True])
def test_recv_unpack_sentinel_blocks(quant):
    """Block 0 all sentinels, block 1 all real, blocks 2 and 3 alternating
    real and sentinel slots (four blocks of the kernel's row block)."""
    R, H = 40, 256
    rng = np.random.RandomState(16)
    recv = jnp.asarray(rng.randn(R, H) * 3, jnp.float32)
    rb = ru_row_block(64, H, 4, 4)
    assert rb == 16
    m = rng.randint(0, R, 4 * rb)
    m[:rb] = R
    m[2 * rb::2] = R
    gmap = jnp.asarray(m.reshape(4, rb), jnp.int32)
    got, want = _recv_unpack_pair(recv, gmap, quant)
    _assert_bitwise(got, want)
    got = np.asarray(got, np.float32).reshape(4 * rb, H)
    assert np.all(got[:rb] == 0) and np.all(got[2 * rb::2] == 0)
    assert np.all(np.any(got[rb:2 * rb] != 0, axis=-1))


@pytest.mark.parametrize("quant", [False, True])
def test_recv_unpack_expert_major_map(quant):
    """A [L, A] map built as the plan builds it: each expert's region holds
    a prefix of routed rows (positions_by_dest), the rest sentinels."""
    from repro.core import slots as S
    L, A, R, H = 8, 48, 96, 128
    rng = np.random.RandomState(17)
    recv = jnp.asarray(rng.randn(R, H) * 3, jnp.float32)
    dest = jnp.asarray(rng.randint(0, L, R), jnp.int32)
    valid = jnp.asarray(rng.rand(R) < 0.8)
    pos, counts = S.positions_by_dest(dest, L, valid)
    gmap = S.build_gather_map(dest, pos, jnp.arange(R, dtype=jnp.int32), valid,
                              L, A, sentinel=R)
    g = np.asarray(gmap)
    for e, c in enumerate(np.asarray(counts)):
        assert np.all(g[e, :c] < R) and np.all(g[e, c:] == R)
    _assert_bitwise(*_recv_unpack_pair(recv, gmap, quant))


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
def test_recv_unpack_sentinel_beside_nonfinite(bad, quant):
    """Rows of inf or NaN land in block 0; block 4 (slots 64-66) reuses
    the same half of the double buffer with sentinels in those slots. The
    sentinel slots come out exactly zero, the non-finite rows as the
    reference has them."""
    R, H = 16, 256
    assert ru_row_block(96, H, 4, 4) == 16
    rng = np.random.RandomState(18)
    recv = rng.randn(R, H).astype(np.float32)
    recv[[3, 7]] = bad
    recv[5, ::3] = bad
    m = rng.randint(0, R, 96)
    m[:3] = [3, 7, 5]
    m[64:67] = R                                      # same buffer slots, block 4
    m[70::4] = R
    gmap = jnp.asarray(m.reshape(3, 32), jnp.int32)
    if quant:
        # quantize finite rows, then plant the non-finite payload and scale
        q, s = ref.quantize_fp8(jnp.asarray(np.nan_to_num(recv)), 128)
        q, s = np.array(q), np.array(s)
        q[[3, 7]] = np.float32(bad)
        s[5] = np.float32(bad)
        q, s = jnp.asarray(q), jnp.asarray(s)
        got, want = (ru_pallas(q, gmap, s, interpret=True),
                     ref.recv_unpack(q, gmap, s))
    else:
        got, want = _recv_unpack_pair(jnp.asarray(recv), gmap, False)
    _assert_bitwise(got, want)
    got = np.asarray(got, np.float32).reshape(96, H)
    sent = m == R
    assert np.all(got[sent] == 0)
    assert not np.all(np.isfinite(got[:3]))


def test_recv_unpack_dequant_full_width():
    """fp8 dequant at the paper's hidden size H = 7168, a small slot map;
    row 0 holds every one of the 256 fp8 codes (subnormals, -0, NaN)."""
    R, H = 12, 7168
    rng = np.random.RandomState(19)
    x = jnp.asarray(rng.randn(R, H) * 4, jnp.float32)
    q, s = ref.quantize_fp8(x, 128)
    codes = np.tile(np.arange(256, dtype=np.uint8), H // 256)
    q = q.at[0].set(jax.lax.bitcast_convert_type(jnp.asarray(codes), q.dtype))
    m = rng.randint(0, R + 1, (2, 5))
    m[0, 0] = 0
    gmap = jnp.asarray(m, jnp.int32)
    got = ru_pallas(q, gmap, s, interpret=True)
    _assert_bitwise(got, ref.recv_unpack(q, gmap, s))
    assert got.dtype == jnp.bfloat16


@pytest.mark.parametrize("M,H,in_size,out_size,rb", [
    (65536, 7168, 1, 2, 16),    # dsv3-ep-ht: fp8 in, bf16 out
    (8192, 7168, 1, 2, 16),     # dsv3-ep-ll-4chip
    (10, 7168, 1, 2, 10),       # fewer slots than a block
    (65536, 32768, 1, 4, 8),    # f32 out: 16 would overflow the buffer
])
def test_recv_unpack_row_block_size(M, H, in_size, out_size, rb):
    assert ru_row_block(M, H, in_size, out_size) == rb


@pytest.mark.parametrize("M,H,block", [(8, 256, 128), (16, 512, 128), (8, 128, 128),
                                       (8, 640, 128)])
def test_fp8_quantize_pallas_matches_ref(M, H, block):
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(M, H) * 4, jnp.float32)
    q, s = qfp8_pallas(x, block, interpret=True)
    qr, sr = ref.quantize_fp8(x, block)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6, atol=0)
    got = ref.dequantize_fp8(q, s, jnp.float32)
    want = ref.dequantize_fp8(qr, sr, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("M,H,block", [(8, 256, 128), (16, 128, 128)])
def test_fp8_dequantize_pallas_matches_ref(M, H, block):
    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(M, H) * 4, jnp.float32)
    q, s = ref.quantize_fp8(x, block)
    got = dqfp8_pallas(q, s, jnp.float32, interpret=True)
    want = ref.dequantize_fp8(q, s, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_quantize_zero_rows_unit_scale():
    """Zero groups must quantize with unit scale in both implementations."""
    x = jnp.zeros((8, 256), jnp.float32)
    q, s = qfp8_pallas(x, 128, interpret=True)
    qr, sr = ref.quantize_fp8(x, 128)
    np.testing.assert_array_equal(np.asarray(s), np.ones((8, 2), np.float32))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_quantize_roundtrip_accuracy():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(32, 512) * 5, jnp.float32)
    q, s = ref.quantize_fp8(x, 128)
    back = ref.dequantize_fp8(q, s, out_dtype=jnp.float32)
    rel = np.abs(np.asarray(back) - np.asarray(x)).mean() / np.abs(np.asarray(x)).mean()
    assert rel < 0.04, rel  # e4m3 block-quant: ~2-3% mean relative error

"""Paged KV pool + split-KV decode attention + continuous batching
(docs/DESIGN.md §10): the allocator never aliases a page across live owners
and fails LOUDLY naming its capacity; the two-stage Pallas decode kernel
matches the chunked-attention oracle in interpret mode — GQA and absorbed
MLA, every split count, ragged last pages, recycled-page garbage; and the
continuous-batching engine's per-request token streams are BITWISE identical
to running each request alone — including join/leave mid-stream and across a
heat-driven placement swap (the rank-kill transition is pinned next door in
tests/test_elastic.py)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# CI seed matrix: the interpret-parity job re-runs this file under several
# seeds (REPRO_TEST_SEED) — data/tables vary, every invariant must hold
SEED = int(os.environ.get("REPRO_TEST_SEED", "0"))

from repro.configs import get_smoke
from repro.kernels import decode_attention as DA
from repro.kernels import ref as KREF
from repro.models.attention import _sdpa_chunked
from repro.models.kv_pages import (PageAllocator, PagePoolExhausted,
                                   pages_for_tokens, write_token)
from repro.runtime.scheduler import ContinuousScheduler, Request
from repro.runtime.server import ContinuousDecodeServer


# --------------------------------------------------------------------------
# allocator invariants
# --------------------------------------------------------------------------

def test_allocator_never_aliases_live_pages():
    a = PageAllocator(16, 4)
    r1, r2, r3 = a.alloc(5), a.alloc(4), a.alloc(7)
    ids = r1 + r2 + r3
    assert sorted(ids) == list(range(16))      # all distinct, full pool
    assert a.free_count == 0 and a.live_count == 16
    a.free(r2)
    r4 = a.alloc(4)                            # recycles r2's pages...
    assert not set(r4) & (set(r1) | set(r3))   # ...but never a LIVE page
    assert a.peak_live == 16                   # high-water survives the free


def test_allocator_exhaustion_is_loud_and_atomic():
    a = PageAllocator(4, 8)
    a.alloc(3)
    # the failure names request size, free count, capacity, and page size —
    # actionable without a debugger
    with pytest.raises(PagePoolExhausted,
                       match=r"requested 2 page\(s\) with 1 free of 4 total "
                             r"\(page_size=8\)"):
        a.alloc(2)
    assert a.free_count == 1                   # failed alloc took nothing
    assert a.alloc(1) is not None


def test_allocator_double_free_raises():
    a = PageAllocator(4, 8)
    (pid,) = a.alloc(1)
    a.free([pid])
    with pytest.raises(ValueError, match=f"page {pid}"):
        a.free([pid])


def test_pages_for_tokens_ceil():
    assert pages_for_tokens(1, 4) == 1
    assert pages_for_tokens(4, 4) == 1
    assert pages_for_tokens(5, 4) == 2
    assert pages_for_tokens(0, 4) == 0


# --------------------------------------------------------------------------
# split-KV kernel parity (interpret mode; smoke dims are below the ops.py
# TPU-alignment gates, so the kernel is exercised DIRECTLY — the ops wrapper
# would route these shapes to the jnp oracle)
# --------------------------------------------------------------------------

def _dense_softmax_ref(q, k, v, lens, scale):
    """Straight numpy softmax over the first lens[b] gathered positions —
    independent of both the kernel and the jnp oracle."""
    B, Hq, dk = q.shape
    Hkv, G = k.shape[2], Hq // k.shape[2]
    dv = v.shape[-1]
    out = np.zeros((B, Hq, dv), np.float32)
    for b in range(B):
        n = int(lens[b])
        if n == 0:
            continue
        kk = k[b, :n].astype(np.float64)                 # [n, Hkv, dk]
        vv = v[b, :n].astype(np.float64)
        for h in range(Hq):
            s = kk[:, h // G] @ q[b, h].astype(np.float64) * scale
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ vv[:, h // G]
    return out


def _paged_case(rng, *, B, Hkv, G, dk, dv, page, max_pages, lens,
                share_kv=False):
    """Random pool + SHUFFLED page tables + garbage in every unreferenced
    page (pad page included) — parity must hold regardless."""
    P = B * max_pages
    k_pool = rng.randn(P + 1, page, Hkv, dk).astype(np.float32)
    v_pool = rng.randn(P + 1, page, Hkv, dv).astype(np.float32)
    perm = rng.permutation(P)
    tbl = np.full((B, max_pages), P, np.int32)
    kd, vd = (np.zeros((B, max_pages * page, Hkv, dk), np.float32),
              np.zeros((B, max_pages * page, Hkv, dv), np.float32))
    for b in range(B):
        used = pages_for_tokens(int(lens[b]), page)
        tbl[b, :used] = perm[b * max_pages:b * max_pages + used]
        for j in range(used):
            kd[b, j * page:(j + 1) * page] = k_pool[tbl[b, j]]
            vd[b, j * page:(j + 1) * page] = (
                k_pool[tbl[b, j], :, :, :dv] if share_kv else v_pool[tbl[b, j]])
    q = rng.randn(B, Hkv * G, dk).astype(np.float32)
    return q, k_pool, v_pool, tbl, kd, vd


@pytest.mark.parametrize("splits", [1, 2, 4])
def test_kernel_matches_oracle_gqa(splits):
    """GQA, ragged last page (10 % 4 = 2), full row, and an IDLE row
    (kv_len 0, all-pad table) — kernel ≡ oracle ≡ dense softmax."""
    rng = np.random.RandomState(SEED + 11)
    B, Hkv, G, dk, dv, page, max_pages = 3, 2, 2, 16, 16, 4, 4
    lens = np.array([10, 16, 0], np.int32)
    scale = dk ** -0.5
    q, kp, vp, tbl, kd, vd = _paged_case(
        rng, B=B, Hkv=Hkv, G=G, dk=dk, dv=dv, page=page,
        max_pages=max_pages, lens=lens)
    got = DA.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        jnp.asarray(lens), scale=scale, num_kv_splits=splits, interpret=True)
    ref = KREF.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        jnp.asarray(lens), scale=scale, num_kv_splits=splits)
    dense = _dense_softmax_ref(q, kd, vd, lens, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[2] == 0.0)   # idle row: EXACT zeros


@pytest.mark.parametrize("splits", [1, 2, 4])
def test_kernel_matches_oracle_mla_shared_pool(splits):
    """Absorbed-MLA share-kv mode: the latent pool (Hkv == 1, v_pages=None)
    is keys and values both, and the rope pool's keys are scored by the
    query's last columns — against the dense softmax over [ckv | k_rope]
    keys and ckv values."""
    rng = np.random.RandomState(SEED + 13)
    B, r, dr, page, max_pages = 3, 16, 8, 4, 4
    Hq = 4
    lens = np.array([7, 13, 0], np.int32)
    scale = (r + dr) ** -0.5
    q, kp, _, tbl, kd, _ = _paged_case(
        rng, B=B, Hkv=1, G=Hq, dk=r + dr, dv=r, page=page,
        max_pages=max_pages, lens=lens, share_kv=True)
    ckv, krope = kp[..., :r], kp[..., r:]
    args = (jnp.asarray(q), jnp.asarray(ckv), None, jnp.asarray(tbl),
            jnp.asarray(lens))
    got = DA.paged_decode_attention(
        *args, scale=scale, num_kv_splits=splits,
        rope_pages=jnp.asarray(krope), interpret=True)
    ref = KREF.paged_decode_attention(
        *args, scale=scale, num_kv_splits=splits,
        rope_pages=jnp.asarray(krope))
    dense = _dense_softmax_ref(q, kd, kd[..., :r], lens, scale)
    # the kernel rounds probabilities to the pool's dtype (f32 here)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[2] == 0.0)


def test_kernel_matches_chunked_prefill_last_row():
    """Cross-check against the PREFILL path: the last causal row of
    ``_sdpa_chunked`` over [B, S] must equal the paged decode of token S-1
    against the first S-1 cached tokens plus itself."""
    rng = np.random.RandomState(SEED + 17)
    B, S, Hkv, G, d, page = 2, 14, 2, 2, 16, 4     # ragged: 14 % 4 = 2
    Hq = Hkv * G
    q = rng.randn(B, S, Hq, d).astype(np.float32)
    k = rng.randn(B, S, Hkv, d).astype(np.float32)
    v = rng.randn(B, S, Hkv, d).astype(np.float32)
    scale = d ** -0.5
    pre = _sdpa_chunked(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        None, scale, None, chunk=8)   # 14 % 8 != 0 too
    # scatter ALL S tokens into pages (identity-ordered tables suffice —
    # shuffled tables are covered above), decode the last token
    max_pages = pages_for_tokens(S, page)
    P = B * max_pages
    kp = np.zeros((P + 1, page, Hkv, d), np.float32)
    vp = np.zeros((P + 1, page, Hkv, d), np.float32)
    tbl = np.full((B, max_pages), P, np.int32)
    for b in range(B):
        for j in range(max_pages):
            pid = b * max_pages + j
            tbl[b, j] = pid
            rows = k[b, j * page:(j + 1) * page]
            kp[pid, :rows.shape[0]] = rows
            rows = v[b, j * page:(j + 1) * page]
            vp[pid, :rows.shape[0]] = rows
    got = DA.paged_decode_attention(
        jnp.asarray(q[:, -1]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tbl), jnp.asarray(np.full(B, S, np.int32)), scale=scale,
        num_kv_splits=2, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(pre[:, -1], np.float32),
                               rtol=2e-5, atol=2e-5)


def test_write_token_lands_at_table_slot_and_pad_for_idle():
    pool = jnp.zeros((5, 4, 1, 2), jnp.float32)     # 4 pages + pad row 4
    tbl = jnp.asarray([[2, 0], [4, 4]], jnp.int32)  # row 1 idle (all pad)
    new = jnp.asarray([[[1.0, 2.0]], [[9.0, 9.0]]], jnp.float32)
    out = write_token(pool, new, tbl, jnp.asarray([5, 0], jnp.int32))
    assert np.allclose(np.asarray(out)[0, 1, 0], [1.0, 2.0])  # page 0, off 1
    assert np.allclose(np.asarray(out)[4, 0, 0], [9.0, 9.0])  # pad page
    assert np.asarray(out)[2].sum() == 0            # nothing else written


# --------------------------------------------------------------------------
# satellite: configurable kv_chunk, ragged max_len % chunk != 0
# --------------------------------------------------------------------------

def test_kv_chunk_ragged_tail_exact():
    """S not a multiple of the chunk: the zero-padded tail must be masked
    EXACTLY — chunk widths that do and don't divide S all agree."""
    rng = np.random.RandomState(SEED + 19)
    B, S, Hkv, G, d = 2, 50, 2, 2, 16
    q = jnp.asarray(rng.randn(B, S, Hkv * G, d), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, d), jnp.float32)
    full = _sdpa_chunked(q, k, v, None, d ** -0.5, None, chunk=S)
    for chunk in (24, 32, 50, 64):                 # 50 % 24, 50 % 32 != 0
        got = _sdpa_chunked(q, k, v, None, d ** -0.5, None, chunk=chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)


def test_kv_chunk_is_config_not_module_global():
    from repro.models import attention as A
    assert not hasattr(A, "_KV_CHUNK")             # the old mutable global
    cfg = get_smoke("dbrx-132b")
    assert cfg.attn.kv_chunk == 1024
    c2 = dataclasses.replace(
        cfg, attn=dataclasses.replace(cfg.attn, kv_chunk=512))
    assert c2.attn.kv_chunk == 512 and cfg.attn.kv_chunk == 1024


# --------------------------------------------------------------------------
# model-level: paged decode step vs dense decode step (logits agreement)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["dbrx-132b", "minicpm3-4b"])
def test_paged_step_matches_dense_step_logits(arch):
    """Drive the SAME token sequence through the dense decode step and the
    paged decode step (f32): logits agree to numerical tolerance at every
    position — GQA and absorbed MLA. (Bitwise token equality is asserted
    between continuous and solo runs of the SAME paged engine below; dense
    vs paged reassociates the softmax so it is allclose, not bitwise.)"""
    from repro.models import get_model
    from repro.parallel.sharding import init_from_specs
    from repro.runtime.steps import paged_serve_state_specs, serve_state_specs
    cfg = dataclasses.replace(get_smoke(arch), dtype=jnp.float32)
    model = get_model(cfg)
    B, T, page = 2, 9, 4
    max_pages = pages_for_tokens(T, page)
    params = init_from_specs(jax.random.PRNGKey(SEED), model.params_spec(cfg),
                             None)
    dense_spec, _ = serve_state_specs(cfg, B, 16)
    paged_spec, _ = paged_serve_state_specs(cfg, B, B * max_pages, page,
                                            max_pages)
    st_d = jax.tree.map(jnp.zeros_like,
                        init_from_specs(jax.random.PRNGKey(1), dense_spec, None))
    st_p = jax.tree.map(jnp.zeros_like,
                        init_from_specs(jax.random.PRNGKey(1), paged_spec, None))
    toks = np.random.RandomState(SEED + 23).randint(0, cfg.vocab, (B, T))
    tbl = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    for t in range(T):
        batch = dict(tokens=jnp.asarray(toks[:, t:t + 1], jnp.int32))
        ld, st_d = model.decode_step(params, st_d, batch, cfg, None)
        batch.update(page_tbl=jnp.asarray(tbl),
                     kv_lens=jnp.full((B,), t, jnp.int32),
                     active=jnp.ones((B,), jnp.int32))
        lp, st_p = model.paged_decode_step(params, st_p, batch, cfg, None)
        np.testing.assert_allclose(np.asarray(ld, np.float32),
                                   np.asarray(lp, np.float32),
                                   rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# continuous batching: join/leave mid-stream, bitwise solo parity
# --------------------------------------------------------------------------

def _requests():
    return [
        Request(0, np.array([3, 5, 7], np.int32), 6, arrival_step=0),
        Request(1, np.array([11, 2], np.int32), 8, arrival_step=0),
        Request(2, np.array([9, 9, 9, 9, 1], np.int32), 5, arrival_step=4),
        Request(3, np.array([4], np.int32), 7, arrival_step=6),
    ]


@pytest.mark.parametrize("arch", ["dbrx-132b", "minicpm3-4b"])
def test_continuous_bitwise_matches_solo(arch):
    """The acceptance bar: requests joining and leaving mid-stream — slots
    recycled, pages recycled LIFO under live neighbours — produce per-request
    token streams BITWISE identical to each request running alone through
    the same engine. Exact-zero masking + batch-row independence, not
    tolerance."""
    cfg = get_smoke(arch)
    reqs = _requests()
    srv = ContinuousDecodeServer(cfg, batch=3, max_len=32, page_size=4)
    m = srv.serve_requests(reqs)
    cont = {r.rid: srv.reqsched.tokens_for(r.rid) for r in reqs}
    srv.close()
    assert m.requests_completed == 4
    assert all(len(cont[r.rid]) == r.max_new_tokens for r in reqs)
    # with 3 slots and 4 requests, request 3 joined a slot recycled from a
    # completed neighbour at least once
    assert m.serve_steps > max(r.prompt.size + r.max_new_tokens for r in reqs)
    assert m.pages_peak <= m.pages_dense_equiv
    for r in reqs:
        solo = ContinuousDecodeServer(cfg, batch=3, max_len=32, page_size=4)
        solo.serve_requests([Request(r.rid, r.prompt, r.max_new_tokens)])
        st = solo.reqsched.tokens_for(r.rid)
        solo.close()
        np.testing.assert_array_equal(cont[r.rid], st)


def test_continuous_releases_all_pages_and_reservations():
    cfg = get_smoke("dbrx-132b")
    srv = ContinuousDecodeServer(cfg, batch=2, max_len=32, page_size=4,
                                 num_pages=8)      # tight pool: forces queueing
    srv.serve_requests(_requests())
    sched = srv.reqsched
    srv.close()
    assert sched.done
    assert sched.alloc.live_count == 0 and sched._reserved == 0
    assert sched.alloc.free_count == 8
    assert np.all(sched._tbl == sched.alloc.pad_page)   # every slot reset
    assert np.all(sched._active == 0)


def test_scheduler_admission_is_reservation_gated():
    """A request is admitted only when the pool can cover its WORST-CASE
    footprint on top of live reservations — lazy alloc then can never raise
    PagePoolExhausted mid-flight."""
    alloc = PageAllocator(4, 4)                    # 16 tokens of pool
    reqs = [Request(0, np.arange(6, dtype=np.int32), 5, arrival_step=0),
            Request(1, np.arange(4, dtype=np.int32), 5, arrival_step=0)]
    # each needs ceil((6+5-1)/4)=3 / ceil((4+5-1)/4)=2 pages: both at once
    # would need 5 > 4, so request 1 must wait for request 0 to finish
    sched = ContinuousScheduler(reqs, 2, 4, alloc)
    feed = sched.advance(0)
    assert list(feed["active"]) == [1, 0]          # only request 0 admitted
    assert sched._reserved + alloc.live_count <= alloc.num_pages
    step = 0
    while not sched.done and step < 64:
        if step:
            feed = sched.advance(step)
        sched.observe(np.zeros((2, 1), np.int32))
        step += 1
    assert sched.done and sorted(sched.finished) == [0, 1]
    assert alloc.live_count == 0


def test_scheduler_rejects_request_larger_than_pool():
    alloc = PageAllocator(2, 4)
    big = Request(7, np.arange(9, dtype=np.int32), 4)   # 12 tokens = 3 pages
    with pytest.raises(ValueError, match="request 7: needs 3 pages"):
        ContinuousScheduler([big], 1, 8, alloc)


def test_continuous_rejects_capacity_factor_and_bad_page_size():
    cfg = get_smoke("dbrx-132b")
    with pytest.raises(ValueError, match="kv_chunk"):
        ContinuousDecodeServer(cfg, batch=2, max_len=16, page_size=3)
    capped = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.5))
    with pytest.raises(ValueError, match="zero-drop"):
        ContinuousDecodeServer(capped, batch=2, max_len=16, page_size=4)


# --------------------------------------------------------------------------
# composition: bitwise parity ACROSS a heat-driven placement swap
# --------------------------------------------------------------------------

def test_continuous_bitwise_across_placement_swap():
    """EPLB swaps mid-serve (PR 2–5 contract) compose with continuous
    batching: placement only moves WHERE experts compute, so per-request
    streams stay bitwise equal to the no-rebalance run — and the engine
    re-jitted at least once."""
    from repro.core import placement as PL
    E = 8
    cfg = get_smoke("dbrx-132b")
    moe = dataclasses.replace(cfg.moe, ep_mode="ll", ep_axis=("data",),
                              track_expert_heat=True, params_physical=True,
                              placement=PL.redundant_placement(E, 8, E))
    cfg = dataclasses.replace(cfg, moe=moe)
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    reqs = _requests()

    srv_a = ContinuousDecodeServer(cfg, batch=8, max_len=32, mesh=mesh,
                                   page_size=4, num_redundant_experts=E)
    srv_a.serve_requests([dataclasses.replace(r) for r in reqs])
    base = {r.rid: srv_a.reqsched.tokens_for(r.rid) for r in reqs}
    srv_a.close()

    srv_b = ContinuousDecodeServer(cfg, batch=8, max_len=32, mesh=mesh,
                                   page_size=4, num_redundant_experts=E,
                                   rebalance_every=4)
    srv_b.serve_requests([dataclasses.replace(r) for r in reqs])
    swapped = {r.rid: srv_b.reqsched.tokens_for(r.rid) for r in reqs}
    assert len(srv_b.placements) >= 1              # at least one swap adopted
    assert len(srv_b._step_cache) >= 1
    srv_b.close()
    for r in reqs:
        np.testing.assert_array_equal(base[r.rid], swapped[r.rid])

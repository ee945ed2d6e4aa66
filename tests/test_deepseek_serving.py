"""DeepSeek-V3 served on one chip at its EP share (docs/DESIGN.md §11): the
engine's logits against the plain reference (``bench/reference/
deepseek_v3.py``, loaded by path: one reference for the tests and the
benchmark), the held experts' shares against the uncut layer, the one-chip
MoE unchanged when every expert is held, YaRN against its closed form, the
576-lane absorbed-MLA decode kernel against its oracle, and the paged
decode gate's warning."""
import dataclasses
import importlib.util
import math
import os
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.placement import identity_placement, rank_experts
from repro.core.routing import route
from repro.kernels import decode_attention as DA
from repro.kernels import ops as KOPS
from repro.kernels import ref as KREF
from repro.models import moe as MOE
from repro.models.layers import ffn_apply, yarn_frequencies, yarn_mscale
from repro.models.mla import softmax_scale
from repro.models.registry import get_model
from repro.parallel.sharding import init_from_specs
from repro.runtime.scheduler import Request
from repro.runtime.server import ContinuousDecodeServer

SEED = int(os.environ.get("REPRO_TEST_SEED", "0"))
_REF_PATH = (Path(__file__).resolve().parents[1] / "bench" / "reference"
             / "deepseek_v3.py")
_spec = importlib.util.spec_from_file_location("bench_reference_deepseek_v3",
                                               _REF_PATH)
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

RANKS = 2                      # the smoke router's 8 experts over 2 ranks


def _cfg(rank=None, dtype=jnp.float32):
    """The DeepSeek-V3 smoke config (MLA, YaRN, sigmoid noaux_tc over 8
    experts in 2 groups, a shared expert), in f32, holding ``rank``'s
    contiguous slice of the experts (all of them when None)."""
    base = get_smoke("deepseek-v3-671b")
    held = (None if rank is None else
            rank_experts(identity_placement(base.moe.num_experts, RANKS), rank))
    return dataclasses.replace(
        base, dtype=dtype, mtp=False,
        moe=dataclasses.replace(base.moe, held_experts=held,
                                routed_scaling=2.5))


def _params(cfg, seed):
    params = init_from_specs(jax.random.PRNGKey(seed),
                             get_model(cfg).params_spec(cfg))
    moe = params["moe_stack"]["moe"]
    moe["sel_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                              moe["sel_bias"].shape)
    return params


def _ref_cfg(cfg):
    m, ml, y = cfg.moe, cfg.mla, cfg.mla.rope_scaling
    held = m.held_experts or tuple(range(m.num_experts))
    return dict(eps=cfg.norm_eps, vocab=cfg.vocab, rope_theta=cfg.attn.rope_base,
                qk_nope=ml.qk_nope_dim, qk_rope=ml.qk_rope_dim,
                kv_rank=ml.kv_lora_rank, top_k=m.top_k, n_group=m.n_groups,
                topk_group=m.topk_groups, routed_scaling=m.routed_scaling,
                held=held, yarn_factor=y.factor,
                yarn_original=y.original_max_position,
                yarn_beta_fast=y.beta_fast, yarn_beta_slow=y.beta_slow,
                yarn_mscale=y.mscale, yarn_mscale_all_dim=y.mscale_all_dim)


def test_served_logits_match_the_reference_forward():
    """Prefill (a token per step) and paged decode through the engine, with
    a rank's 4 of 8 experts held: every logit the engine computed for a
    live row equals the reference's full forward over that request's
    tokens (un-absorbed MLA, YaRN, noaux_tc routing over all 8 experts, the
    held experts and the shared expert)."""
    cfg = _cfg(rank=1)
    params = _params(cfg, SEED + 3)
    model = get_model(cfg)
    srv = ContinuousDecodeServer(cfg, batch=2, max_len=16, params=params,
                                 page_size=4, seed=0)
    seen = []

    def step(params, state, feed):
        logits, state = model.paged_decode_step(params, state, feed, cfg, None)
        seen.append({k: np.asarray(v) for k, v in feed.items()}
                    | {"logits": np.asarray(logits[:, -1, :cfg.vocab])})
        return jnp.argmax(logits[:, -1, :cfg.vocab], -1)[:, None], state
    srv.step = step
    rng = np.random.default_rng(SEED)
    reqs = [Request(0, rng.integers(0, cfg.vocab, 5), 6, 0),
            Request(1, rng.integers(0, cfg.vocab, 7), 4, 2)]
    with jax.default_matmul_precision("highest"):
        srv.serve_requests(reqs)
    rc = _ref_cfg(cfg)
    checked = 0
    for b in range(2):
        rows = [(int(f["kv_lens"][b]), int(f["tokens"][b, 0]), f["logits"][b])
                for f in seen if f["active"][b]]
        seq = np.asarray([t for _, t, _ in rows], np.int32)
        assert [p for p, _, _ in rows] == list(range(seq.size))
        h, _ = REF.hidden(params, jnp.asarray(seq), rc)
        ref = np.asarray(REF.logits(params, h, rc))
        got = np.stack([lg for _, _, lg in rows])
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        checked += seq.size
    assert checked == 5 + 6 - 1 + 7 + 4 - 1


def _moe_input(cfg, seed, T=24):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T // 2, cfg.d_model))
    return x.astype(cfg.dtype)


def test_held_shares_sum_to_the_uncut_layer():
    """Over every rank's held slice, the routed parts add up, with the
    shared expert counted once, to the reference's uncut layer (all 8
    experts held)."""
    full = _cfg()
    p = _params(full, SEED + 5)["moe_stack"]
    p = jax.tree.map(lambda a: a[0], p)["moe"]
    x = _moe_input(full, SEED + 6)
    shared = ffn_apply(p["shared"], x, full.act)
    total = -shared * (RANKS - 1)
    with jax.default_matmul_precision("highest"):
        for r in range(RANKS):
            cfg = _cfg(rank=r)
            held = np.asarray(cfg.moe.held_experts)
            pr = dict(p, **{k: p[k][held] for k in ("w_gate", "w_up", "w_down")})
            y, _ = MOE.moe_block(pr, x, cfg, None)
            total = total + y
        ref, _ = REF._moe(p, x.reshape(-1, full.d_model), _ref_cfg(full), "f32")
    np.testing.assert_allclose(np.asarray(total).reshape(ref.shape),
                               np.asarray(ref), rtol=1e-4, atol=1e-5)


def _parent_dense_fallback(p, x, cfg):
    """The one-chip MoE as it was before experts could be held: dense over
    all E experts."""
    m = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    r = route(xt.astype(jnp.float32) @ p["router"], MOE._router_cfg(m),
              p.get("sel_bias"))
    w1, w3, w2 = p["w_gate"], p["w_up"], p["w_down"]
    h_g = jnp.einsum("td,edf->tef", xt, w1)
    h_u = jnp.einsum("td,edf->tef", xt, w3)
    h = (jax.nn.silu(h_g.astype(jnp.float32)) * h_u.astype(jnp.float32)).astype(x.dtype)
    y_all = jnp.einsum("tef,efd->ted", h, w2)
    oh = jax.nn.one_hot(r.topk_idx, m.num_experts, dtype=jnp.float32)
    gate = jnp.einsum("tk,tke->te", r.topk_weights, oh)
    y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), gate).astype(x.dtype)
    y = y.reshape(B, S, D)
    if m.shared_experts:
        y = y + ffn_apply(p["shared"], x, cfg.act)
    return y


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "dbrx-132b"])
@pytest.mark.parametrize("held", ["none", "all"])
def test_all_experts_held_is_the_parent_dense_fallback(arch, held):
    """Holding every expert (no slice named, or all E named) the one-chip
    MoE is bit for bit the dense fallback it replaced (bf16, jitted)."""
    cfg = get_smoke(arch)
    if held == "all":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held_experts=tuple(range(cfg.moe.num_experts))))
    p = init_from_specs(jax.random.PRNGKey(SEED + 7), MOE.moe_spec(cfg))
    x = _moe_input(cfg, SEED + 8)
    got = jax.jit(lambda p, x: MOE.moe_block(p, x, cfg, None)[0])(p, x)
    want = jax.jit(lambda p, x: _parent_dense_fallback(p, x, cfg))(p, x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_yarn_frequencies_and_scale_closed_form():
    """DeepSeek-V3's rope: 64 rotary dims, base 1e4, factor 40 over 4096
    positions, beta 32/1. The correction range is [10, 23]: pairs below
    keep base^(-2i/64), pairs above take it / 40, a linear ramp between;
    softmax scale 192^-0.5 · (0.1 ln 40 + 1)²."""
    cfg = get_smoke("deepseek-v3-671b")
    y = cfg.mla.rope_scaling
    inv = yarn_frequencies(64, 1e4, y)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    i = np.arange(32)
    ramp = np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-12)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(
        inv, REF.yarn_inv_freq(64, 1e4, 40, 4096, 32, 1), rtol=1e-12)
    m = 0.1 * math.log(40) + 1
    assert yarn_mscale(40.0, 1.0) == pytest.approx(m, rel=1e-12)
    assert m == pytest.approx(1.3689, abs=1e-4)
    full = dataclasses.replace(cfg, mla=dataclasses.replace(
        cfg.mla, qk_nope_dim=128, qk_rope_dim=64))
    assert softmax_scale(full) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    plain = dataclasses.replace(cfg, mla=dataclasses.replace(
        cfg.mla, rope_scaling=None))
    assert softmax_scale(plain) == (16 + 8) ** -0.5


def _mla_pools(rng, *, B, page, max_pages, lens, dtype):
    """Latent (512) and rope (64) pools with shuffled tables; every
    unreferenced page and the pad page hold garbage. Request 1's last page
    is recycled: past its live prefix it still holds a previous owner's
    rows (10x larger). Request 0's last page holds an inf past its prefix."""
    P = B * max_pages
    ckv = rng.standard_normal((P + 1, page, 1, 512)).astype(np.float32)
    kr = rng.standard_normal((P + 1, page, 1, 64)).astype(np.float32)
    tbl = np.full((B, max_pages), P, np.int32)
    perm = rng.permutation(P)
    for b in range(B):
        used = -(-int(lens[b]) // page)
        tbl[b, :used] = perm[b * max_pages:b * max_pages + used]

    def last(b):
        return tbl[b, (int(lens[b]) - 1) // page], int(lens[b]) % page
    pg, row = last(1)
    ckv[pg, row:] *= 10.0
    kr[pg, row:] *= 10.0
    pg, row = last(0)
    ckv[pg, row, 0, 3] = np.inf
    kr[pg, row, 0, 5] = -np.inf
    return (jnp.asarray(ckv, dtype), jnp.asarray(kr, dtype),
            jnp.asarray(tbl), jnp.asarray(lens))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_mla_decode_kernel_at_576_lanes(dtype, tol):
    """The share-kv kernel at DeepSeek-V3's widths (latent 512 + rope 64),
    interpret mode, against ``ref.paged_decode_attention``: ragged
    kv_lens, a recycled page (a previous owner's rows past the live
    prefix), an inf in a masked slot, and an idle row (exact zeros). bf16 pools multiply in bf16 with bf16 probabilities."""
    rng = np.random.default_rng(SEED + 21)
    B, Hq, page, max_pages = 3, 16, 8, 4
    lens = np.array([13, 30, 0], np.int32)       # both last pages ragged
    ckv, kr, tbl, lens = _mla_pools(rng, B=B, page=page, max_pages=max_pages,
                                    lens=lens, dtype=dtype)
    q = jnp.asarray(rng.standard_normal((B, Hq, 576)), dtype)
    kw = dict(scale=192 ** -0.5, num_kv_splits=2, rope_pages=kr)
    got = DA.paged_decode_attention(q, ckv, None, tbl, lens, interpret=True,
                                    **kw)
    want = KREF.paged_decode_attention(q, ckv, None, tbl, lens, **kw)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert np.all(got[2] == 0.0)


def test_paged_decode_gate_warns_once_per_shape_on_a_tpu(monkeypatch):
    """A shape the kernel does not take runs the oracle; on a TPU that is
    said once per shape."""
    monkeypatch.setattr(KOPS, "_use_pallas", lambda: (True, False))
    monkeypatch.setattr(KOPS, "_FALLBACK_WARNED", set())
    rng = np.random.default_rng(SEED)
    q = jnp.asarray(rng.standard_normal((2, 4, 24)), jnp.float32)
    ckv = jnp.asarray(rng.standard_normal((5, 4, 1, 16)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((5, 4, 1, 8)), jnp.float32)
    args = (q, ckv, None, jnp.asarray([[0, 1], [2, 3]], jnp.int32),
            jnp.asarray([5, 7], jnp.int32))
    with pytest.warns(UserWarning, match="falls back to the jnp oracle"):
        out = KOPS.paged_decode_attention(*args, scale=0.2, rope_pages=kr)
    want = KREF.paged_decode_attention(*args, scale=0.2, rope_pages=kr)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        KOPS.paged_decode_attention(*args, scale=0.2, rope_pages=kr)

"""The plain references against the program at smoke size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke
import weights
from reference import dbrx as REF_DBRX
from reference import ep as REF_EP

from repro.core.routing import route
from repro.models.layers import embed_lookup, logits_out, rmsnorm
from repro.models.registry import get_model
from repro.models.transformer import layer_apply
from repro.parallel.sharding import ParamSpec

SERVE = smoke.driver("serve")
EP = smoke.driver("ep_round_trip")


def _dbrx_params(seed):
    cfg = SERVE.arch_config(smoke.DBRX_SMOKE)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                          get_model(cfg).params_spec(cfg),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    return cfg, weights.make(seed, shapes)


def test_dbrx_reference_equals_program_forward_in_f32():
    """Same weights in f32, both at highest precision: the program's own
    layer (attention, MoE dense path, head) and the reference agree."""
    cfg, params = _dbrx_params(3)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seq = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, 24),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = embed_lookup(p32["embed"], seq[None])
        lp = jax.tree.map(lambda a: a[0], p32["moe_stack"])
        x, _, _ = layer_apply(lp, x, cfg, None)
        prog = logits_out(rmsnorm(x, p32["ln_f"], cfg.norm_eps),
                          p32["lm_head"])[0, :, :cfg.vocab]
    rc = SERVE.ref_config(smoke.DBRX_SMOKE)
    h, margin = REF_DBRX.hidden(params, seq, rc)
    ref = REF_DBRX._logits(params, h, rc, "f32")
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert margin.shape == seq.shape and bool((margin >= 0).all())


def test_dbrx_served_gaps_score_each_served_token():
    _, params = _dbrx_params(4)
    rc = SERVE.ref_config(smoke.DBRX_SMOKE)
    prompt = np.asarray([5, 9, 13], np.int32)
    seq = jnp.asarray([5, 9, 13, 0, 0, 0], jnp.int32)
    h, _ = REF_DBRX.hidden(params, seq, rc)
    best = np.asarray(jnp.argmax(REF_DBRX._logits(params, h, rc, "f32"), -1))
    # greedy served tokens from the reference itself read a gap of zero
    served = [int(best[2])]
    for _ in range(2):
        s = np.concatenate([prompt, served])
        h, _ = REF_DBRX.hidden(params, jnp.asarray(np.pad(s, (0, 6 - s.size))), rc)
        served.append(int(jnp.argmax(REF_DBRX._logits(params, h, rc, "f32"), -1)[s.size - 1]))
    g, c, m = REF_DBRX.served_gaps(params, prompt, np.asarray(served), rc,
                                   pad_to=8, control=True)
    assert g.tolist() == [0.0, 0.0, 0.0]
    assert c.shape == (3,) and m.shape == (3,)
    wrong = np.asarray(served) + 1
    g2, _, _ = REF_DBRX.served_gaps(params, prompt, wrong, rc, pad_to=8)
    assert (g2 > 0).any()


def test_ep_reference_routing_matches_the_program_router():
    conf = smoke.DSV3_SMOKE
    rc = EP.router_config(conf)
    x = jax.random.normal(jax.random.key(1), (64, conf["hidden_size"]))
    w = jax.random.normal(jax.random.key(2), (conf["hidden_size"], 16)) * 0.06
    with jax.default_matmul_precision("highest"):
        r = route(x @ w, rc)
    idx, tw, tie = REF_EP.route(x, w, EP.ref_router(conf))
    ok = ~np.asarray(tie)
    assert ok.sum() >= 60
    np.testing.assert_array_equal(np.sort(np.asarray(r.topk_idx)[ok], -1),
                                  np.sort(np.asarray(idx)[ok], -1))
    np.testing.assert_allclose(np.sort(np.asarray(r.topk_weights)[ok], -1),
                               np.sort(np.asarray(tw)[ok], -1), rtol=1e-5)


@pytest.mark.parametrize("quant,tol", [("fp8", 0.07), ("int4", 0.3)])
def test_ep_payload_rounding(quant, tol):
    x = jax.random.normal(jax.random.key(3), (8, 256))
    q = REF_EP.quantize(x, quant)
    err = float(jnp.linalg.norm(q - x) / jnp.linalg.norm(x))
    assert 0 < err < tol
    assert REF_EP.quantize(x, "none") is x

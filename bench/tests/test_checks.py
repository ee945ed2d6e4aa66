"""The check that decides ``correct``, driven through the whole run at
smoke size with the device check skipped: sound runs pass, and each fault
the cells can have, and each control, comes out not correct."""
import jax
import jax.numpy as jnp
import pytest

import run as RUN
import smoke

SERVE = smoke.driver("serve")
EP = smoke.driver("ep_round_trip")


@pytest.fixture(scope="module")
def serve_run():
    return SERVE.run(smoke.cell(smoke.DBRX_SMOKE, smoke.SERVE_SMOKE, seed=2,
                                seconds=0.4, hooks={"control": True}))


def test_serve_sound_run_is_correct(serve_run):
    assert RUN.is_correct(serve_run["checks"])
    assert serve_run["attempted"] > 0 and serve_run["failed"] == 0
    assert {"output_tok_s", "itl_p95_ms", "ttft_p95_ms"} <= set(serve_run["e2e"])


def test_serve_control_is_not_correct(serve_run):
    """fp8 reference in the program's place: its widest gap fails."""
    limit = smoke.SERVE_SMOKE["limit_logit_gap"]
    assert serve_run["control"]["logit_gap_max"] > 3 * limit


def test_serve_altered_token_is_not_correct():
    def alter(tok, feed):
        return (tok + 1) % smoke.DBRX_SMOKE["vocab_size"]
    out = SERVE.run(smoke.cell(smoke.DBRX_SMOKE, smoke.SERVE_SMOKE, seed=2,
                               seconds=0.3, hooks={"alter": alter}))
    assert not RUN.is_correct(out["checks"])


@pytest.fixture(scope="module")
def ep_run():
    return EP.run(smoke.cell(smoke.DSV3_SMOKE, smoke.EP_SMOKE, chips=4,
                             seed=5, seconds=0.3, hooks={"control": True}))


def test_ep_sound_run_is_correct(ep_run):
    assert RUN.is_correct(ep_run["checks"])
    assert ep_run["e2e"]["ep_layer_us"] > 0


def test_ep_control_is_not_correct(ep_run):
    """int4 payload in place of fp8: its worst token fails."""
    limit = smoke.EP_SMOKE["limit_rel_err"]
    assert ep_run["control"]["worst_token_rel_err"] > 3 * limit


def test_ep_altered_answer_is_not_correct():
    def alter(y):
        return y.at[0].multiply(1.5)
    out = EP.run(smoke.cell(smoke.DSV3_SMOKE, smoke.EP_SMOKE, chips=4, seed=5,
                            seconds=0.2, hooks={"alter": alter}))
    assert not RUN.is_correct(out["checks"])


def test_ep_exchange_left_out_is_not_correct(monkeypatch):
    from repro.core import ll
    monkeypatch.setattr(ll, "_a2a", lambda x, group: x)
    out = EP.run(smoke.cell(smoke.DSV3_SMOKE, smoke.EP_SMOKE, chips=4, seed=5,
                            seconds=0.2))
    assert not RUN.is_correct(out["checks"])

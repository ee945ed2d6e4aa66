"""Scenario implementations. Five traffic shapes through the real serving
engines, telemetry on, acceptance asserted in-bench:

  poisson   — Poisson arrivals through the continuous-batching engine; all
              requests must complete, paged <= dense page accounting, one
              admit/complete instant per request, and the profiler trace
              (Perfetto-readable) holds the serve.* step spans.
  bursty    — synchronized arrival bursts larger than the slot count; a
              queue backlog must FORM (visible in the per-step time series)
              and fully drain.
  drift     — Zipf-style routing skew that MOVES between expert pairs
              mid-serve (driven through the router's selection bias, so the
              skew flows through the real routed model, not a synthetic
              histogram); the EPLB rebalancer must cut the per-rank
              imbalance ratio after each rebalance boundary, including
              after the hot set drifts — the case where heat decay earns
              its keep.
  cliff     — context-length sweep against a deliberately small page pool;
              requests that fit must complete with monotone page high-water,
              requests past the cliff must be REJECTED loudly up front
              (reservation-gated admission), and raw pool exhaustion must
              raise PagePoolExhausted — never silent corruption.
  ramp      — the same request set at growing max concurrency; steps to
              completion must not increase, and per-request token streams
              must stay bitwise identical across concurrency levels.

Rows land in results/benchmarks/scenarios.json (folded into
BENCH_ll_kernels.json schema v7); profiler-trace/series artifacts under
results/benchmarks/scenarios/.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS, pct_ms, table, write_result
from benchmarks.scenarios.arrivals import (bursty_arrivals, poisson_arrivals,
                                           zipf_prompt_lengths)
from repro.configs import get_smoke
from repro.models.kv_pages import (PageAllocator, PagePoolExhausted,
                                   pages_for_tokens)
from repro.runtime.scheduler import Request
from repro.runtime.server import ContinuousDecodeServer, DecodeServer
from repro.runtime.telemetry import Tracer, TimeSeries

ARTIFACTS = RESULTS / "scenarios"


def _mesh8():
    return jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _ll_cfg(**moe_kw):
    cfg = get_smoke("dbrx-132b")
    moe = dataclasses.replace(cfg.moe, ep_mode="ll", ep_axis=("data",),
                              track_expert_heat=True, **moe_kw)
    return dataclasses.replace(cfg, moe=moe)


def _requests(arrivals, plens, max_new, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(i, rng.randint(0, 256, int(plens[i])).astype(np.int32),
                    max_new, arrival_step=int(arrivals[i]))
            for i in range(len(arrivals))]


# --------------------------------------------------------------------------
# poisson
# --------------------------------------------------------------------------

def scenario_poisson(n_req=12, rate=0.5, max_new=8):
    arrivals = poisson_arrivals(n_req, rate, seed=0)
    plens = zipf_prompt_lengths(n_req, 3, 8, seed=1)
    tr, ts = Tracer(), TimeSeries()
    srv = ContinuousDecodeServer(_ll_cfg(), batch=8, max_len=32, mesh=_mesh8(),
                                 page_size=4, tracer=tr, series=ts)
    trace_dir = ARTIFACTS / "poisson_trace"
    with jax.profiler.trace(str(trace_dir), create_perfetto_trace=True):
        m = srv.serve_requests(_requests(arrivals, plens, max_new))
    srv.close()

    # ---- acceptance ----
    assert m.requests_completed == n_req, m.requests_completed
    assert m.pages_peak <= m.pages_dense_equiv, (m.pages_peak,
                                                 m.pages_dense_equiv)
    events = tr.events()
    names = [e[1] for e in events]
    assert names.count("admit") == n_req, names.count("admit")
    assert names.count("complete") == n_req, names.count("complete")
    assert "serve.step" in names and "serve.admit" in names
    trace_path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    series_path = ts.to_jsonl(ARTIFACTS / "poisson_series.jsonl")
    ttfts = [r["ttft_s"] for r in m.per_request]
    row = dict(scenario="poisson", n_req=n_req, rate_per_step=rate,
               steps=m.serve_steps, ttft_p50_ms=pct_ms(ttfts, 50),
               ttft_p95_ms=pct_ms(ttfts, 95),
               itl_p50_ms=round(m.itl_p50_s * 1e3, 2),
               itl_p95_ms=round(m.itl_p95_s * 1e3, 2),
               pages_peak=m.pages_peak,
               pages_ratio=round(m.pages_peak / m.pages_dense_equiv, 3),
               trace_events=len(events))
    return [row], dict(trace=str(trace_path), series=str(series_path))


# --------------------------------------------------------------------------
# bursty
# --------------------------------------------------------------------------

def scenario_bursty(n_bursts=2, burst=12, gap=10, max_new=6):
    arrivals = bursty_arrivals(n_bursts, burst, gap)
    n_req = len(arrivals)
    plens = np.full(n_req, 4)
    ts = TimeSeries()
    srv = ContinuousDecodeServer(_ll_cfg(), batch=8, max_len=32, mesh=_mesh8(),
                                 page_size=4, series=ts)
    m = srv.serve_requests(_requests(arrivals, plens, max_new))
    srv.close()

    steps = [r for r in ts.rows if r["kind"] == "step"]
    depths = [r["queue_depth"] for r in steps]
    # ---- acceptance: a backlog must form (burst > slot count) and drain ----
    assert m.requests_completed == n_req, m.requests_completed
    assert max(depths) >= burst - srv.batch, (max(depths), burst, srv.batch)
    assert depths[-1] == 0, depths[-10:]        # backlog fully drained
    row = dict(scenario="bursty", n_req=n_req, bursts=n_bursts,
               burst_size=burst, steps=m.serve_steps,
               max_queue_depth=int(max(depths)),
               ttft_p95_ms=round(m.ttft_p95_s * 1e3, 2),
               itl_p95_ms=round(m.itl_p95_s * 1e3, 2))
    return [row], {}


# --------------------------------------------------------------------------
# drifting skew
# --------------------------------------------------------------------------

def _set_hot_pair(srv, pair, bias=100.0):
    """Steer the router's expert SELECTION onto ``pair`` host-side via the
    aux-free selection bias (models/moe.py ``sel_bias``): the skew then flows
    through the real routed decode — dispatch, heat counters, placement —
    rather than a synthetic histogram. Gate weights stay unbiased."""
    sb = np.asarray(srv.params["moe_stack"]["moe"]["sel_bias"])
    new = np.zeros_like(sb)
    new[..., list(pair)] = bias
    srv.params["moe_stack"]["moe"]["sel_bias"] = jnp.asarray(new)


def scenario_drift(window=8, segments=4, drop_factor=0.8, spike_factor=1.25):
    """Zipf skew that drifts: segments 0-1 route hot onto experts {0,1},
    segments 2-3 onto {4,5}. One rebalance boundary per segment. The
    acceptance bar (in-bench): the imbalance ratio measured AFTER a
    rebalance must drop vs the window before it — both for the initial skew
    and again after the drift — and the drift itself must show up as a
    spike under the stale placement."""
    cfg = _ll_cfg(use_selection_bias=True)
    E = cfg.moe.num_experts
    tr, ts = Tracer(), TimeSeries()
    srv = DecodeServer(cfg, batch=8, max_len=64, mesh=_mesh8(),
                       rebalance_every=window, num_redundant_experts=E,
                       heat_decay=0.7, tracer=tr, series=ts)
    hot = [(0, 1), (0, 1), (4, 5), (4, 5)]
    _set_hot_pair(srv, hot[0])
    prompts = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (8, 6)), jnp.int32)
    tok, _ = srv.prefill(prompts)
    for seg in range(segments):
        if seg and hot[seg] != hot[seg - 1]:
            _set_hot_pair(srv, hot[seg])
        outs, _ = srv.decode(tok, window)
        tok = jnp.asarray(outs[:, -1:])
    srv.close()

    wrows = [r for r in ts.rows if r["kind"] == "rebalance"]
    assert len(wrows) == segments, [r["kind"] for r in ts.rows]
    imb = [r["imbalance"] for r in wrows]
    # ---- acceptance: rebalancing must EARN its keep under drift ----
    # window 1 ran under the post-rebalance placement for {0,1}: must drop
    assert imb[1] < imb[0] * drop_factor, (imb, "no drop after rebalance")
    # window 2 ran hot on {4,5} under the stale {0,1}-optimized table: spike
    assert imb[2] > imb[1] * spike_factor, (imb, "drift did not spike")
    # window 3 ran under the re-adapted table (heat decay forgetting {0,1})
    assert imb[3] < imb[2] * drop_factor, (imb, "no re-drop after drift")
    swaps = sum(1 for e in tr.events() if e[1] == "placement_swap")
    assert swaps >= 2, swaps            # adapt + re-adapt at minimum

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    series_path = ts.to_jsonl(ARTIFACTS / "drift_series.jsonl")
    rows = [dict(scenario="drift", segment=i, hot_experts=list(hot[i]),
                 imbalance=round(imb[i], 3),
                 window_tokens=wrows[i]["window_tokens"],
                 placements_adopted=wrows[i]["placements_adopted"])
            for i in range(segments)]
    return rows, dict(series=str(series_path))


# --------------------------------------------------------------------------
# context-length cliff
# --------------------------------------------------------------------------

def scenario_cliff(num_pages=12, page_size=4, max_new=8):
    """Sweep prompt length toward the page-pool cliff. Requests whose
    worst-case footprint fits the pool complete with a monotone page
    high-water; past the cliff, reservation-gated admission REJECTS up
    front (loud ValueError naming the pool), before any device step — and
    the raw allocator raises PagePoolExhausted at the exact page."""
    srv = ContinuousDecodeServer(_ll_cfg(), batch=8, max_len=64, mesh=_mesh8(),
                                 page_size=page_size, num_pages=num_pages)
    rows, last_peak = [], 0
    for L in (8, 16, 32, 44, 56):
        need = pages_for_tokens(L + max_new - 1, page_size)
        reqs = _requests([0], [L], max_new)
        if need <= num_pages:
            m = srv.serve_requests(reqs)
            assert m.requests_completed == 1, m.requests_completed
            peak = srv.reqsched.alloc.peak_live
            assert peak == need, (peak, need)       # lazy alloc, exact
            assert peak >= last_peak, (peak, last_peak)
            last_peak = peak
            rows.append(dict(scenario="cliff", prompt_len=L,
                             pages_needed=need, pool_pages=num_pages,
                             outcome="ok", pages_peak=peak))
        else:
            # ---- acceptance: the cliff is LOUD and happens up front ----
            try:
                srv.serve_requests(reqs)
            except ValueError as e:
                assert "pool has only" in str(e), e
                rows.append(dict(scenario="cliff", prompt_len=L,
                                 pages_needed=need, pool_pages=num_pages,
                                 outcome="rejected", pages_peak=None))
            else:
                raise AssertionError(
                    f"prompt_len={L} needs {need} pages > pool {num_pages} "
                    "but admission did not reject")
    srv.close()
    assert [r["outcome"] for r in rows] == ["ok", "ok", "ok",
                                            "rejected", "rejected"], rows

    # raw allocator: exhaustion raises at the exact page, never silently
    alloc = PageAllocator(4, page_size)
    alloc.alloc(4)
    try:
        alloc.alloc(1)
    except PagePoolExhausted:
        pass
    else:
        raise AssertionError("PageAllocator over-allocated past the pool")
    return rows, {}


# --------------------------------------------------------------------------
# concurrency ramp
# --------------------------------------------------------------------------

def scenario_ramp(n_req=16, max_new=6):
    """The same 16-request set at max concurrency 8 then 16 (mesh-divisible
    slot counts): more slots must never take more steps, and every
    request's token stream must be bitwise identical across levels."""
    rows, streams, steps_seen = [], None, None
    for B in (8, 16):
        srv = ContinuousDecodeServer(_ll_cfg(), batch=B, max_len=32,
                                     mesh=_mesh8(), page_size=4)
        m = srv.serve_requests(_requests(np.zeros(n_req, int),
                                         np.full(n_req, 5), max_new))
        got = {r: srv.reqsched.tokens_for(r).tolist() for r in range(n_req)}
        srv.close()
        assert m.requests_completed == n_req, m.requests_completed
        # ---- acceptance ----
        if streams is None:
            streams = got
        else:
            assert got == streams, "token streams changed with concurrency"
        if steps_seen is not None:
            assert m.serve_steps <= steps_seen, (m.serve_steps, steps_seen)
        steps_seen = m.serve_steps
        rows.append(dict(scenario="ramp", max_concurrency=B,
                         steps=m.serve_steps,
                         ttft_p95_ms=round(m.ttft_p95_s * 1e3, 2),
                         output_tok_s=round(m.output_tok_s, 1),
                         pages_peak=m.pages_peak, bitwise_parity=True))
    return rows, {}


# --------------------------------------------------------------------------

def main():
    sections, artifacts = {}, {}
    for name, fn in [("poisson", scenario_poisson),
                     ("bursty", scenario_bursty),
                     ("drift", scenario_drift),
                     ("cliff", scenario_cliff),
                     ("ramp", scenario_ramp)]:
        print(f"\n---- scenario: {name} ----", flush=True)
        rows, arts = fn()
        sections[name] = rows
        if arts:
            artifacts[name] = arts
        cols = list(rows[0].keys())
        table(rows, cols, f"scenario: {name}")
    print("\nacceptance bars (asserted above): all requests complete; "
          "paged <= dense; backlog forms AND drains; post-rebalance "
          "imbalance drops (incl. after drift); cliff rejects loudly "
          "before any step; bitwise parity across concurrency")
    if artifacts:
        print("artifacts:", json.dumps(artifacts, indent=1))
    write_result("scenarios", dict(
        config=dict(model="dbrx-132b smoke", ranks=8, ep_mode="ll",
                    page_size=4),
        **{k: dict(rows=v) for k, v in sections.items()},
        artifacts=artifacts))
    return sections


if __name__ == "__main__":
    main()

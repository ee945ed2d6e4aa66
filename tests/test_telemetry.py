"""Serving telemetry (docs/DESIGN.md §11): the tracer/time-series subsystem
is host-side and boundary-scoped — tracing ON must leave every token stream
BITWISE identical to tracing OFF (GQA and MLA continuous serve, including
across an EPLB placement swap and a kill/rejoin recovery), the disabled
tracer must be a true no-op outside a profiler session (shared span
singleton, zero events), every span must reach the profiler's trace with
its args (spans nest, every recovery transition has its span), and
``ServeMetrics.as_dict()`` must stay ``json.dumps``-able with the
``timeline``/``series`` fields carrying numpy scalars."""
import dataclasses
import json
import os

import numpy as np
import pytest

# CI seed matrix: the interpret-parity job re-runs this file under several
# seeds (REPRO_TEST_SEED) — data/routing vary, every invariant must hold
SEED = int(os.environ.get("REPRO_TEST_SEED", "0"))

from repro.configs import get_smoke
from repro.core import placement as PL
from repro.runtime.fault import FaultInjector
from repro.runtime.scheduler import Request
from repro.runtime.server import ContinuousDecodeServer, ServeMetrics
from repro.runtime.telemetry import (NULL_SERIES, NULL_TRACER, NullTracer,
                                     NullTimeSeries, TimeSeries, Tracer,
                                     json_safe)


class FakeClock:
    """Injectable monotonic clock: advances only when told to."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


def span_names(tr) -> list[str]:
    """Names of a tracer's spans, in the order they closed."""
    return [name for ph, name, *_ in tr.events() if ph == "X"]


def assert_spans_nest(tr) -> None:
    """Every span either contains or is disjoint from every other one:
    the host plane's stack of annotations is well-formed."""
    spans = sorted(((t, t + d, n) for ph, n, t, d, _ in tr.events()
                    if ph == "X"), key=lambda s: (s[0], -s[1]))
    stack: list[tuple] = []
    for t0, t1, name in spans:
        assert t1 >= t0, name
        while stack and t0 >= stack[-1][1]:
            stack.pop()
        assert not stack or t1 <= stack[-1][1], (name, stack[-1])
        stack.append((t0, t1, name))


def host_spans(trace_dir) -> list[tuple[str, int, int, dict]]:
    """(name, start ns, end ns, args) of the profiler trace's host
    annotations on the Python thread, in start order."""
    import glob

    import jax
    xplane = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats})
                           for e in line.events if not e.name.startswith("$"))
    return sorted(out, key=lambda s: s[1])


# --------------------------------------------------------------------------
# tracer unit tests (fake clock: timings are exact, not approximate)
# --------------------------------------------------------------------------

def test_tracer_fake_clock_deterministic():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", step=0):
        clk.tick(0.002)
        with tr.span("inner"):
            clk.tick(0.001)
        tr.instant("mark", rid=np.int64(5))
        tr.counter("queue_depth", 4)
        clk.tick(0.0005)
    assert len(tr) == 4
    by_name = {e[1]: e for e in tr.events()}
    # inner: opened at t=2ms for 1ms; outer: t=0 for 3.5ms — exact
    assert by_name["inner"][:4] == ("X", "inner", 0.002, 0.001)
    assert by_name["outer"][:4] == ("X", "outer", 0.0, 0.0035)
    assert by_name["outer"][4] == {"step": 0}
    assert by_name["mark"][0] == "i" and by_name["mark"][4] == {"rid": 5}
    assert by_name["queue_depth"][0] == "C"
    assert_spans_nest(tr)
    # summary folds span time per name
    s = tr.summary()
    assert s["outer"]["count"] == 1 and s["outer"]["total_s"] == 0.0035
    assert s["mark"]["ph"] == "i" and s["mark"]["total_s"] == 0.0
    assert span_names(tr) == ["inner", "outer"]


def test_tracer_spans_reach_the_profiler_with_args(tmp_path):
    """Every span also enters a TraceAnnotation: under a profiler session
    the spans land on the trace's host plane, nested as opened, carrying
    their args and those set while open."""
    import jax
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("serve.outer", step=3):
            with tr.span("serve.inner") as sp:
                sp.set_metadata(kv_tokens=np.int64(12), active=2)
    got = {n: (t0, t1, a) for n, t0, t1, a in host_spans(tmp_path)}
    assert got["serve.outer"][2] == {"step": 3}
    assert got["serve.inner"][2] == {"kv_tokens": 12, "active": 2}
    assert got["serve.outer"][0] <= got["serve.inner"][0]
    assert got["serve.inner"][1] <= got["serve.outer"][1]
    # the in-memory events keep the args too
    args = {e[1]: e[4] for e in tr.events()}
    assert args["serve.inner"] == {"kv_tokens": 12, "active": 2}


def test_span_survives_exception_and_still_validates():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with pytest.raises(RuntimeError):
        with tr.span("boundary"):
            clk.tick(0.001)
            raise RuntimeError("mid-boundary failure")
    assert tr.events() == [("X", "boundary", 0.0, 0.001, {})]
    assert_spans_nest(tr)


def test_null_tracer_and_series_are_noops():
    tr = NullTracer()
    assert not tr.enabled and not NULL_TRACER.enabled
    # the disabled tracer hands out ONE shared span object: no per-step
    # allocation on the serve hot path (no profiler session is active)
    s1, s2 = tr.span("serve.step", step=0), tr.span("serve.rebalance")
    assert s1 is s2
    with s1 as sp:
        sp.set_metadata(kv_tokens=1)
    tr.instant("x")
    tr.counter("y", 1.0)
    assert len(tr) == 0 and tr.summary() == {} and tr.events() == []
    ns = NullTimeSeries()
    ns.record(kind="step", itl_s=1.0)
    assert ns.rows == () and not ns.enabled and not NULL_SERIES.enabled


def test_null_tracer_annotates_only_under_a_profiler_session(tmp_path):
    """With no in-memory tracer the boundary spans still reach a profiler
    session, as bare annotations; outside one they are the shared no-op."""
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with NULL_TRACER.span("serve.admit", step=1) as sp:
            assert isinstance(sp, jax.profiler.TraceAnnotation)
            sp.set_metadata(active=4)
    assert NULL_TRACER.span("serve.admit") is NULL_TRACER.span("serve.poll")
    got = [(n, a) for n, _, _, a in host_spans(tmp_path)]
    assert ("serve.admit", {"step": 1, "active": 4}) in got
    assert len(NULL_TRACER) == 0


def test_serve_metrics_as_dict_json_serializable():
    """timeline/series land in as_dict() with numpy leaves coerced."""
    m = ServeMetrics(
        ttft_s=np.float64(0.1), itl_mean_s=0.01, itl_p99_s=0.02,
        output_tok_s=np.float32(123.0), total_tokens=np.int64(64),
        timeline={"serve.step": {"count": np.int64(8),
                                 "total_s": np.float64(0.08), "ph": "X"}},
        series=[{"kind": "step", "itl_s": np.float32(0.01),
                 "rank_loads": np.arange(4)}])
    d = m.as_dict()
    out = json.loads(json.dumps(d))
    assert out["timeline"]["serve.step"]["count"] == 8
    assert out["series"][0]["rank_loads"] == [0, 1, 2, 3]
    assert json_safe(np.bool_(True)) in (True, 1)


# --------------------------------------------------------------------------
# bitwise parity: tracing on vs off through the continuous engine
# --------------------------------------------------------------------------

def _requests():
    return [Request(0, np.array([3, 5, 7], np.int32), 6),
            Request(1, np.array([11, 2], np.int32), 8),
            Request(2, np.array([9, 9, 9, 9, 1], np.int32), 5,
                    arrival_step=4),
            Request(3, np.array([4], np.int32), 7, arrival_step=6)]


@pytest.mark.parametrize("arch", ["dbrx-132b", "minicpm3-4b"])
def test_continuous_tracing_on_off_bitwise(arch, tmp_path):
    """GQA (dbrx) and absorbed-MLA (minicpm3) continuous serve: turning the
    tracer + time series on must not move a single token — telemetry reads
    host state the boundaries already materialize."""
    cfg = get_smoke(arch)

    off = ContinuousDecodeServer(cfg, batch=3, max_len=32, page_size=4)
    m_off = off.serve_requests(_requests())
    base = {r.rid: off.reqsched.tokens_for(r.rid) for r in _requests()}
    off.close()
    assert m_off.timeline is None and m_off.series is None

    tr, se = Tracer(), TimeSeries()
    on = ContinuousDecodeServer(cfg, batch=3, max_len=32, page_size=4,
                                tracer=tr, series=se)
    m_on = on.serve_requests(_requests())
    got = {r.rid: on.reqsched.tokens_for(r.rid) for r in _requests()}
    on.close()

    for rid, toks in base.items():
        np.testing.assert_array_equal(toks, got[rid])
    assert m_on.requests_completed == m_off.requests_completed == 4
    assert m_on.serve_steps == m_off.serve_steps

    assert_spans_nest(tr)
    names = set(span_names(tr))
    assert {"serve.admit", "serve.step", "serve.readback",
            "serve.poll"} <= names
    inst = [e[1] for e in tr.events() if e[0] == "i"]
    assert inst.count("admit") == 4 and inst.count("complete") == 4
    for name in ("serve.admit", "serve.step", "serve.readback", "serve.poll"):
        assert m_on.timeline[name]["count"] == m_on.serve_steps
    # per-step series rows carry queue/slot/page occupancy
    steps = [r for r in m_on.series if r["kind"] == "step"]
    assert len(steps) == m_on.serve_steps
    assert all(r["pages_live"] >= 0 and r["queue_depth"] >= 0 for r in steps)
    assert max(r["pages_live"] for r in steps) <= m_on.pages_peak
    json.dumps(m_on.as_dict())


def test_serve_requests_spans_in_the_profiler_trace(tmp_path, bench_trace):
    """A few steps of the smoke continuous engine under a profiler session,
    no in-memory tracer: every step is serve.admit, serve.step,
    serve.readback, serve.poll on the trace's host plane (read by the
    benchmark's bench/trace.py), and serve.admit's counters are the fed
    rows': kv_tokens = sum(kv_lens + 1) over the active rows."""
    import jax
    srv = ContinuousDecodeServer(get_smoke("dbrx-132b"), batch=2, max_len=32,
                                 page_size=4)
    feeds, step = [], srv.step

    def recording_step(params, state, feed):
        feeds.append({k: np.asarray(v).copy() for k, v in feed.items()})
        return step(params, state, feed)

    srv.step = recording_step
    with jax.profiler.trace(str(tmp_path)):
        m = srv.serve_requests(_requests())
    srv.close()
    assert m.requests_completed == 4 and m.timeline is None

    boundary = ("serve.admit", "serve.step", "serve.readback", "serve.poll")
    host = bench_trace.load(str(tmp_path)).host
    names = [h.name for h in sorted(host, key=lambda h: h.start)
             if h.name in boundary]
    assert len(feeds) == m.serve_steps
    assert names == list(boundary) * m.serve_steps

    admits = [a for n, _, _, a in host_spans(tmp_path) if n == "serve.admit"]
    assert len(admits) == len(feeds)
    for a, f in zip(admits, feeds):
        act = f["active"] > 0
        assert a["kv_tokens"] == int((f["kv_lens"][act] + 1).sum())
        assert a["active"] == int(act.sum())
        assert a["admitted"] == int((act & (f["kv_lens"] == 0)).sum())
    assert sum(a["admitted"] for a in admits) == 4
    # two slots for four requests: the later arrivals wait for a slot
    assert sum(a["queued_steps"] for a in admits) > 0


def test_scheduler_counters_at_each_boundary():
    """The counters the server puts on serve.admit, by hand: live rows,
    KV tokens the step reads, rows inside their prompt, admissions and
    their queue wait in steps."""
    from repro.models.kv_pages import PageAllocator
    from repro.runtime.scheduler import ContinuousScheduler
    reqs = [Request(0, np.array([1, 2, 3], np.int32), 2),
            Request(1, np.array([4], np.int32), 3),
            Request(2, np.array([5, 6], np.int32), 1, arrival_step=1)]
    sched = ContinuousScheduler(reqs, 2, 8, PageAllocator(16, 4))
    want = [  # active, kv_tokens, prefill_rows, admitted, queued_steps
        (2, 1 + 1, 2, 2, 0),        # r0 pos 0, r1 pos 0
        (2, 2 + 2, 1, 0, 0),        # r0 pos 1, r1 pos 1 (decoding)
        (2, 3 + 3, 1, 0, 0),        # r0 pos 2 (last prompt), r1 pos 2
        (2, 4 + 1, 1, 1, 2),        # r0 pos 3; r2 admitted at 3, due at 1
        (1, 2, 1, 0, 0),            # r2 pos 1, its last prompt token
    ]
    for step, w in enumerate(want):
        sched.advance(step)
        c = sched.counters
        assert (c["active"], c["kv_tokens"], c["prefill_rows"], c["admitted"],
                c["queued_steps"]) == w, (step, c)
        sched.observe(np.full((2, 1), 7, np.int32))
    assert sched.done


# --------------------------------------------------------------------------
# parity + well-formedness across a placement swap AND a kill/rejoin
# --------------------------------------------------------------------------

def _cfg_physical(placement):
    cfg = get_smoke("dbrx-132b")
    moe = dataclasses.replace(cfg.moe, ep_mode="ll", ep_axis=("data",),
                              track_expert_heat=True, params_physical=True,
                              placement=placement)
    return dataclasses.replace(cfg, moe=moe)


def _mesh8():
    import jax
    return jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def test_traced_swap_and_kill_rejoin_bitwise_and_wellformed(tmp_path):
    """The acceptance scenario: continuous serve over the 8-rank mesh with
    EPLB swaps every 4 steps AND rank 2 killed then rejoined. Tracing on
    must stay bitwise-equal to tracing off, and the trace must contain the
    rebalance span plus BOTH recovery spans with phase timings."""
    E = 8
    cfg = _cfg_physical(PL.redundant_placement(E, 8, E))
    mesh = _mesh8()
    kw = dict(batch=8, max_len=32, page_size=4, num_redundant_experts=E,
              rebalance_every=4, miss_threshold=1)

    srv_a = ContinuousDecodeServer(cfg, mesh=mesh,
                                   fault_injector=FaultInjector(
                                       8, kill={3: 2}, rejoin={8: 2}), **kw)
    srv_a.serve_requests(_requests())
    base = {i: srv_a.reqsched.tokens_for(i) for i in range(4)}
    srv_a.close()

    tr, se = Tracer(), TimeSeries()
    srv_b = ContinuousDecodeServer(cfg, mesh=mesh,
                                   fault_injector=FaultInjector(
                                       8, kill={3: 2}, rejoin={8: 2}),
                                   tracer=tr, series=se, **kw)
    m = srv_b.serve_requests(_requests())
    sched = srv_b.reqsched
    srv_b.close()

    # (a) bitwise parity across swap + shrink + expand, telemetry on
    for i in range(4):
        np.testing.assert_array_equal(base[i], sched.tokens_for(i))
    assert [e["kind"] for e in srv_b.recoveries] == ["shrink", "expand"]
    assert m.recovery_count == 2

    # (b) trace well-formedness: spans nest, durations >= 0, every
    # recovery transition has exactly one span
    assert_spans_nest(tr)
    names = span_names(tr)
    assert names.count("serve.recover:shrink") == 1
    assert names.count("serve.recover:expand") == 1
    assert names.count("serve.rebalance") >= 1
    assert {"serve.poll", "serve.step", "serve.admit"} <= set(names)
    inst = [e[1] for e in tr.events() if e[0] == "i"]
    assert inst.count("fault_detected") == 2
    assert inst.count("placement_swap") >= 2    # shrink + expand at least
    # per-transition phase timings (detect lands as the fault_detected
    # instant; repack/adopt/restore are timed inside the recovery span)
    for e in srv_b.recoveries:
        assert e["phases"]["repack_s"] >= 0.0
        assert "adopt_s" in e["phases"] or "restore_s" in e["phases"]
    # top-level recovery spans carry the transition args (the nested
    # recover:repack / recover:adopt phase spans are unannotated timings)
    rec = [e[4] for e in tr.events()
           if e[1] in ("serve.recover:shrink", "serve.recover:expand")]
    assert len(rec) == 2
    assert all("step" in a and "died" in a for a in rec)

    # (c) windowed series rows from the boundaries the engine already syncs
    kinds = {r["kind"] for r in m.series}
    assert "rebalance" in kinds and {"recover:shrink", "recover:expand"} <= kinds
    for r in m.series:
        if r["kind"] != "step":
            assert r["imbalance"] >= 1.0 and len(r["rank_loads"]) == 8
    json.dumps(m.as_dict())


# --------------------------------------------------------------------------
# driver-level: run_rebalancing with telemetry
# --------------------------------------------------------------------------

def test_run_rebalancing_traced_host_skeleton():
    """The EPLB driver skeleton with a pure-host fn: rebalance spans at
    every advance boundary, series rows showing the adopted table improving
    the skewed window's imbalance, and zero telemetry overhead on the
    placement schedule itself (same placements as the untraced run)."""
    from repro.core import EpGroupConfig
    from repro.core.placement import run_rebalancing

    E, N = 8, 4
    heat = np.zeros(E)
    heat[:2] = 100.0                      # two hot experts
    base_cfg = EpGroupConfig(num_experts=E, max_tokens_per_rank=16, hidden=8,
                             top_k=2, mode="ll")

    def make(group):
        return lambda item: (item, heat)

    items = list(range(6))
    _, pls_off = run_rebalancing(base_cfg, make, items, advance_every=2,
                                 ep_size=N, num_redundant=2)
    clk = FakeClock()
    tr, se = Tracer(clock=clk), TimeSeries()
    _, pls_on = run_rebalancing(base_cfg, make, items, advance_every=2,
                                ep_size=N, num_redundant=2,
                                tracer=tr, series=se)
    assert [p.fingerprint() if p else None for p in pls_on] == \
           [p.fingerprint() if p else None for p in pls_off]
    assert_spans_nest(tr)
    # boundaries at items 1 and 3 (never after the last item)
    assert span_names(tr).count("rebalance") == 2
    rows = [r for r in se.rows if r["kind"] == "rebalance"]
    assert len(rows) == 2
    # the redundant rebalance spreads the two hot experts' replicas
    assert rows[0]["placement_changed"]
    assert rows[0]["imbalance_after"] <= rows[0]["imbalance"]
    assert all(r["window_tokens"] == 200.0 for r in rows)

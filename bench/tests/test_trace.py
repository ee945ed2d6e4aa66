"""The reduction from a profiler trace to the per-layer numbers, on a small
trace recorded on a TPU v5e (the dsv3-ep-ht cell, two chunks of HT round
trips) and on hand-made events."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

import trace as TR
import run as RUN
from peaks import TABLE

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def test_leaves_drop_containers_and_keep_names():
    ops = TR._leaves([_ev("%while.3 = (s32[]) while(...)", 0, 100),
                      _ev("%fusion.1 = f32[2] fusion(...)", 10, 20),
                      _ev("%recv_unpack.7 = bf16[1] custom-call(...)", 40, 30)])
    assert ops.names == ["fusion.1", "recv_unpack.7"]
    assert TR.busy_ns(ops) == 50
    assert TR.time_by_op(ops) == {"fusion": 20, "recv_unpack": 30}


def test_idle_gaps_are_named_by_the_benchmark_span_over_them():
    ops = TR._leaves([_ev("%a.1 = x", 0, 10), _ev("%b.2 = x", 30, 10),
                      _ev("%c.3 = x", 45, 5)])
    host = [TR.Span("bench.engine", 8, 32), TR.Span("$api.py try", 9, 31),
            TR.Span("bench.window", 0, 60)]
    gaps = TR.idle_gaps(ops, 0, 60, host)
    assert gaps == [("bench.engine", 20), ("bench.window", 10),
                    ("bench.window", 5)]


def test_scopes_from_hlo():
    hlo = ('  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, '
           'metadata={op_name="jit(f)/while/body/ep_dispatch/add" '
           'source_file="x.py"}\n  ROOT %copy.1 = f32[8]{0} copy(%fusion.4)\n')
    scopes = TR.scopes_from_hlo(hlo)
    assert scopes == {"fusion.4": "jit(f)/while/body/ep_dispatch/add"}
    assert TR.in_scope(scopes, "ep_dispatch")("fusion.4")
    assert not TR.in_scope(scopes, "ep_combine")("fusion.4")


@pytest.fixture(scope="module")
def recorded():
    tr = TR.load(str(DATA / "ep_ht.xplane.pb.gz"))
    scopes = json.loads((DATA / "ep_ht.scopes.json").read_text())
    return tr, scopes


def test_recorded_trace_summary(recorded):
    tr, scopes = recorded
    s = TR.summarize(tr, "bench.window", scopes=scopes,
                     scope_names=("ep_dispatch", "ep_combine"),
                     kernels=("dispatch_pack", "recv_unpack",
                              "combine_gather_reduce"))
    assert s["chips"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    scoped = s["scope_s"]["ep_dispatch"] + s["scope_s"]["ep_combine"]
    assert 0.5 * s["busy_s"] < scoped <= s["busy_s"] * 1.0001
    k = s["kernel_s"]
    assert k["combine_gather_reduce"] > k["recv_unpack"] > k["dispatch_pack"] > 0
    assert s["a2a_s"] == 0.0
    tops = [t for _, t in s["device_ops"]]
    assert tops == sorted(tops, reverse=True) and len(tops) == 10
    gaps = [g for _, g in s["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(n.startswith("bench.") or n == "none" for n, _ in s["idle_gaps"])


def test_readers_on_the_recorded_trace(recorded):
    tr, scopes = recorded
    s = TR.summarize(tr, "bench.window", scopes=scopes,
                     scope_names=("ep_dispatch", "ep_combine"),
                     kernels=("dispatch_pack", "recv_unpack"))
    T, N, K, H = 4096, 1, 8, 7168
    routing = [dict(distinct=np.asarray([T]), remote=np.asarray([0]),
                    received=np.asarray([T]), copies=np.asarray([T * K]),
                    tokens=T, ranks=N)]
    rts = int(json.loads((DATA / "ep_ht.counters.json").read_text())["round_trips"])
    ctx = dict(trace=s, peaks=TABLE["TPU v5e"],
               counters=dict(round_trips=rts, routing=routing, hidden=H, fp8=True))
    vals = {}
    for name in ("ep.dispatch_us", "ep.combine_us", "ep.idle_share",
                 "ep.step_mfu", "dispatch_pack_roofline",
                 "recv_unpack_roofline", "ep.a2a_us"):
        vals[name] = RUN.load_module(RUN.BENCH / "metrics" / f"{name}.py").read(ctx)
    assert vals["ep.a2a_us"] is None
    assert 0 <= vals["ep.idle_share"] < 100
    for k in ("ep.step_mfu", "dispatch_pack_roofline", "recv_unpack_roofline"):
        assert 0 < vals[k] <= 100
    per_rt = s["busy_s"] / rts * 1e6
    assert vals["ep.dispatch_us"] + vals["ep.combine_us"] <= per_rt * 1.0001


def test_readers_find_nothing_and_say_so():
    ctx = dict(trace=dict(busy_s=1.0, window_s=2.0, scope_s={}, kernel_s={},
                          a2a_s=0.0), peaks=TABLE["TPU v5e"], counters={})
    for name in ("ep.dispatch_us", "ep.step_mfu", "dispatch_pack_roofline",
                 "serve.step_device_ms", "serve.host_ms_per_step", "serve.mfu"):
        assert RUN.load_module(RUN.BENCH / "metrics" / f"{name}.py").read(ctx) is None
    assert RUN.load_module(RUN.BENCH / "metrics" / "serve.idle_share.py").read(ctx) == 50.0

"""The reduction of what the program names in a trace (bench/spans.py): on
the recorded TPU v5e trace of the dsv3-ep-ht cell (made before the program
named any scope or span), and on hand-made events."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

import run as RUN
import spans as SP
import trace as TR
from peaks import TABLE

DATA = Path(__file__).resolve().parent / "data"
NEW_READERS = ("serve.moe_device_ms", "serve.attn_device_ms",
               "serve.head_device_ms", "paged_decode_roofline",
               "serve.step_idle_ms", "serve.engine_idle_ms",
               "serve.queue_wait_steps", "serve.prefill_row_share",
               "ep.handle_us", "ep.dispatch_send_us", "ep.dispatch_recv_us",
               "ep.combine_send_us", "ep.combine_recv_us")


def _reader(name):
    return RUN.load_module(RUN.BENCH / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def recorded():
    tr = TR.load(str(DATA / "ep_ht.xplane.pb.gz"))
    scopes = json.loads((DATA / "ep_ht.scopes.json").read_text())
    return tr, scopes


def test_trace_summary_of_the_recorded_trace_is_unchanged(recorded):
    """bench/trace.py's numbers on the recorded trace, pinned."""
    tr, scopes = recorded
    s = TR.summarize(tr, "bench.window", scopes=scopes,
                     scope_names=("ep_dispatch", "ep_combine"),
                     kernels=("dispatch_pack", "recv_unpack",
                              "combine_gather_reduce"))
    assert s["window_s"] == 2.198630808 and s["chips"] == 1
    assert s["busy_s"] == 2.185717497 and s["a2a_s"] == 0.0
    assert s["scope_s"] == {"ep_dispatch": 0.644449496,
                            "ep_combine": 1.431214226}
    assert s["kernel_s"] == {"dispatch_pack": 0.23237451,
                             "recv_unpack": 0.387152065,
                             "combine_gather_reduce": 1.179313426}
    assert s["device_ops"][:4] == [["combine_gather_reduce", 1.179313426],
                                   ["recv_unpack", 0.387152065],
                                   ["dispatch_pack", 0.23237451],
                                   ["copy", 0.186890133]]
    assert s["idle_gaps"][:2] == [["bench.chunk", 0.002581105],
                                  ["bench.chunk", 0.002207029]]


def test_idle_by_span_sums_to_window_less_busy(recorded):
    tr, scopes = recorded
    s = TR.summarize(tr, "bench.window", scopes=scopes)
    p = SP.summarize(str(DATA / "ep_ht.xplane.pb.gz"), scopes)
    assert p["window_s"] == s["window_s"]
    assert sum(p["idle_by_span"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    assert set(p["idle_by_span"]) == {"bench.chunk", "bench.window"}
    # a program that named nothing: no scope time, no serve.* span
    assert set(p["scope_s"]) == set(SP.PROGRAM_SCOPES)
    assert not any(p["scope_s"].values()) and p["host"] == {}


def test_new_readers_find_nothing_in_the_old_trace(recorded, tmp_path,
                                                   monkeypatch):
    tr, scopes = recorded
    run_dir = tmp_path / "dsv3-ep-ht"
    run_dir.mkdir()
    with gzip.open(DATA / "ep_ht.xplane.pb.gz", "rb") as f:
        (run_dir / "ep_ht.xplane.pb").write_bytes(f.read())
    (run_dir / "scopes.json").write_text(json.dumps(scopes))
    monkeypatch.setattr(SP, "TRACES", tmp_path)
    s = TR.summarize(tr, "bench.window", scopes=scopes)
    ctx = dict(trace=s, peaks=TABLE["TPU v5e"],
               config=json.loads((RUN.BENCH / "configs" /
                                  "dbrx-132b-1l.json").read_text()),
               counters=dict(round_trips=8, steps=8))
    assert SP.for_run(ctx) is not None
    for name in NEW_READERS:
        assert _reader(name).read(ctx) is None, name
    # a trace whose window is not the reader's is not this run's
    ctx["trace"] = dict(s, window_s=s["window_s"] + 1.0)
    assert SP.for_run(ctx) is None
    monkeypatch.setattr(SP, "TRACES", tmp_path / "none")
    assert SP.for_run(ctx) is None


def _ops(*intervals):
    return TR.DeviceOps(names=[f"op.{i}" for i in range(len(intervals))],
                        start=np.asarray([s for s, _ in intervals], float),
                        end=np.asarray([e for _, e in intervals], float))


def test_idle_is_credited_to_the_innermost_span():
    """Idle pieces go to the span entered last over them; a span that
    outlives its parent (the harness's bench.engine) counts from its entry."""
    ops = _ops((0, 10), (30, 40), (45, 50))
    host = [SP.HostSpan("bench.window", 0, 60, {}),
            SP.HostSpan("serve.step", 5, 36, {}),
            SP.HostSpan("bench.serve_step", 6, 34, {}),
            SP.HostSpan("bench.engine", 34, 44, {}),
            SP.HostSpan("serve.poll", 41, 43, {}),
            SP.HostSpan("$python.frame", 12, 14, {})]
    idle = SP.idle_by_span(ops, 0, 60, host)
    assert idle == pytest.approx({"bench.serve_step": 20e-9,
                                  "bench.engine": 2e-9, "serve.poll": 2e-9,
                                  "bench.window": 11e-9})
    assert sum(idle.values()) == pytest.approx(
        (60 - TR.busy_ns(ops)) / 1e9)
    assert SP.idle_by_span(_ops(), 0, 10, []) == {"none": 10e-9}


def test_host_summary_sums_numeric_args_inside_the_window():
    host = [SP.HostSpan("serve.admit", 10, 20, dict(kv_tokens=5, active=2,
                                                    note="x")),
            SP.HostSpan("serve.admit", 30, 35, dict(kv_tokens=7, active=3)),
            SP.HostSpan("serve.admit", 95, 105, dict(kv_tokens=100)),
            SP.HostSpan("bench.engine", 10, 20, {})]
    out = SP.host_summary(host, 0, 100)
    assert out == {"serve.admit": dict(count=2, total_s=pytest.approx(15e-9),
                                       args=dict(kv_tokens=12, active=5))}


def test_readers_arithmetic(monkeypatch):
    summary = dict(
        window_s=3.0,
        scope_s=dict(dict.fromkeys(SP.PROGRAM_SCOPES, 0.0), moe=2.0,
                     attn=0.5, head=0.25, paged_decode=0.2,
                     **{"ep.dispatch_recv": 0.01}),
        host={"serve.step": dict(count=200, total_s=2.9, args={}),
              "serve.admit": dict(count=200, total_s=0.1,
                                  args=dict(kv_tokens=2_000_000,
                                            active=12_000, prefill_rows=3_000,
                                            admitted=20, queued_steps=50))},
        idle_by_span={"serve.step": 0.1, "bench.serve_step": 0.18,
                      "serve.admit": 0.04, "serve.readback": 0.06,
                      "serve.poll": 0.02, "bench.engine": 0.05})
    monkeypatch.setattr(SP, "for_run", lambda ctx: summary)
    conf = json.loads(
        (RUN.BENCH / "configs" / "dbrx-132b-1l.json").read_text())
    ctx = dict(trace={}, peaks=TABLE["TPU v5e"], config=conf,
               counters=dict(steps=200, round_trips=100))
    got = {n: _reader(n).read(ctx) for n in NEW_READERS}
    assert got["serve.moe_device_ms"] == pytest.approx(10.0)
    assert got["serve.attn_device_ms"] == pytest.approx(2.5)
    assert got["serve.head_device_ms"] == pytest.approx(1.25)
    assert got["serve.step_idle_ms"] == pytest.approx(1.4)
    assert got["serve.engine_idle_ms"] == pytest.approx(0.6)
    assert got["serve.queue_wait_steps"] == pytest.approx(2.5)
    assert got["serve.prefill_row_share"] == pytest.approx(25.0)
    assert got["ep.dispatch_recv_us"] == pytest.approx(100.0)
    assert got["ep.handle_us"] is None
    # 10,000 KV tokens per step x 2 x 8 heads x 128 x 2 B = 40.96 MB per
    # step at 819 GB/s = 50.0 us, against 1 ms of paged decode per step
    assert got["paged_decode_roofline"] == pytest.approx(5.001221, rel=1e-6)

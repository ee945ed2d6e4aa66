"""The traffic generator and the window accounting, on the host alone."""
import json

import numpy as np
import pytest

import traffic_gen as T
import window as W
from run import BENCH


@pytest.mark.parametrize("name", ["chat", "longgen"])
def test_rate_is_four_fifths_of_capacity(name):
    tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    cap = T.capacity_rate(tr)
    assert tr["rate"] == pytest.approx(tr["load"] * cap, rel=1e-3)


def test_capacity_from_distribution_means():
    tr = dict(slots=64, prompt_len=dict(dist="uniform", lo=16, hi=128),
              output_len=dict(dist="uniform", lo=10, hi=10))
    assert T.mean_length(tr["prompt_len"]) == pytest.approx(72.0, abs=1e-3)
    assert T.capacity_rate(tr) == pytest.approx(64 / (72 + 10 - 1), rel=1e-4)


def test_lognormal_quantiles_median_and_clip():
    spec = dict(dist="lognormal", median=256, sigma=0.8, lo=32, hi=1024)
    q = T.quantiles(spec, np.asarray([1e-9, 0.5, 1 - 1e-9]))
    assert q.tolist() == [32, 256, 1024]


def _traffic():
    return json.loads((BENCH / "traffic" / "chat.json").read_text())


def test_every_seed_gets_the_same_work_in_another_order():
    tr = dict(_traffic(), requests=256)
    a = T.schedule(tr, 1, 1000)
    b = T.schedule(tr, 2**33 + 7, 1000)
    blk = tr["block"]
    for k in ("prompt_len", "output_len"):
        for i in range(0, 256, blk):
            assert sorted(a[k][i:i + blk]) == sorted(b[k][i:i + blk])
        assert a[k].tolist() != b[k].tolist()
    gaps_a, gaps_b = np.diff(a["arrival"]), np.diff(b["arrival"])
    assert abs(gaps_a.sum() - gaps_b.sum()) <= blk


def test_same_seed_same_inputs():
    tr = dict(_traffic(), requests=64)
    a, b = T.schedule(tr, 123, 1000), T.schedule(tr, 123, 1000)
    assert a["arrival"].tolist() == b["arrival"].tolist()
    assert all((x == y).all() for x, y in zip(a["tokens"], b["tokens"]))
    assert [t.size for t in a["tokens"]] == a["prompt_len"].tolist()
    assert all(t.max() < 1000 for t in a["tokens"])


def test_window_accounting():
    # steps end every 10 ms from t=0.01; the window is steps 10..19
    end = 0.01 * (np.arange(40) + 1)
    reqs = [
        # due before the window: not counted for TTFT, its tokens count
        W.RequestTimes(5, end[[9, 10, 11]].tolist()),
        # due at step 12, first token at step 15: TTFT from end[11]
        W.RequestTimes(12, end[[15, 16, 30]].tolist()),
        # due at step 19, first token after the close: followed, counted
        W.RequestTimes(19, end[[25, 26]].tolist()),
        # due after the window: ignored
        W.RequestTimes(21, end[[22]].tolist()),
    ]
    s = W.window_stats(reqs, end, 0.0, 10, 19)
    assert s["window_s"] == pytest.approx(end[19] - end[9])
    assert s["steps"] == 10
    assert s["due"] == 2 and s["missing_first_token"] == 0
    assert sorted(s["ttft_s"].tolist()) == pytest.approx(
        [end[15] - end[11], end[25] - end[18]])
    # tokens inside [end[9], end[19]]: 9, 10, 11 of the first; 15, 16
    assert s["output_tokens"] == 5
    # gaps with both ends inside: 9->10, 10->11, 15->16
    assert sorted(s["itl_s"].tolist()) == pytest.approx([0.01, 0.01, 0.01])


def test_window_counts_a_due_request_with_no_token_as_missing():
    end = 0.01 * (np.arange(20) + 1)
    s = W.window_stats([W.RequestTimes(3, [])], end, 0.0, 2, 10)
    assert s["due"] == 1 and s["missing_first_token"] == 1


def test_percentiles_in_ms():
    assert W.pct_ms([0.001, 0.002, 0.003], 50) == pytest.approx(2.0)

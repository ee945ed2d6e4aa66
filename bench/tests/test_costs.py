"""FLOP and byte counts from shapes and routing counts."""
import numpy as np
import pytest

import costs
from peaks import TABLE, UnknownDevice, peaks_for

CFG = dict(d_model=8, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=2,
           n_experts=4, top_k=2, d_ff_expert=16, vocab=10)


def test_token_flops_by_hand():
    proj = 2 * 8 * 2 * (2 * 4 + 2 * 2)          # q, o and k, v projections
    attn = 2 * 2 * 4 * 2 * 5                    # scores and values, 5 keys
    moe = 2 * 8 * 4 + 2 * 3 * 2 * 8 * 16        # router, two experts
    head = 2 * 8 * 10
    assert costs.token_flops(CFG, 5) == 2 * (proj + attn + moe) + head


def test_tokens_flops_sums_tokens():
    assert costs.tokens_flops(CFG, [1, 5, 9]) == pytest.approx(
        sum(costs.token_flops(CFG, c) for c in (1, 5, 9)))


def test_payload_bytes():
    assert costs.payload_bytes(7168, fp8=True) == 7168 + 4 * 56
    assert costs.payload_bytes(7168, fp8=False) == 2 * 7168


def test_routing_counts_and_bytes_by_hand():
    # 2 ranks, 2 experts each; rank 0 holds experts 0-1, rank 1 experts 2-3
    idx = np.asarray([[[0, 1], [0, 2]],      # rank 0's tokens
                      [[3, 2], [1, 3]]])     # rank 1's tokens
    c = costs.routing_counts(idx, experts_per_rank=2)
    assert c["distinct"].tolist() == [3, 3]      # (t0: r0) (t1: r0, r1)
    assert c["remote"].tolist() == [1, 1]
    assert c["received"].tolist() == [3, 3]
    assert c["copies"].tolist() == [4, 4]
    b = costs.ep_bytes(c, hidden=128, fp8=True)
    pb = 128 + 4
    assert b["hbm"].tolist() == [2 * 128 * 2 + 3 * pb + 3 * pb + 3 * 128 * 2] * 2
    assert b["ici"].tolist() == [pb + 128 * 2] * 2
    assert b["dispatch_pack"].tolist() == [2 * 128 * 2 + 3 * pb] * 2
    assert b["recv_unpack"].tolist() == [3 * pb + 4 * 128 * 2] * 2


def test_least_time_takes_the_binding_bound_on_the_busiest_rank():
    p = TABLE["TPU v5e"]
    t = costs.least_time(np.asarray([p.hbm_bytes, 2 * p.hbm_bytes]),
                         np.asarray([0.0, 3 * p.ici_bytes]), p)
    assert t == pytest.approx(3.0)


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12

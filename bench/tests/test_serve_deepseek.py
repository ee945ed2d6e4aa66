"""The ``serve_deepseek`` driver, its cost functions and its readers, on the
CPU at smoke widths."""
import json

import pytest

import costs_mla
import smoke
import spans
import traffic_gen as T
from peaks import TABLE
from run import BENCH, load_module

DRV = smoke.driver("serve_deepseek")

DSV3_SERVE_SMOKE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 256,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "n_group": 2,
    "topk_group": 1, "n_shared_experts": 1, "routed_scaling_factor": 1.0,
    "norm_topk_prob": True, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "hidden_act": "silu",
    "deployment": {"router_experts": 8, "ranks": 2, "rank": 0},
    "program": {"preset": "deepseek_v3_671b", "smoke": True},
}
# smoke widths round more than the published ones (d 64): the limit sits
# between this size's program and control readings
SERVE_SMOKE = dict(smoke.SERVE_SMOKE, driver="serve_deepseek",
                   limit_logit_gap=0.1, route_margin=0.002)


@pytest.fixture(scope="module")
def dsv3_run():
    return DRV.run(smoke.cell(DSV3_SERVE_SMOKE, SERVE_SMOKE,
                              hooks={"control": True}))


def test_dsv3_sound_run_is_correct(dsv3_run):
    checks = {c["name"]: c for c in dsv3_run["checks"]}
    for c in checks.values():
        assert c["value"] <= c["limit"], c
    assert dsv3_run["info"]["checked_tokens"] > 20
    assert dsv3_run["info"]["held_experts"] == [0, 1, 2, 3]
    assert dsv3_run["e2e"]["output_tok_s"] > 0


def test_dsv3_control_is_not_correct(dsv3_run):
    assert (dsv3_run["control"]["logit_gap_max"]
            > SERVE_SMOKE["limit_logit_gap"])


def test_config_file_keeps_every_published_number():
    """The served configuration is the catalog's DeepSeek-V3 config.json
    with only the ``reduced`` keys changed, each stated under
    ``published``; the program agrees with every width."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "deepseek-v3-ep32-5l")
    conf = json.loads((BENCH.parent / entry["file"]).read_text())
    ep = json.loads((BENCH / "configs" / "deepseek-v3-ep.json").read_text())
    published = dict(ep, **ep["published"])
    assert set(conf["published"]) == set(entry["reduced"])
    for k, v in conf["published"].items():
        assert published[k] == v
    for k in ep:
        if k in ("published", "cut", "program", "reference", "payload",
                 "source") or k in entry["reduced"]:
            continue
        assert conf[k] == published[k], k
    assert DRV.held_experts(conf) == tuple(range(8))
    for key in ("deployment", "assumed", "departures"):
        assert conf[key]


def test_reason_traffic_is_four_fifths_of_capacity():
    tr = json.loads((BENCH / "traffic" / "reason-2k.json").read_text())
    assert tr["rate"] == pytest.approx(tr["load"] * T.capacity_rate(tr),
                                       rel=1e-3)


def test_costs_by_hand():
    conf = json.loads((BENCH / "configs" / "deepseek-v3-ep32-5l.json")
                      .read_text())
    assert costs_mla.decode_row_bytes(conf) == 1152
    assert costs_mla.decode_token_flops(conf) == 2 * 128 * (2 * 512 + 64)
    assert costs_mla.decode_bytes(conf, 10) == 10 * 1152 * 5
    assert costs_mla.expert_bytes(conf) == 3 * 7168 * 2048 * 2
    assert costs_mla.experts_flops(conf, 3) == 3 * 6 * 7168 * 2048
    d, H = 7168, 128
    proj = 2 * (d * 1536 + 1536 * H * 192 + d * 576 + H * 128 * 512
                + H * 512 * 128 + H * 128 * d)
    tok = (5 * proj + 6 * d * 18432 + 4 * (2 * d * 256 + 6 * d * 2048)
           + 2 * d * 129280)
    assert costs_mla.token_flops(conf) == tok
    assert costs_mla.step_flops(conf, [3, 5], 7) == (
        2 * tok + 8 * 278528 * 5 + 7 * 6 * d * 2048)
    pk = TABLE["TPU v5e"]
    assert costs_mla.least_s(819e9, 0.0, pk) == pytest.approx(1.0)
    assert costs_mla.least_s(0.0, 197e12, pk) == pytest.approx(1.0)


def test_readers_arithmetic(monkeypatch):
    conf = json.loads((BENCH / "configs" / "deepseek-v3-ep32-5l.json")
                      .read_text())
    pk = TABLE["TPU v5e"]
    host = {"serve.admit": dict(count=10, args=dict(kv_tokens=10 * 819e9
                                                    / 1152 / 5 / 1e3)),
            "serve.readback": dict(count=10, args=dict(
                experts_hit=10 * 819e9 / (3 * 7168 * 2048 * 2) / 1e3,
                local_rows=10.0))}
    summary = dict(scope_s={"paged_decode": 0.002}, host=host)
    monkeypatch.setattr(spans, "for_run", lambda ctx: summary)
    ctx = dict(trace=dict(scope_s={"moe.experts": 0.004}, window_s=1.0),
               counters=dict(steps=1), peaks=pk, config=conf)

    def read(name):
        mod = load_module(BENCH / "metrics" / f"{name}.py")
        monkeypatch.setattr(mod, "for_run", lambda ctx: summary,
                            raising=False)
        return mod.read(ctx)
    # 1 ms of bytes, and a little more of FLOPs (242 FLOP per byte, past
    # the v5e's ridge of 240), against 2 ms in the scope per step
    kv = host["serve.admit"]["args"]["kv_tokens"] / 10
    flops_s = kv * 2 * 128 * (2 * 512 + 64) * 5 / 197e12
    assert flops_s > 1e-3
    assert read("mla_decode_roofline") == pytest.approx(flops_s / 2e-3 * 100)
    assert read("serve.moe_experts_ms") == pytest.approx(4.0)
    assert read("moe_experts_roofline") == pytest.approx(25.0)
    del host["serve.readback"]
    assert read("moe_experts_roofline") is None
    ctx["trace"]["scope_s"] = {}
    assert read("serve.moe_experts_ms") is None

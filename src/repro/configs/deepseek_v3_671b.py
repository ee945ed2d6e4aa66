"""DeepSeek-V3-671B [arXiv:2412.19437]: 61L d_model=7168 128H MLA,
d_ff=18432 dense / 2048 per expert, vocab=129280, MoE: 1 shared + 256 routed
top-8, sigmoid gating, group-limited (8 groups, top-4), aux-loss-free bias,
first 3 layers dense, MTP. **The paper's primary workload family** — this is
the arch the NCCL EP evaluation models (256 experts, hidden 7168, top-8).

Rope: YaRN over the 64 rotary dims (factor 40 over 4096 original positions,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 1), so the softmax
scale is 192^-0.5 · (0.1·ln 40 + 1)²; RMSNorm eps 1e-6.

EP deployment per shape (mirrors §VI/VII):
  train/prefill: HT mode, wide EP over ("data","model") = 256 ranks, L=1,
                 hierarchical two-stage a2a (outer=data, inner=model);
  decode:        LL mode, EP over ("data",) = 16 ranks, L=16,
                 expert-TP over model, fp8 dispatch payloads.
"""
import dataclasses

from repro.models.config import (ArchConfig, AttnSpec, MLASpec, MoESpec,
                                 YarnScaling)

# the published rope_scaling (config.json "rope_scaling", type "yarn")
YARN = YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)


def full_config(shape=None):
    kind = "decode" if shape in ("decode_32k", "long_500k") else "train"
    if kind == "train":
        # Flat (single-stage) a2a beats the hierarchical two-stage on the
        # single-pod mesh: both EP axes are same-fabric ICI, so the 2x bytes
        # of the extra hop are never paid back (measured: memory 499->163s,
        # collective 183->88s — docs/EXPERIMENTS.md §Perf D3). Hierarchy remains
        # the right choice only when EP spans the genuinely slower pod axis.
        moe = MoESpec(
            num_experts=256, top_k=8, d_ff_expert=2048, shared_experts=1,
            first_k_dense=3, gating="sigmoid", n_groups=8, topk_groups=4,
            use_selection_bias=True, routed_scaling=2.5,
            ep_mode="ht", ep_axis=("data", "model"), ht_hierarchical=False,
            capacity_factor=1.25, expert_capacity_factor=1.25,
            quantize_dispatch=True,   # fp8 dispatch: -39% collective (§Perf D4)
        )
    else:
        moe = MoESpec(
            num_experts=256, top_k=8, d_ff_expert=2048, shared_experts=1,
            first_k_dense=3, gating="sigmoid", n_groups=8, topk_groups=4,
            use_selection_bias=True, routed_scaling=2.5,
            ep_mode="ll", ep_axis=("data",), ll_layout="nccl_ep",
            capacity_factor=None, expert_capacity_factor=2.0,
            quantize_dispatch=True,
        )
    micro = {"train_4k": 8, "prefill_32k": 1}.get(shape, 1)
    return ArchConfig(
        name="deepseek-v3-671b", family="lm", num_layers=61, d_model=7168,
        d_ff=18432, vocab=129280,
        attn=AttnSpec(n_heads=128, n_kv=128, head_dim=128, kind="mla"),
        mla=MLASpec(q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
                    qk_rope_dim=64, v_head_dim=128, rope_scaling=YARN),
        moe=moe, mtp=(kind == "train"), microbatch=micro, norm_eps=1e-6,
    )


def smoke_config():
    return ArchConfig(
        name="deepseek-v3-smoke", family="lm", num_layers=3, d_model=64,
        d_ff=128, vocab=256,
        attn=AttnSpec(n_heads=4, n_kv=4, head_dim=16, kind="mla"),
        mla=MLASpec(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                    qk_rope_dim=8, v_head_dim=16, rope_scaling=YARN),
        moe=MoESpec(num_experts=8, top_k=2, d_ff_expert=32, shared_experts=1,
                    first_k_dense=1, gating="sigmoid", n_groups=2,
                    topk_groups=1, use_selection_bias=True,
                    ep_mode="auto", ep_axis=("data",), capacity_factor=None),
        mtp=True, remat=False,
    )

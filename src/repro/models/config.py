"""Architecture configuration schema covering all ten assigned families."""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv: int
    head_dim: int
    kind: Literal["gqa", "mla"] = "gqa"
    rope_base: float = 10000.0
    rope_fraction: float = 1.0        # chatglm3 "2d RoPE" = 0.5 (half rotary)
    window: int | None = None         # sliding-window width (local layers)
    qk_norm: bool = False
    logit_softcap: float | None = None
    # KV tile width for chunked (online-softmax) prefill attention AND the
    # tiling contract with the paged decode path: a paged serving engine
    # requires kv_chunk % page_size == 0 so prefill chunking and decode
    # paging agree on boundaries. Ragged tails (S % kv_chunk != 0) are
    # handled by masked padding, not asserted away.
    kv_chunk: int = 1024
    # KV-split count for the two-stage paged decode attention kernel
    # (flash-decoding parallelism); clamped to the page-table width at call
    # sites so tiny configs stay valid.
    decode_kv_splits: int = 4


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling, as a DeepSeek-V2/V3 ``rope_scaling`` of type
    "yarn" states it (models/layers.py ``yarn_frequencies``, ``yarn_mscale``)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLASpec:
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    # rope scaling of the rotary key/query dims; None = plain rope
    rope_scaling: YarnScaling | None = None


@dataclasses.dataclass(frozen=True)
class MoESpec:
    num_experts: int
    top_k: int
    d_ff_expert: int
    shared_experts: int = 0
    first_k_dense: int = 0            # deepseek-v3: first 3 layers dense
    gating: Literal["softmax", "sigmoid"] = "softmax"
    n_groups: int = 1
    topk_groups: int = 1
    use_selection_bias: bool = False
    routed_scaling: float = 1.0
    norm_topk: bool = True
    aux_loss_weight: float = 1e-3
    # --- EP communication (the paper's knobs) ---
    ep_mode: Literal["ll", "ht", "baseline", "auto"] = "auto"
    ll_layout: Literal["nccl_ep", "deepep"] = "nccl_ep"
    ep_axis: tuple[str, ...] = ("model",)
    capacity_factor: float | None = 1.25
    expert_capacity_factor: float | None = 1.25
    ht_hierarchical: bool = False
    # hierarchical-HT chunk count: >1 streams the two a2a stages (prefill
    # pipelining, core/ht.py); must divide the per-EP-rank token count
    ht_num_chunks: int = 1
    quantize_dispatch: bool = False
    # --- EPLB (core/placement.py) ---
    # Explicit expert placement table (EpPlacement) with optional redundant
    # replicas; None = contiguous striping. In the default logical mode
    # expert weights stay stored in logical [E, ...] order — moe_block
    # rebinds them to physical slot order in-graph when a placement is set.
    placement: "object | None" = None
    # Adopt-once physical parameter mode (serving fast path): expert-stacked
    # weights (w_gate/w_up/w_down) are stored ALREADY in `placement`'s
    # physical [N*S, ...] slot order and moe_block skips the per-step
    # in-graph expansion entirely. The runtime rebinds params host-side at
    # placement-adoption boundaries (checkpoint.adopt_expert_params, buffers
    # donated). Keep False for training, where placements may swap mid-epoch
    # and checkpoints should stay placement-independent; with placement=None
    # the physical layout coincides with the logical one (docs/DESIGN.md §8).
    params_physical: bool = False
    # Fold per-logical-expert routed-token counts into the decode state
    # ("expert_heat") so serving reports load imbalance and the rebalance
    # hook (runtime/server.py) can re-place experts between steps. The
    # on-device counter is f32: the serving hook drains it to host float64
    # at every rebalance boundary, so exact counting holds for any window
    # below ~16M routed tokens per expert.
    track_expert_heat: bool = False
    # The logical experts this chip holds on the one-chip path: one rank's
    # slice of an EpPlacement (core/placement.rank_experts). The router
    # still routes over all num_experts; the layer computes only the held
    # experts' part of the result and the expert-stacked weights hold
    # len(held_experts) rows. None = every expert is held here.
    held_experts: tuple[int, ...] | None = None


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_state: int
    headdim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 128
    n_groups: int = 1


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Literal["lm", "gemma3", "hybrid", "ssm", "encdec", "vlm"]
    num_layers: int
    d_model: int
    d_ff: int
    vocab: int
    attn: AttnSpec | None = None
    mla: MLASpec | None = None
    moe: MoESpec | None = None
    ssm: SSMSpec | None = None
    # gemma3: (local, global) pattern + local window
    local_global: tuple[int, int] | None = None
    local_window: int = 1024
    # zamba2: one shared attention block applied every `shared_attn_period`
    shared_attn_period: int | None = None
    # encdec
    enc_layers: int = 0
    dec_layers: int = 0
    cross_attn: bool = False
    src_len: int = 4096               # encoder memory length (frontend stub)
    # vlm
    img_tokens: int = 0               # patch embeddings injected at the front
    act: Literal["swiglu", "geglu", "gelu"] = "swiglu"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    # multi-token prediction (deepseek-v3 MTP): extra depth-1 head
    mtp: bool = False
    # training-time knobs
    remat: bool = True
    microbatch: int = 1               # gradient-accumulation chunks

    def padded_vocab(self, multiple: int = 256) -> int:
        return ((self.vocab + multiple - 1) // multiple) * multiple

    def padded_heads(self, multiple: int = 16) -> int:
        n = self.attn.n_heads if self.attn else 0
        return ((n + multiple - 1) // multiple) * multiple

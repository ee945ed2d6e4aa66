"""Operations and bytes of a DeepSeek-V3 (MLA + held experts) decode step,
computed from the configuration file's widths and the step's counts alone
(contexts, routed rows on held experts, held experts hit): never from an
implementation's buffers, so a share of a peak reads the same work whatever
implements it. Pure Python/numpy. Keys are the configuration file's
(``bench/configs/deepseek-v3-ep32-5l.json``).
"""
from __future__ import annotations

import numpy as np

BF16 = 2


def _n(conf: dict) -> tuple[int, int]:
    """(dense layers, MoE layers)."""
    dense = conf["first_k_dense_replace"]
    return dense, conf["num_hidden_layers"] - dense


def decode_row_bytes(conf: dict) -> int:
    """Bytes of one cached token in one layer: the latent row c_kv and the
    rotary key, in bf16 (512 + 64 values at the published widths)."""
    return (conf["kv_lora_rank"] + conf["qk_rope_head_dim"]) * BF16


def decode_token_flops(conf: dict) -> int:
    """Absorbed-MLA decode FLOPs per cached token per layer: scores over
    [q_absorbed | q_rope] (r + rope wide) and values over c_kv (r wide),
    for every head: 2 · H · (2r + rope)."""
    return (2 * conf["num_attention_heads"]
            * (2 * conf["kv_lora_rank"] + conf["qk_rope_head_dim"]))


def decode_bytes(conf: dict, kv_tokens: float) -> float:
    """Least bytes paged decode reads for ``kv_tokens`` = Σ(kv_len + 1)
    over a step's live rows, in every layer."""
    return kv_tokens * decode_row_bytes(conf) * conf["num_hidden_layers"]


def decode_flops(conf: dict, kv_tokens: float) -> float:
    return kv_tokens * decode_token_flops(conf) * conf["num_hidden_layers"]


def expert_bytes(conf: dict) -> int:
    """Bytes of one routed expert's SwiGLU weights (gate, up, down)."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"] * BF16


def experts_bytes(conf: dict, experts_hit: float) -> float:
    """Least bytes of the held experts' FFNs: every held expert with a row
    reads its weights once (``experts_hit`` summed over the MoE layers)."""
    return experts_hit * expert_bytes(conf)


def experts_flops(conf: dict, local_rows: float) -> float:
    """FLOPs of the routed (token, expert) rows that land on held experts
    (``local_rows`` summed over the MoE layers): 3 matmuls of d × f."""
    return local_rows * 6 * conf["hidden_size"] * conf["moe_intermediate_size"]


def token_flops(conf: dict) -> float:
    """Model FLOPs of one token apart from its context and its routed rows:
    the MLA projections of every layer (W_qa, W_qb, W_kva, the absorbed
    W_uk and W_uv, W_o), the dense FFN of the dense layers, the router
    (over all routed experts of the deployment) and the shared expert of the
    MoE layers, and the LM head. Embedding lookup and norms are left out."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    ql, r = conf["q_lora_rank"], conf["kv_lora_rank"]
    nope, rope, v = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                     conf["v_head_dim"])
    dense, moe = _n(conf)
    proj = 2 * (d * ql + ql * H * (nope + rope) + d * (r + rope)
                + H * nope * r + H * r * v + H * v * d)
    ffn = 6 * d * conf["intermediate_size"]
    router = 2 * d * conf["deployment"]["router_experts"]
    shared = (6 * d * conf["moe_intermediate_size"]
              * conf["n_shared_experts"])
    return ((dense + moe) * proj + dense * ffn + moe * (router + shared)
            + 2 * d * conf["vocab_size"])


def step_flops(conf: dict, contexts, local_rows: float) -> float:
    """Model FLOPs of one engine step: each live row's token at its context
    (``contexts`` = kv_len + 1 per live row) and the step's routed rows on
    held experts."""
    c = np.asarray(contexts, np.float64)
    return (c.size * token_flops(conf) + decode_flops(conf, c.sum())
            + experts_flops(conf, local_rows))


def least_s(bytes_: float, flops: float, peaks) -> float:
    """Least time: the larger of bytes over the HBM peak and FLOPs over the
    bf16 peak."""
    return max(bytes_ / peaks.hbm_bytes, flops / peaks.bf16_flops)

"""Paged decode's share of its roofline (%): the KV bytes the step's paged
decode must read, over the HBM peak, over the device time in the
program's ``paged_decode`` scope per step. Each live row reads K and V of
its kv_len + 1 tokens (the ``kv_tokens`` counter of the ``serve.admit``
spans, averaged over the window's steps): per token
2 × kv heads × head size × bytes per value × layers."""
from spans import for_run

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def kv_bytes_per_token(conf: dict) -> int:
    """K and V bytes of one token over every layer of the configuration."""
    head_dim = conf["d_model"] // conf["n_heads"]
    return (2 * conf["attn_config"]["kv_n_heads"] * head_dim
            * BYTES[conf["torch_dtype"]] * conf["n_layers"])


def read(ctx):
    s = for_run(ctx)
    n = ctx["counters"].get("steps", 0)
    if s is None or not n:
        return None
    t = s["scope_s"].get("paged_decode", 0.0)
    admit = s["host"].get("serve.admit")
    if not t or admit is None or "kv_tokens" not in admit["args"]:
        return None
    per_step = admit["args"]["kv_tokens"] / admit["count"]
    least = (per_step * kv_bytes_per_token(ctx["config"])
             / ctx["peaks"].hbm_bytes)
    return least / (t / n) * 100.0

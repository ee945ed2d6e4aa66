"""Absorbed-MLA paged decode's share of its roofline (%): the least time of
the step's paged decode over the device time in the program's
``paged_decode`` scope per step. Each live row reads its kv_len + 1 latent
rows (c_kv and the rotary key) in every layer and scores and sums them for
every head (``costs_mla.decode_bytes``, ``decode_flops``); the least time
is the larger of bytes over the HBM peak and FLOPs over the bf16 peak.
Σ(kv_len + 1) per step is the ``kv_tokens`` counter of the ``serve.admit``
spans averaged over the window's steps."""
import costs_mla
from spans import for_run


def read(ctx):
    s = for_run(ctx)
    n = ctx["counters"].get("steps", 0)
    if s is None or not n:
        return None
    t = s["scope_s"].get("paged_decode", 0.0)
    admit = s["host"].get("serve.admit")
    if not t or admit is None or "kv_tokens" not in admit["args"]:
        return None
    conf = ctx["config"]
    kv = admit["args"]["kv_tokens"] / admit["count"]
    least = costs_mla.least_s(costs_mla.decode_bytes(conf, kv),
                              costs_mla.decode_flops(conf, kv), ctx["peaks"])
    return least / (t / n) * 100.0

"""Whole-step model FLOP utilisation (%): the model FLOPs of every token the
engine processed in the traced window (prompt and output tokens; routed
top-k experts only, attention projections and scores, the LM head; see
costs.token_flops) per second of the window, over the chip's bf16 peak."""


def read(ctx):
    f = ctx["counters"].get("flops", 0.0)
    if not f:
        return None
    return f / ctx["trace"]["window_s"] / ctx["peaks"].bf16_flops * 100.0

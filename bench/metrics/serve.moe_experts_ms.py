"""Device time in the program's ``moe.experts`` scope (the held experts'
SwiGLU FFNs and their weighted sum, inside ``moe``) per engine step (ms)."""


def read(ctx):
    t = ctx["trace"]["scope_s"].get("moe.experts", 0.0)
    n = ctx["counters"].get("steps", 0)
    return t / n * 1e3 if t and n else None

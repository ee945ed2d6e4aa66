"""Device time in the program's ``attn`` scope (ln1, projections, paged
attention, residual) per engine step (ms)."""
from spans import scope_per


def read(ctx):
    return scope_per(ctx, "attn", "steps", 1e3)

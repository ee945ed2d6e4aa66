"""Device time in the program's ``moe`` scope (ln2, ``moe_block``, residual)
per engine step (ms)."""
from spans import scope_per


def read(ctx):
    return scope_per(ctx, "moe", "steps", 1e3)

"""Device time in the program's ``head`` scope (final norm, LM head,
greedy argmax) per engine step (ms)."""
from spans import scope_per


def read(ctx):
    return scope_per(ctx, "head", "steps", 1e3)

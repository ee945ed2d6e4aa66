"""Device idle time per engine step (ms) while the host was inside the
engine's ``serve.step`` span (the compiled step's call and its wait),
including the harness's ``bench.serve_step`` nested in it."""
from spans import idle_per_step_ms


def read(ctx):
    return idle_per_step_ms(ctx, ("serve.step", "bench.serve_step"),
                            required="serve.step")

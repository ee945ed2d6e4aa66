"""Device idle time per engine step (ms) while the host was inside the
engine's own boundary work: ``serve.admit`` (admission, feed build),
``serve.readback`` (token readback, scheduler observe) and ``serve.poll``
(fault poll, rebalance check)."""
from spans import idle_per_step_ms


def read(ctx):
    return idle_per_step_ms(ctx, ("serve.admit", "serve.readback",
                                  "serve.poll"), required="serve.admit")

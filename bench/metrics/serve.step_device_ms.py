"""Device busy time per engine step (ms): the union of the device's
operation intervals in the traced window over the steps run in it."""
from metrics._lib import per


def read(ctx):
    return per(ctx, ctx["trace"]["busy_s"], "steps", 1e3)

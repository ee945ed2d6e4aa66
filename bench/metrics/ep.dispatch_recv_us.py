"""Device time in the EP API's ``ep.dispatch_recv`` scope per round trip (us),
averaged over the chips."""
from spans import scope_per


def read(ctx):
    return scope_per(ctx, "ep.dispatch_recv", "round_trips", 1e6)

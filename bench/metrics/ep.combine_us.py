"""Device time inside the harness's ``ep_combine`` scope per round trip
(us), averaged over the chips."""
from metrics._lib import per


def read(ctx):
    return per(ctx, ctx["trace"]["scope_s"].get("ep_combine", 0.0),
               "round_trips", 1e6)

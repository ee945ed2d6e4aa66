"""Device time of all-to-all operations per round trip (us), averaged over
the chips. None where no all-to-all ran (one chip)."""
from metrics._lib import per


def read(ctx):
    t = ctx["trace"]["a2a_s"]
    return per(ctx, t, "round_trips", 1e6) if t else None

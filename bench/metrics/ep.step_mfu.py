"""The whole round trip's share of the chip's peak (%): its least time over
its measured device time per round trip. The round trip needs bytes, not
FLOPs, so the least time is the larger of its least HBM bytes over the HBM
peak and its off-chip bytes over the ICI peak, on the busiest rank
(costs.ep_bytes)."""
import numpy as np

import costs
from metrics._lib import ep_bytes, per


def read(ctx):
    b = ep_bytes(ctx)
    t = per(ctx, ctx["trace"]["busy_s"], "round_trips", 1.0)
    if b is None or not t:
        return None
    return costs.least_time(b["hbm"], b["ici"], ctx["peaks"]) / t * 100.0

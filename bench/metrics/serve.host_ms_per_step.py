"""Host time per engine step (ms): from one step's return to the next
step's call (scheduler, readback, admission), from the harness's wrapper of
the step, over the traced window."""
import numpy as np


def read(ctx):
    gaps = ctx["counters"].get("host_gaps_s") or []
    return float(np.mean(gaps)) * 1e3 if gaps else None

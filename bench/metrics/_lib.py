"""Shared arithmetic of the per-layer readers (this file names no metric).
Each reader is ``read(ctx) -> float | None``: ``ctx`` holds the trace
summary of the traced window (bench/trace.py ``summarize``), the driver's
counters over that window, the peaks, the configuration and the traffic.
A reader that finds nothing to read returns None."""
from __future__ import annotations

import numpy as np

import costs


def per(ctx, seconds: float, key: str, scale: float):
    """``seconds`` of device time per unit of the counter ``key``."""
    n = ctx["counters"].get(key, 0)
    return seconds / n * scale if n else None


def idle_share(ctx):
    tr = ctx["trace"]
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0


def ep_bytes(ctx) -> dict | None:
    """Mean over the checked round trips of each rank's least bytes."""
    c = ctx["counters"]
    if not c.get("routing"):
        return None
    per_rt = [costs.ep_bytes(r, c["hidden"], c["fp8"]) for r in c["routing"]]
    return {k: np.mean([b[k] for b in per_rt], axis=0) for k in per_rt[0]}


def kernel_roofline(ctx, kernel: str):
    """Required bytes of ``kernel``'s calls over the HBM peak, over their
    summed device time, per round trip, in %."""
    b = ep_bytes(ctx)
    t = ctx["trace"]["kernel_s"].get(kernel, 0.0)
    n = ctx["counters"].get("round_trips", 0)
    if b is None or not t or not n:
        return None
    least = float(np.mean(b[kernel])) / ctx["peaks"].hbm_bytes
    return least / (t / n) * 100.0

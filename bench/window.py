"""Window accounting for served requests: which requests, tokens and gaps
count, and the percentile arithmetic. Pure numpy, no JAX.

Times are host-clock seconds. ``step_end[i]`` is when step i's tokens were
back on the host; step i starts at ``step_end[i-1]`` (the boundary where the
scheduler admitted it), so a request due at step a waits from
``step_end[a-1]``. The window is [open, close], both step boundaries.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def pct_ms(a, q) -> float:
    """q-th percentile of a seconds array, in ms."""
    return float(np.percentile(np.asarray(a, np.float64), q)) * 1e3


@dataclasses.dataclass
class RequestTimes:
    arrival_step: int
    token_times: list            # host time of each generated token


def step_start(step_end: np.ndarray, step: int, t_begin: float) -> float:
    """Host time at which ``step`` began: the end of the step before it."""
    return t_begin if step == 0 else float(step_end[step - 1])


def window_stats(requests: list[RequestTimes], step_end: np.ndarray,
                 t_begin: float, open_step: int, close_step: int) -> dict:
    """Counts and latencies of one window.

    The window opens at the start of ``open_step`` and closes at the end of
    ``close_step``. A request is due in the window when its arrival step
    lies in [open_step, close_step]; its TTFT runs from the start of that
    step to its first token, however late that comes (the caller follows it
    past the close). Tokens count when emitted inside the window, and an
    inter-token gap counts when both of its ends lie inside it."""
    t_open = step_start(step_end, open_step, t_begin)
    t_close = float(step_end[close_step])
    ttft, gaps, out_tokens, due, missing = [], [], 0, 0, 0
    for r in requests:
        ts = np.asarray(r.token_times, np.float64)
        inside = (ts >= t_open) & (ts <= t_close)
        out_tokens += int(inside.sum())
        if ts.size > 1:
            a, b = ts[:-1], ts[1:]
            keep = (a >= t_open) & (b <= t_close)
            gaps.extend((b - a)[keep].tolist())
        if open_step <= r.arrival_step <= close_step:
            due += 1
            if ts.size:
                ttft.append(ts[0] - step_start(step_end, r.arrival_step,
                                               t_begin))
            else:
                missing += 1
    return dict(window_s=t_close - t_open, t_open=t_open, t_close=t_close,
                steps=close_step - open_step + 1, output_tokens=out_tokens,
                due=due, missing_first_token=missing,
                ttft_s=np.asarray(ttft), itl_s=np.asarray(gaps))

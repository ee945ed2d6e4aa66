"""What the program names in a profiler trace: its host spans (``serve.*``,
with their args, beside the harness's ``bench.*``), device idle time
credited to the host span over it, and device time in the program's named
scopes (``PROGRAM_SCOPES``).

``bench/trace.py`` reduces a trace to the summary every reader gets; that
summary keeps no span args and no program scope. This module reduces the
same trace for the readers of what the program itself names:

- ``scope_s``: {scope: device seconds in it}, for each of
  ``PROGRAM_SCOPES``, averaged over the chips (as ``trace.summarize``).
- ``host``: {span name: dict(count, total_s, args)} for each ``serve.*``
  span that lies inside the window; ``args`` sums its numeric args.
- ``idle_by_span``: {label: seconds}. Every idle interval of the window is
  cut at the boundaries of the ``bench.*`` and ``serve.*`` spans, and each
  piece is credited to the innermost span over it (the one entered last),
  else ``none``. Averaged over the chips, the pieces sum to window − busy.

``bench/run.py`` writes the trace of a ``--trace 1`` run under
``bench/_out/trace/<cell>/``, and the compiled program's scopes
(``scopes.json``) beside it just before the readers run; ``for_run`` takes
the newest such directory, and refuses it unless its window is the one in
the reader's summary. A program that names none of these gives empty
entries, and each reader then returns None.
"""
from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import math
import os
import re
from pathlib import Path

import numpy as np

import trace as TR
from device import WINDOW_SPAN

TRACES = Path(__file__).resolve().parent / "_out" / "trace"

# the program's named scopes (models/transformer.py, models/attention.py,
# runtime/steps.py, core/api.py, core/backend.py)
PROGRAM_SCOPES = ("attn", "paged_decode", "moe", "head", "ep.handle",
                  "ep.dispatch_send", "ep.dispatch_recv",
                  "ep.combine_send", "ep.combine_recv")
HOST_PREFIXES = ("bench.", "serve.")


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float          # ns
    end: float
    args: dict


def _profile(path: str):
    import jax
    if os.path.isdir(path):
        path = TR.find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def load(path: str) -> tuple[list, list[HostSpan]]:
    """([DeviceOps] one per chip, the ``bench.*``/``serve.*`` host spans
    with their args) of an ``.xplane.pb`` (``.gz``, or a directory holding
    one), read as ``trace.load`` reads it."""
    devices, host = [], []
    for plane in _profile(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append((int(plane.name.rsplit(":", 1)[1]),
                                    TR._leaves(line.events)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append(HostSpan(e.name, e.start_ns,
                                             e.start_ns + e.duration_ns,
                                             dict(e.stats)))
    devices.sort(key=lambda t: t[0])
    return [d for _, d in devices], host


def host_summary(host: list[HostSpan], t0: float, t1: float) -> dict:
    """Count, seconds and summed numeric args of each ``serve.*`` span
    inside [t0, t1] (ns)."""
    out: dict[str, dict] = {}
    for h in host:
        if not h.name.startswith("serve.") or h.start < t0 or h.end > t1:
            continue
        row = out.setdefault(h.name, dict(count=0, total_s=0.0, args={}))
        row["count"] += 1
        row["total_s"] += (h.end - h.start) / 1e9
        for k, v in h.args.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["args"][k] = row["args"].get(k, 0) + v
    return out


def _busy_before(starts: np.ndarray, ends: np.ndarray, t: np.ndarray):
    """Busy ns before each time in ``t``, over merged intervals."""
    if not starts.size:
        return np.zeros_like(t)
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])
    i = np.searchsorted(starts, t, side="right")       # intervals begun
    j = np.maximum(i - 1, 0)
    part = np.clip(t - starts[j], 0.0, ends[j] - starts[j])
    return np.where(i > 0, cum[j] + part, 0.0)


def idle_by_span(ops, t0: float, t1: float, host: list[HostSpan]) -> dict:
    """Idle seconds of one chip in [t0, t1] (ns), by the innermost
    ``bench.*``/``serve.*`` span over each piece (module docstring)."""
    spans = sorted((h for h in host if h.name.startswith(HOST_PREFIXES)
                    and h.end > t0 and h.start < t1),
                   key=lambda h: h.start)
    cuts = {t0, t1}
    for h in spans:
        cuts.update(min(max(t, t0), t1) for t in (h.start, h.end))
    cuts = np.asarray(sorted(cuts))
    merged = TR.union(ops.start, ops.end)
    bs = np.asarray([s for s, _ in merged], np.float64)
    be = np.asarray([e for _, e in merged], np.float64)
    idle = np.diff(cuts) - np.diff(_busy_before(bs, be, cuts))
    out: dict[str, float] = {}
    active, nxt = [], 0
    for a, gap in zip(cuts[:-1].tolist(), idle.tolist()):
        while nxt < len(spans) and spans[nxt].start <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [h for h in active if h.end > a]
        if gap <= 0:
            continue
        inner = (max(active, key=lambda h: (h.start, -h.end)).name
                 if active else "none")
        out[inner] = out.get(inner, 0.0) + gap / 1e9
    return out


def summarize(path: str, scopes: dict[str, str],
              window: str = WINDOW_SPAN) -> dict:
    """``window_s``, ``scope_s``, ``host`` and ``idle_by_span`` of the trace
    at ``path`` over the host span ``window`` (module docstring)."""
    devices, host = load(path)
    t0, t1 = TR.window_of(TR.Trace(devices=devices, host=host), window)
    per = [TR.clip(d, t0, t1) for d in devices]
    if not per:
        raise ValueError("the trace holds no TPU device plane")

    def mean(f):
        return float(np.mean([f(d) for d in per])) / 1e9

    idle = [idle_by_span(d, t0, t1, host) for d in per]
    labels = sorted(set().union(*idle))
    return dict(
        window_s=(t1 - t0) / 1e9,
        scope_s={s: mean(lambda d, s=s: TR.busy_ns(d, TR.in_scope(scopes, s)))
                 for s in PROGRAM_SCOPES},
        host=host_summary(host, t0, t1),
        idle_by_span={k: float(np.mean([i.get(k, 0.0) for i in idle]))
                      for k in labels},
    )


@functools.lru_cache(maxsize=1)
def _summarize_dir(path: str, mtime: float) -> dict:
    scopes = json.loads(Path(path, "scopes.json").read_text())
    return summarize(path, scopes)


def for_run(ctx) -> dict | None:
    """This run's summary (module docstring), or None where there is no
    trace whose window matches the reader's summary."""
    found = list(TRACES.glob("*/scopes.json"))
    if not found:
        return None
    newest = max(found, key=lambda p: p.stat().st_mtime)
    s = _summarize_dir(str(newest.parent), newest.stat().st_mtime)
    if not math.isclose(s["window_s"], ctx["trace"]["window_s"],
                        rel_tol=1e-9):
        return None
    return s


def scope_per(ctx, scope: str, key: str, scale: float):
    """Device seconds in the program scope ``scope`` per unit of the
    driver's counter ``key``, times ``scale``; None where the scope or the
    counter is missing."""
    s = for_run(ctx)
    t = s["scope_s"].get(scope, 0.0) if s is not None else 0.0
    n = ctx["counters"].get(key, 0)
    return t / n * scale if t and n else None


def idle_per_step_ms(ctx, spans: tuple[str, ...], required: str):
    """Idle ms per step credited to ``spans``; None unless the program's
    span ``required`` is in the trace."""
    s = for_run(ctx)
    n = ctx["counters"].get("steps", 0)
    if s is None or required not in s["host"] or not n:
        return None
    return sum(s["idle_by_span"].get(k, 0.0) for k in spans) / n * 1e3


def admit_ratio(ctx, num: str, den: str):
    """Sum of ``serve.admit``'s arg ``num`` over the window by that of
    ``den``; None where either is missing or the denominator is 0."""
    s = for_run(ctx)
    args = (s["host"].get("serve.admit", {}).get("args", {})
            if s is not None else {})
    if num not in args or not args.get(den):
        return None
    return args[num] / args[den]

"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, time by operation and by named scope,
collective time, and the longest idle gaps labelled by what the host was
doing. Only JAX is needed (``jax.profiler.ProfileData``).

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` has one event per executed HLO instruction, named by the
instruction's text (``%recv_unpack.7 = bf16[...] custom-call(...)``), and a
host plane (``/host:CPU``) whose Python thread's line holds the benchmark's
own ``jax.profiler.TraceAnnotation`` spans. Control-flow instructions (a
``while`` around a loop body) are events that contain other events; they are
left out, so busy time is the union of the leaf operations. Named scopes do
not appear in the trace itself; they come from the ``op_name`` metadata of
the compiled program's HLO text, matched by instruction name.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re

import numpy as np

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s*=")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_SUFFIX = re.compile(r"(\.\d+)+$")


def instr_name(event_name: str) -> str:
    """``%recv_unpack.7 = bf16[...] ...`` -> ``recv_unpack.7``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0].lstrip("%")


def base_name(instr: str) -> str:
    """``recv_unpack.7`` -> ``recv_unpack``; ``fusion.12`` -> ``fusion``."""
    return _SUFFIX.sub("", instr)


def scopes_from_hlo(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name metadata} of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        s = _OPNAME.search(line)
        if s:
            out[m.group(1)] = s.group(1)
    return out


@dataclasses.dataclass
class DeviceOps:
    """Leaf operations of one chip, as arrays sorted by start (ns)."""
    names: list           # instruction names
    start: np.ndarray
    end: np.ndarray


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    devices: list                  # [DeviceOps] one per chip
    host: list                     # [Span] the benchmark's host annotations


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _leaves(events) -> DeviceOps:
    ev = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                 for e in events), key=lambda t: (t[0], -t[1]))
    keep = []
    for i, (s, e, n) in enumerate(ev):
        # a container (while/conditional) encloses the events after it
        if i + 1 < len(ev) and ev[i + 1][0] < e and ev[i + 1][1] <= e:
            continue
        keep.append((s, e, instr_name(n)))
    return DeviceOps(names=[k[2] for k in keep],
                     start=np.asarray([k[0] for k in keep], np.float64),
                     end=np.asarray([k[1] for k in keep], np.float64))


def load(path: str) -> Trace:
    """Parse an ``.xplane.pb`` (gzipped when it ends in ``.gz``, or the
    directory holding one). Host spans come from the Python thread's line
    (named after the interpreter: ``python``, ``python3``)."""
    import jax
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append((int(plane.name.rsplit(":", 1)[1]),
                                    _leaves(line.events)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend(Span(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                for e in line.events)
    devices.sort(key=lambda t: t[0])
    return Trace(devices=[d for _, d in devices], host=host)


def clip(ops: DeviceOps, t0: float, t1: float) -> DeviceOps:
    """Operations that overlap [t0, t1], cut to it."""
    m = (ops.end > t0) & (ops.start < t1)
    return DeviceOps([n for n, k in zip(ops.names, m) if k],
                     np.maximum(ops.start[m], t0), np.minimum(ops.end[m], t1))


def union(start: np.ndarray, end: np.ndarray) -> list[tuple[float, float]]:
    """Merged intervals of [start, end) pairs."""
    out = []
    for s, e in sorted(zip(start.tolist(), end.tolist())):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: DeviceOps, select=None) -> float:
    """Length of the union of the (selected) operations' intervals."""
    idx = range(len(ops.names)) if select is None else [
        i for i, n in enumerate(ops.names) if select(n)]
    idx = list(idx)
    if not idx:
        return 0.0
    return float(sum(e - s for s, e in union(ops.start[idx], ops.end[idx])))


def time_by_op(ops: DeviceOps) -> dict[str, float]:
    """Summed device ns by base instruction name."""
    out: dict[str, float] = collections.Counter()
    for n, s, e in zip(ops.names, ops.start, ops.end):
        out[base_name(n)] += e - s
    return dict(out)


def in_scope(scopes: dict[str, str], scope: str):
    """Selector: instructions whose op_name lies under ``scope``."""
    part = f"/{scope}/"
    return lambda n: part in f"/{scopes.get(n, '')}/"


def idle_gaps(ops: DeviceOps, t0: float, t1: float,
              host: list[Span]) -> list[tuple[str, float]]:
    """Device idle gaps in [t0, t1] (ns), longest first, each named by the
    innermost of the benchmark's host annotations (``bench.*``) that covers
    its midpoint ("none" if none). The host and device clocks of a trace
    agree to about a millisecond."""
    host = [h for h in host if h.name.startswith("bench.")]
    busy = union(ops.start, ops.end)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [h for h in host if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.end - h.start).name if cover else "none"
        out.append((name, e - s))
    out.sort(key=lambda t: -t[1])
    return out


def window_of(trace: Trace, name: str) -> tuple[float, float]:
    """[start, end] (ns) of the host annotation ``name`` (the traced
    window), which must occur once."""
    spans = [h for h in trace.host if h.name == name]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span in the trace, found "
                         f"{len(spans)}")
    return spans[0].start, spans[0].end


def summarize(trace: Trace, window: str, scopes: dict[str, str] | None = None,
              scope_names=(), kernels=(), top: int = 10) -> dict:
    """The numbers the per-layer readers use, over the host span ``window``,
    in seconds, each averaged over the chips (busy, scopes, kernels,
    all-to-all) — and the breakdown of the first chip."""
    t0, t1 = window_of(trace, window)
    scopes = scopes or {}
    per = [clip(d, t0, t1) for d in trace.devices]
    if not per:
        raise ValueError("the trace holds no TPU device plane")

    def mean(f):
        return float(np.mean([f(d) for d in per])) / 1e9

    is_a2a = lambda n: base_name(n).startswith(("all-to-all", "all_to_all"))
    ops0 = time_by_op(per[0])
    return dict(
        window_s=(t1 - t0) / 1e9,
        busy_s=mean(busy_ns),
        chips=len(per),
        scope_s={s: mean(lambda d, s=s: busy_ns(d, in_scope(scopes, s)))
                 for s in scope_names},
        kernel_s={k: mean(lambda d, k=k: busy_ns(d, lambda n: base_name(n) == k))
                  for k in kernels},
        a2a_s=mean(lambda d: busy_ns(d, is_a2a)),
        device_ops=[[k, v / 1e9] for k, v in sorted(
            ops0.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[n, g / 1e9] for n, g in
                   idle_gaps(per[0], t0, t1, trace.host)[:top]],
    )

"""Host-side serving telemetry: step-boundary spans on the profiler's clock
+ windowed time series.

The serving loop (PRs 4-8) makes load-bearing runtime decisions — heat-driven
placement swaps, fault shrink/expand, admission/retirement, page allocation —
that were previously only visible as end-of-run ``ServeMetrics`` scalars.
This module makes them observable without perturbing the thing observed:

* ``Tracer`` records named spans and instant events at EXISTING host-side
  step boundaries (``serve.admit``, ``serve.step``, ``serve.readback``,
  ``serve.poll``, ``serve.prefill``, ``serve.rebalance``, ``serve.adopt``,
  ``serve.recover:shrink`` / ``serve.recover:expand``, ``serve.checkpoint``,
  ``serve.drain``). Every span also enters a
  ``jax.profiler.TraceAnnotation`` carrying its args, so under a profiler
  session (``jax.profiler.trace(dir, create_perfetto_trace=True)``) the
  spans land on the host plane of the same ``.xplane.pb`` / Perfetto trace
  as the device ops, on one clock. In memory the tracer keeps the events
  for ``summary()`` (``ServeMetrics.timeline``) and ``events()``.
* ``TimeSeries`` records per-window rows (ITL, queue depth, active slots,
  pages live/peak, per-rank heat + imbalance ratio, alive ranks,
  straggler/rebase counters) and exports JSONL.

Hard contracts (pinned by tests/test_telemetry.py):

* **Host-side only, boundary-scoped.** Telemetry never adds a device sync:
  spans wrap host code that already runs at step boundaries, and heat series
  rows reuse the ``device_get`` the rebalancer/recovery path already
  performs. Decode token streams are bitwise identical tracing on vs off.
* **Disabled == no-op.** ``NULL_TRACER`` / ``NULL_SERIES`` are shared
  singletons whose methods allocate nothing per step while no profiler
  session is active (``span`` returns one shared no-op context manager;
  ``record`` returns immediately). Under a profiler session ``NULL_TRACER``
  spans are bare ``TraceAnnotation``s: the serving loop's boundary spans
  reach the profiler with or without an in-memory tracer.
* **Deterministic tests.** The clock is injectable (monotonic callable
  returning seconds); tests drive a fake clock and assert exact durations.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Callable

from jax.profiler import TraceAnnotation


def json_safe(obj):
    """Recursively coerce numpy scalars/arrays (and other non-JSON leaves)
    into plain Python so ``json.dumps`` succeeds on metrics payloads."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    # numpy scalars expose .item(); arrays expose .tolist()
    if hasattr(obj, "item") and not hasattr(obj, "__len__"):
        return json_safe(obj.item())
    if hasattr(obj, "tolist"):
        return json_safe(obj.tolist())
    return str(obj)


class _NullSpan:
    """Shared no-op context manager handed out by a disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        return None


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager recording one span in memory and in the profiler.
    ``set_metadata`` adds args once the span is open (counters known only
    at its end), as ``TraceAnnotation.set_metadata`` does."""
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **self._args)
        self._ann.__enter__()
        self._t0 = self._tracer.clock()
        return self

    def set_metadata(self, **args):
        self._args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        tr = self._tracer
        tr._events.append(("X", self._name, self._t0,
                           tr.clock() - self._t0, self._args))
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Named spans + instant events with an injectable monotonic clock.

    Events are stored as host tuples ``(ph, name, t_s, dur_s, args)``:
    ``"X"`` spans, ``"i"`` instants, ``"C"`` counters. Spans also go to the
    profiler (module docstring); instants and counters stay in memory."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._events: list[tuple] = []   # (ph, name, t_s, dur_s, args)

    # -- recording ---------------------------------------------------------
    def span(self, name: str, **args) -> _Span:
        """Context manager timing a named host-side region."""
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        self._events.append(("i", name, self.clock(), 0.0, args))

    def counter(self, name: str, value: float) -> None:
        self._events.append(("C", name, self.clock(), 0.0, {"value": value}))

    # -- reading -----------------------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> list[tuple]:
        return list(self._events)

    def summary(self) -> dict:
        """Per-name aggregate (count + total seconds for spans) folded into
        ``ServeMetrics.timeline``. JSON-safe by construction."""
        out: dict[str, dict] = {}
        for ph, name, _t, dur, _a in self._events:
            row = out.setdefault(name, {"count": 0, "total_s": 0.0, "ph": ph})
            row["count"] += 1
            if ph == "X":
                row["total_s"] = round(row["total_s"] + float(dur), 9)
        return out


class NullTracer:
    """Disabled tracer: nothing in memory. Outside a profiler session every
    method is a no-op with no per-call allocation (``span`` returns one
    shared context-manager object); inside one, ``span`` is a bare
    ``TraceAnnotation``."""

    enabled = False

    def span(self, name, **args):
        # TraceMe's own "is a session recording" check, ~0.1 µs
        if TraceAnnotation.is_enabled():
            return TraceAnnotation(name, **args)
        return _NULL_SPAN

    def instant(self, name, **args):
        return None

    def counter(self, name, value):
        return None

    def __len__(self):
        return 0

    def events(self):
        return []

    def summary(self):
        return {}


NULL_TRACER = NullTracer()


def recording(tracer) -> bool:
    """Whether ``tracer``'s spans are recorded anywhere: an enabled tracer,
    or a profiler session. Args that cost a readback are set only then."""
    return tracer.enabled or TraceAnnotation.is_enabled()


class TimeSeries:
    """Append-only recorder of per-window metric rows (plain dicts)."""

    enabled = True

    def __init__(self):
        self.rows: list[dict] = []

    def record(self, **fields) -> None:
        self.rows.append(json_safe(fields))

    def __len__(self) -> int:
        return len(self.rows)

    def to_jsonl(self, path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("w") as f:
            for row in self.rows:
                f.write(json.dumps(row) + "\n")
        return path


class NullTimeSeries:
    """Disabled series: ``record`` returns immediately, ``rows`` stays ()."""

    enabled = False
    rows: tuple = ()

    def record(self, **fields):
        return None

    def __len__(self):
        return 0


NULL_SERIES = NullTimeSeries()


__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER",
    "TimeSeries", "NullTimeSeries", "NULL_SERIES", "json_safe",
]

"""What the harness reads from the device and the compiled programs: the
Pallas kernels in a compiled program, the peak of device memory, and the
traced window."""
from __future__ import annotations

import contextlib
import re

import jax

WINDOW_SPAN = "bench.window"


def kernel_counts(hlo_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled TPU program, by ``pallas_call`` name
    (each is a ``tpu_custom_call`` instruction named after its kernel)."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([A-Za-z_][\w-]*?)(?:\.\d+)*\s*=", line)
        name = m.group(1) if m else "?"
        counts[name] = counts.get(name, 0) + 1
    return counts


def peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class TracedWindow:
    """Profiler on for part of a run's window. ``begin()`` starts the trace
    and opens the host span that bounds the traced window; ``end()`` closes
    both. Nothing happens when ``trace_dir`` is None."""

    def __init__(self, trace_dir: str | None):
        self.dir = trace_dir
        self._span = None
        self.active = False
        self.done = False

    def begin(self):
        if self.dir is None or self.active or self.done:
            return
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.active = True

    def end(self):
        if not self.active:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host annotation in the trace, only while tracing."""
    if on:
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield

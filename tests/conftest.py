"""Test fixtures. EP-collective tests need a handful of devices to exercise
shard_map all-to-alls, so we ask the host platform for 8 (NOT the production
512 — that belongs exclusively to launch/dryrun.py). Single-device smoke
tests are unaffected: they just use device 0.
"""
import importlib.util
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

BENCH_TRACE = Path(__file__).resolve().parents[1] / "bench" / "trace.py"


@pytest.fixture(scope="session")
def bench_trace():
    """bench/trace.py, the benchmark's reduction of a profiler trace
    (imported by path: its plain name would shadow the standard library's
    ``trace``)."""
    if "bench_trace" not in sys.modules:
        spec = importlib.util.spec_from_file_location("bench_trace",
                                                      BENCH_TRACE)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_trace"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_trace"]

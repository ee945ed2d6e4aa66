"""Run one benchmark cell once and print its result as the last line.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell (``workloads``), its configuration file, its traffic file
(``bench/traffic/<traffic>.json``, which names the driver), the driver
(``bench/drivers/<driver>.py``) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``). Adding a cell, traffic
mix, configuration or metric adds files and entries; it edits none.

The run refuses (exit 2, no result) when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from the peak table. An earlier
line records the Pallas kernels of the cell's compiled step and the peak of
device memory. The checks' numbers, each beside its limit, are the last
lines on standard error and the last key of the result line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


class SetupError(RuntimeError):
    """The benchmark cannot run here (no chip, unknown cell, missing file)."""


@dataclasses.dataclass
class Cell:
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace_dir: str | None
    devices: list
    peaks: object
    hooks: dict = dataclasses.field(default_factory=dict)


def load_module(path: Path):
    """Import a file found by name (names may hold dots)."""
    if not path.is_file():
        raise SetupError(f"no such file: {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SetupError(f"no such file: {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def resolve(bench: dict, workload: str) -> dict:
    """The cell's entries and files, or SetupError naming what is missing."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise SetupError(f"unknown config {cell['config']!r}")
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    driver = BENCH / "drivers" / f"{traffic['driver']}.py"
    if not driver.is_file():
        raise SetupError(f"unknown driver {traffic['driver']!r}")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    return dict(cell=cell, config=config, traffic=traffic, driver=driver,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def check_devices(chips: int):
    """(devices, peaks) or SetupError: a TPU with enough chips, listed in
    the peak table."""
    import jax
    from peaks import UnknownDevice, peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips; JAX found {len(devs)}")
    try:
        return devs[:chips], peaks_for(devs[0].device_kind)
    except UnknownDevice as e:
        raise SetupError(str(e)) from None


def reduce_trace(out: dict, spec: dict, cell: Cell) -> dict:
    """Per-layer metrics from the trace and the driver's counters."""
    import trace as TR
    from device import WINDOW_SPAN
    tr = TR.load(cell.trace_dir)
    drv = load_module(spec["driver"])
    scopes = TR.scopes_from_hlo(out["hlo"])
    Path(cell.trace_dir, "scopes.json").write_text(json.dumps(scopes))
    Path(cell.trace_dir, "counters.json").write_text(json.dumps(
        {k: v for k, v in out["counters"].items() if isinstance(v, (int, float))}))
    summary = TR.summarize(
        tr, WINDOW_SPAN, scopes=scopes,
        scope_names=getattr(drv, "SCOPES", ()),
        kernels=getattr(drv, "KERNELS", ()))
    ctx = dict(trace=summary, counters=out["counters"], peaks=cell.peaks,
               config=cell.config, traffic=cell.traffic, chips=cell.chips)
    metrics = {}
    for m in spec["per_layer"]:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    return metrics, summary


def is_correct(checks) -> bool:
    """Every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = resolve(load_json(ROOT / "BENCHMARK.json"), args.workload)
        devices, peaks = check_devices(spec["cell"]["chips"])
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = str(BENCH / "_out" / "trace" / args.workload)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    cell = Cell(config=spec["config"],
                traffic=spec["traffic"], chips=spec["cell"]["chips"],
                seed=args.seed, seconds=args.seconds, trace_dir=trace_dir,
                devices=devices, peaks=peaks)
    out = load_module(spec["driver"]).run(cell)
    setup_s = out["t_open"] - T_PROCESS
    print(json.dumps(dict(kernels=out["kernels"],
                          peak_bytes_in_use=out["memory_peak_bytes"],
                          info=out["info"])), flush=True)
    breakdown = None
    if args.trace:
        metrics, summary = reduce_trace(out, spec, cell)
        busy = dict(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = dict(device_ops=summary["device_ops"],
                         idle_gaps=summary["idle_gaps"])
    else:
        busy = {}
        metrics = {}
        for m in spec["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else out["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    checks = out["checks"]
    correct = is_correct(checks)
    d0 = devices[0]
    result = dict(correct=correct, attempted=out["attempted"],
                  failed=out["failed"], metrics=metrics,
                  device=dict(platform=d0.platform, kind=d0.device_kind,
                              count=len(devices),
                              memory_peak_bytes=out["memory_peak_bytes"],
                              **busy))
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: dict(value=c["value"], limit=c["limit"])
                        for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a cell's check limit: the program's number over many
seeds and the control's number on the same inputs, in one process (one
set-up of the chip). Not run by the benchmark's own runs.

  python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3 ...

Prints one JSON line per seed: the checks' numbers (the program's) and the
control's numbers (the reference in the precision below the
configuration's, put in the program's place), with the end-to-end values.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as RUN  # puts bench/ and src/ on the path


def by_margin(detail: dict, cuts=(0.0, 0.005, 0.01, 0.02, 0.05, 0.1)):
    """For each cut: positions whose routing margin lies below it, and the
    widest gap of the program and of the control over the rest."""
    import numpy as np
    g, c, m = (np.asarray(detail[k]) for k in ("gap", "control", "margin"))
    return [dict(cut=x, left_out=int((m < x).sum()),
                 program=float(g[m >= x].max(initial=0.0)),
                 control=float(c[m >= x].max(initial=0.0))) for x in cuts]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        spec = RUN.resolve(RUN.load_json(RUN.ROOT / "BENCHMARK.json"),
                           args.workload)
        devices, peaks = RUN.check_devices(spec["cell"]["chips"])
    except RUN.SetupError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    drv = RUN.load_module(spec["driver"])
    for seed in args.seeds:
        cell = RUN.Cell(config=spec["config"],
                        traffic=spec["traffic"], chips=spec["cell"]["chips"],
                        seed=seed, seconds=args.seconds, trace_dir=None,
                        devices=devices, peaks=peaks, hooks={"control": True})
        out = drv.run(cell)
        detail = (out["control"] or {}).pop("detail", None)
        if detail is not None:
            out["info"]["by_margin"] = by_margin(detail)
        print(json.dumps(dict(
            seed=seed,
            program={c["name"]: c["value"] for c in out["checks"]},
            control=out["control"], e2e=out["e2e"], info=out["info"])),
            flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings from which the limits of a cell's `correct` are set.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 [--out F]

One process reads, for every seed, the compared numbers of the program
(the lower reading), of the control (the reference computed through fp8:
the upper reading) and of the faults the cell can have, each against the
float32 reference; the cell's driver says how (`calibrate` in
bench/drivers/<driver>.py). The benchmark's own runs do not run this. Each
seed prints one JSON line; `--out` keeps them all.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.join(ROOT, "bench")]
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


def calibrate(root: str, workload: str, seeds: list, *,
              seconds: float = 10.0, allow_cpu: bool = False) -> list:
    cell = harness.find_cell(root, workload)
    devices = harness.check_devices(cell.workload["chips"],
                                    allow_cpu=allow_cpu)
    if not allow_cpu:
        from repro.launch import compile_cache
        compile_cache.enable()
    drv = harness.load_module(root, "drivers", cell.traffic["driver"])
    ref = harness.load_module(root, "reference", cell.cfg["family"])
    ctx = harness.Context(cell=cell, seed=seeds[0], seconds=seconds,
                          trace=False, devices=devices,
                          spans=harness.Spans(),
                          clock=harness.CompileClock(), trace_dir="")
    rows = drv.calibrate(ctx, ref, seeds)
    for row in rows:
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window of a serving cell's readings")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        rows = calibrate(ROOT, args.workload,
                         [int(s) for s in args.seeds.split(",")],
                         seconds=args.seconds)
    except harness.CellError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

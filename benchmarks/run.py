"""Paper-utility reproductions — one entry per paper table/figure.

These suites reproduce the paper's utility results on CPU-sized synthetic
tasks; they are correctness evidence, not speed measurements (the repo's
speed benchmark is `bench/run.py`).

Prints ``name,us_per_call,derived`` CSV (harness contract).

Mapping to the paper:
  table1_*     Tables 1/11, Figure 3 — fixed vs adaptive per-layer utility
  table4_*     Tables 4/12 — epoch-constrained adaptive-per-layer vs flat
  fig5/6_*     Figures 5/6, Table 10 — quantile & allocation ablations

Each suite runs in a process of its own, one after another: on an
accelerator a process that has touched JAX holds the device.

Run:  PYTHONPATH=src python -m benchmarks.run [--full] [--only PREFIX]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# suite name -> module under benchmarks/
SUITES = {
    "utility": "bench_utility",
    "epochs": "bench_epochs",
    "quantile": "bench_quantile",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size benches (slower)")
    ap.add_argument("--only", default=None,
                    help="run only benches whose module name contains this")
    args = ap.parse_args()
    quick = not args.full

    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name, module in SUITES.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        code = (f"from benchmarks.{module} import run\n"
                f"for line in run(quick={quick}):\n"
                f"    print(line, flush=True)\n")
        rc = subprocess.run([sys.executable, "-c", code], cwd=_REPO).returncode
        if rc:
            failures += 1
            print(f"{name}_SUITE_ERROR,0,exit {rc}", flush=True)
        print(f"# suite {name} took {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

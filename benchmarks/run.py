"""Benchmark orchestrator — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (harness contract).

Mapping to the paper:
  fig1_*       Figure 1   — per-update efficiency of clipping schemes
  table1_*     Tables 1/11, Figure 3 — fixed vs adaptive per-layer utility
  table4_*     Tables 4/12 — epoch-constrained adaptive-per-layer vs flat
  table6_*     Table 6 / Sec 4 — per-device clipping communication
  fig5/6_*     Figures 5/6, Table 10 — quantile & allocation ablations
  kernel_*     ghost-norm op microbenches (Sec 3.1 fused op)
  roofline_*   EXPERIMENTS.md §Roofline (from the multi-pod dry-run)
  serve_*      beyond-paper: slot-pool continuous-batching serving engine
               vs dispatch-per-token loops (occupancy + arrival sweeps)

Every suite that persists measurements writes a ``BENCH_*.json`` artifact
next to this file; after the suites run, ``aggregate()`` folds them all
into ``BENCH_summary.json`` so the perf trajectory across PRs is
machine-readable from ONE file (``--aggregate-only`` refreshes it without
re-benchmarking).

Each suite runs in a process of its own, one after another: on an
accelerator a process that has touched JAX holds the device, so a runner
that ran suites in-process would lock out the workers that `startup`,
`sharded` and `scaling` start.

Run:  PYTHONPATH=src python -m benchmarks.run [--full] [--only PREFIX]
                                              [--aggregate-only]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_SUMMARY_PATH = os.path.join(_BENCH_DIR, "BENCH_summary.json")


def aggregate() -> str:
    """Fold every BENCH_*.json artifact into BENCH_summary.json."""
    artifacts = {}
    for path in sorted(glob.glob(os.path.join(_BENCH_DIR, "BENCH_*.json"))):
        name = os.path.basename(path)
        if name == os.path.basename(_SUMMARY_PATH):
            continue
        try:
            with open(path) as fh:
                artifacts[name] = json.load(fh)
        except (OSError, ValueError) as e:
            artifacts[name] = {"error": f"{type(e).__name__}: {e}"}
    summary = {"unix_time": int(time.time()), "artifacts": artifacts}
    with open(_SUMMARY_PATH, "w") as fh:
        json.dump(summary, fh, indent=1)
    return _SUMMARY_PATH


# suite name -> module under benchmarks/
SUITES = {
    "throughput": "bench_throughput",
    "kernels": "bench_kernels",
    "startup": "bench_startup",
    "sharded": "bench_sharded",
    "serve": "bench_serve",
    "utility": "bench_utility",
    "epochs": "bench_epochs",
    "quantile": "bench_quantile",
    "scaling": "bench_scaling",
    "roofline": "roofline",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size benches (slower)")
    ap.add_argument("--only", default=None,
                    help="run only benches whose module name contains this")
    ap.add_argument("--aggregate-only", action="store_true",
                    help="just rebuild BENCH_summary.json from existing "
                         "BENCH_*.json artifacts")
    args = ap.parse_args()
    quick = not args.full
    if args.aggregate_only:
        print(f"# wrote {aggregate()}", file=sys.stderr)
        return

    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name, module in SUITES.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        code = (f"from benchmarks.{module} import run\n"
                f"for line in run(quick={quick}):\n"
                f"    print(line, flush=True)\n")
        rc = subprocess.run([sys.executable, "-c", code],
                            cwd=os.path.dirname(_BENCH_DIR)).returncode
        if rc:
            failures += 1
            print(f"{name}_SUITE_ERROR,0,exit {rc}", flush=True)
        print(f"# suite {name} took {time.time()-t0:.1f}s", file=sys.stderr)
    print(f"# wrote {aggregate()}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

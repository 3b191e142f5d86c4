"""Cold- vs warm-start wall time for the train and serve entry points.

PR 3 measured ~19s of retrace+compile for one production dryrun; every
train/serve/service worker pays its own version of that cold on startup.
This bench measures what the persistent compile cache
(`repro.launch.compile_cache`) buys back: each entry point runs as a REAL
subprocess twice against the same fresh cache root — the first run compiles
and serializes (cold), the second deserializes (warm) — and the full
process wall time (interpreter + imports + trace + compile/deserialize +
the actual steps) is recorded to ``benchmarks/BENCH_startup.json`` with the
topology stamp, folded into ``BENCH_summary.json`` by ``benchmarks/run``.

``python -m benchmarks.bench_startup --smoke`` ASSERTS the acceptance bar:
warm-start wall time strictly below cold-start for BOTH entry points
(scripts/bench_smoke.sh and CI run this).

The parent never imports JAX: on an accelerator a process that has touched
JAX holds the device, and the entry points it starts need it. The topology
stamp comes from a child too (`python -m repro.kernels.autotune --show`).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

from benchmarks.common import csv_line

_OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_startup.json")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# small enough to finish in seconds, big enough that compile dominates the
# cold run (measured ~8.6s cold vs ~3.3s warm for train on the CPU container)
ENTRIES = {
    "train": ["-m", "repro.launch.train", "--arch", "tiny", "--steps", "2",
              "--batch", "8", "--seq", "32", "--docs", "64",
              "--log-every", "100"],
    "serve": ["-m", "repro.launch.serve", "--arch", "tiny", "--mode",
              "engine", "--batch", "2", "--slots", "2", "--prompt-len", "8",
              "--gen", "8"],
}


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ("src" + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else "src")
    return env


def _run_cli(argv: list[str], cache_root: str) -> float:
    t0 = time.perf_counter()
    subprocess.run([sys.executable, *argv, "--cache-dir", cache_root],
                   cwd=_REPO, env=_child_env(), check=True,
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    return time.perf_counter() - t0


def _topology() -> dict:
    """The device stamp, read in a child process (see module doc)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.kernels.autotune", "--show"],
        cwd=_REPO, env=_child_env(), check=True, capture_output=True,
        text=True)
    return json.loads(out.stdout)["topology"]


def run(quick: bool = True) -> list[str]:
    lines: list[str] = []
    records: list[dict] = []
    root = tempfile.mkdtemp(prefix="repro_startup_")
    try:
        for entry, argv in ENTRIES.items():
            cache = os.path.join(root, entry)  # fresh root per entry = cold
            cold = _run_cli(argv, cache)
            warm = _run_cli(argv, cache)
            rec = {
                "name": f"startup_{entry}",
                "cold_s": round(cold, 3),
                "warm_s": round(warm, 3),
                "speedup": round(cold / warm, 2) if warm > 0 else None,
                "warm_faster": warm < cold,
            }
            records.append(rec)
            lines.append(csv_line(f"startup_{entry}_cold", cold * 1e6,
                                  "subprocess_wall"))
            lines.append(csv_line(f"startup_{entry}_warm", warm * 1e6,
                                  f"speedup={rec['speedup']};"
                                  f"warm_faster={rec['warm_faster']}"))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    payload = {"topology": _topology(), "unix_time": int(time.time()),
               "records": records}
    with open(_OUT_PATH, "w") as fh:
        json.dump(payload, fh, indent=1)
    lines.append(csv_line("startup_bench_json_written", 0.0, _OUT_PATH))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="assert the acceptance bar: warm < cold for both "
                         "entry points")
    args = ap.parse_args(argv)
    for line in run(quick=True):
        print(line, flush=True)
    if args.smoke:
        with open(_OUT_PATH) as fh:
            recs = json.load(fh)["records"]
        bad = [r["name"] for r in recs if not r["warm_faster"]]
        if bad:
            print(f"SMOKE FAIL: warm start not faster for {bad}",
                  file=sys.stderr)
            return 1
        print(f"# startup smoke OK: "
              + ", ".join(f"{r['name']} {r['cold_s']}s->{r['warm_s']}s "
                          f"({r['speedup']}x)" for r in recs))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

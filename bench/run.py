"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json and its files by name (bench/harness.py).
The driver named by the cell's traffic mix builds the program's entry,
warms every shape the window uses (that is set-up), measures for
`--seconds`, then checks what the window's path produced against the plain
reference. `--trace 0` prints the cell's end-to-end metrics, `--trace 1`
records the profiler trace of the window and prints the per-layer metrics.
The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, (breakdown,) compared. A run that finds no TPU,
too few chips, or no program beside the benchmark exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# run as a script, Python puts bench/ first on the path, where trace.py
# would shadow the standard library's module of that name
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.join(ROOT, "bench")]
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, trace as trace_lib  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def compared_with_limits(cell, compared: dict) -> tuple:
    """{name: {value, limit}} and whether every number is within its limit."""
    limits = harness.limits_of(cell)
    out, ok = {}, bool(compared)
    for name, value in compared.items():
        limit = limits.get(name, {}).get("limit")
        out[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and harness.finite(value) \
            and value <= limit
    return out, ok


def per_layer(cell, ctx, stats, root) -> tuple:
    summary = trace_lib.summarize_dir(ctx.trace_dir)
    try:
        peak = harness.peak_of(ctx.devices[0].device_kind)
    except harness.CellError:  # a CPU rehearsal: no device metric
        peak = None
    run = types.SimpleNamespace(
        summary=summary, stats=stats, cfg=cell.cfg, traffic=cell.traffic,
        chips=cell.workload["chips"], peak=peak,
        flops=harness.load_module(root, "flops", cell.cfg["family"]))
    top = sorted(summary.kernel_s.items(), key=lambda kv: -kv[1])[:30]
    print("# device time by op: " + ", ".join(
        f"{k} {v:.4f}s x{summary.kernel_n[k]}" for k, v in top),
        file=sys.stderr)
    print(f"# device time by program: {summary.program_s} runs "
          f"{summary.program_n}", file=sys.stderr)
    metrics = {}
    for m in cell.metrics("per_layer"):
        reader = harness.load_module(root, "metrics", m["name"])
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    return metrics, summary


def main(argv=None, *, root: str = ROOT, allow_cpu: bool = False,
         cache: bool = True) -> int:
    args = parse(argv)
    try:
        cell = harness.find_cell(root, args.workload)
        if not os.path.isdir(os.path.join(root, "src", "repro")):
            raise harness.CellError(f"no program (src/repro) under {root}")
        sys.path.insert(0, os.path.join(root, "src"))
        driver = harness.load_module(root, "drivers", cell.traffic["driver"])
        devices = harness.check_devices(cell.workload["chips"],
                                        allow_cpu=allow_cpu)
        if not allow_cpu:
            harness.peak_of(devices[0].device_kind)
    except (harness.CellError, OSError, KeyError, IndexError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    import jax
    if cache:
        from repro.launch import compile_cache
        compile_cache.enable()
    ctx = harness.Context(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, spans=harness.Spans(),
        clock=harness.CompileClock(),
        trace_dir=os.path.join(root, "bench", ".run", "trace-" + cell.name))
    out = driver.run(ctx)
    ctx.log(f"compiles inside the window: {ctx.compiles_in_window} "
            f"(traces {ctx.traces_in_window}); set-up "
            f"{ctx.window_start_age:.3f} s")
    compared, within = compared_with_limits(cell, out["compared"])
    correct = within and out["failed"] == 0
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.trace:
        metrics, summary = per_layer(cell, ctx, out["stats"], root)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = summary.breakdown()
    else:
        e2e = dict(out["e2e"], setup_s=ctx.window_start_age)
        line["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end") if m["name"] in e2e}
        line["device"] = device
    line["compared"] = compared
    print(f"correct {correct}; compared with their limits:", file=sys.stderr)
    for name, c in compared.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

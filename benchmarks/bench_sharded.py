"""Sharded execution engine bench: step time + collective profile per mode.

The paper's Sec-4 claim is measured from the EXECUTING multi-device path
(shard_map train step, `repro.core.dp_sgd` with `mesh=`), not inferred from
a lowering: for each device count in (1, 4, 8) virtual CPU devices this
suite runs `per_layer`, `ghost_flat` and `per_group`-as-per-device on a
(data, model) mesh, records median step wall time, and classifies every
compiled collective by the mesh axes it crosses
(`launch.hlo_analysis.collective_axis_summary`). The headline columns:

  * `model_axis_norm_collectives` — MUST be 0 for per_group (per-device
    clipping is communication-free before scaling) and >= 1 for ghost_flat
    (the (B,) total-norm psum);
  * `by_axis` — norm traffic (model) vs grad traffic (data / data+model).

Each device count needs its own XLA device set, so the parent re-execs
itself as a `--child` subprocess with
`XLA_FLAGS=--xla_force_host_platform_device_count=N` before jax init. That
flag only shapes the CPU backend: on an accelerator the child refuses to
run, and the parent raises instead of recording a result.
Results land in ``benchmarks/BENCH_sharded.json`` (folded into
``BENCH_summary.json`` by ``benchmarks/run.py``).

Run:  PYTHONPATH=src python -m benchmarks.bench_sharded [--full|--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

_OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_sharded.json")
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MODES = ("per_layer", "ghost_flat", "per_group")
# device count -> (data, model) mesh
MESHES = {1: (1, 1), 4: (2, 2), 8: (2, 4)}


def _child(devices: int, quick: bool) -> dict:
    """Measure all modes on THIS process's devices (exactly `devices`)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import timeit, topology
    from repro import optim
    from repro.configs import get_config
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import init_params
    from repro.launch.hlo_analysis import (classify_collectives,
                                           filter_model_norm_rows,
                                           summarize_axis_rows)
    from repro.launch.inputs import concrete_train_batch
    from repro.launch.mesh import make_debug_mesh
    from repro.models.transformer import build_model

    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"bench_sharded measures virtual CPU devices; JAX found "
            f"{jax.default_backend()!r}, where "
            "--xla_force_host_platform_device_count cannot set the count")
    if jax.device_count() != devices:
        raise SystemExit(f"want {devices} devices, JAX sees "
                         f"{jax.device_count()}")
    d, m = MESHES[devices]
    mesh = make_debug_mesh(d, m)
    cfg = get_config("tiny")
    model = build_model(cfg)
    params = init_params(model.spec, jax.random.PRNGKey(0))
    b, t = (8, 64) if quick else (16, 128)
    batch = concrete_train_batch(cfg, b, t, jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)

    records = []
    for mode in MODES:
        dpc = DPConfig(mode=mode, sigma=1.0, sampling_rate=0.01, steps=100,
                       adaptive=True, backend="xla")
        init_fn, step_fn, _ = make_dp_train_step(
            model.loss_fn, model.spec, model.layout, optim.adam(1e-3), dpc,
            batch_size=b, mesh=mesh)
        opt_state, dp_state = init_fn(params)
        step = jax.jit(step_fn)
        lowered = step.lower(params, opt_state, dp_state, batch, key)
        hlo = lowered.compile().as_text()
        us = timeit(step, params, opt_state, dp_state, batch, key,
                    warmup=1, iters=3 if quick else 5)
        rows = classify_collectives(hlo, mesh)  # parse the HLO once
        records.append({
            "mode": mode,
            "us_per_step": round(us, 1),
            "collectives_by_axis": summarize_axis_rows(rows),
            "model_axis_norm_collectives": sum(
                r["count"] for r in filter_model_norm_rows(rows)),
        })
    return {"device_count": devices, "mesh": f"{d}x{m}", "quick": quick,
            "batch": b, "seq": t, "topology": topology(),
            "records": records}


def run(quick: bool = True, device_counts=(1, 4, 8)) -> list[str]:
    """Parent: one subprocess per device count; writes BENCH_sharded.json."""
    from benchmarks.common import csv_line

    lines = []
    runs = {}
    for n in device_counts:
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n}"])
        env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, "-m", "benchmarks.bench_sharded", "--child",
               "--devices", str(n)] + ([] if quick else ["--full"])
        out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                             cwd=os.path.join(os.path.dirname(__file__), ".."),
                             timeout=1800)
        mm = re.search(r"CHILD_RESULT (.*)", out.stdout)
        if out.returncode != 0 or not mm:
            raise RuntimeError(f"bench_sharded child for {n} devices failed "
                               f"(exit {out.returncode}):\n"
                               f"{out.stderr.strip()[-2000:]}")
        payload = json.loads(mm.group(1))
        runs[str(n)] = payload
        for r in payload["records"]:
            model_norm = r["model_axis_norm_collectives"]
            lines.append(csv_line(
                f"sharded_step_{r['mode']}_{n}dev", r["us_per_step"],
                f"mesh={payload['mesh']};"
                f"model_axis_norm_collectives={model_norm:g}"))
    data = {"runs": {}}
    if os.path.exists(_OUT_PATH):  # merge: a smoke run must not clobber
        try:                       # the full 1/4/8-device sweep
            prev = json.load(open(_OUT_PATH))
            if isinstance(prev.get("runs"), dict):
                data = prev
        except (OSError, ValueError):
            pass
    data.pop("quick", None)  # quick is per-run: a smoke refresh of one
    data["unix_time"] = int(time.time())  # device count must not relabel
    data["runs"].update(runs)             # the retained full-sweep records
    with open(_OUT_PATH, "w") as fh:
        json.dump(data, fh, indent=1)
    lines.append(csv_line("sharded_bench_json_written", 0.0, _OUT_PATH))
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: 4-device run only")
    args = ap.parse_args()
    if args.child:
        payload = _child(args.devices, quick=not args.full)
        print("CHILD_RESULT " + json.dumps(payload), flush=True)
        return
    counts = (4,) if args.smoke else (1, 4, 8)
    for line in run(quick=not args.full, device_counts=counts):
        print(line, flush=True)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) combo.

For each combination this driver builds the REAL step function (the full
adaptive per-layer DP-SGD train step — clipping, quantile update, noise,
optimizer — or the one-token serve step), jits it with explicit
in/out_shardings on the production mesh, lowers it against
ShapeDtypeStruct inputs (no allocation), compiles, and extracts:

  * memory_analysis()  — per-device argument/output/temp/peak bytes
  * cost_analysis()    — HLO flops / bytes accessed
  * collective bytes   — parsed from the post-SPMD HLO text per collective
                         kind (all-reduce, all-gather, reduce-scatter,
                         all-to-all, collective-permute)

Results go to benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json, which
benchmarks/roofline.py and EXPERIMENTS.md consume.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs import ARCH_IDS, get_config
from repro.core.clipping import tied_refusal
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import abstract_params
from repro.launch import inputs as I
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze_hlo, backward_passes
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   opt_state_shardings, params_shardings,
                                   replicated)
from repro.models.config import INPUT_SHAPES
from repro.models.transformer import build_model

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

# Large archs take the paper's DP-LoRA path for the train shape (frozen base
# does not fit optimizer+grads on 16 GB/chip otherwise; see DESIGN.md).
LORA_TRAIN_ARCHS = {"deepseek-v3-671b": 32, "qwen2-vl-72b": 32}

# long_500k policy (DESIGN.md §4): native sub-quadratic, MLA-latent, or the
# documented sliding-window variant; pure full-attention archs skip.
LONG_OK = {"zamba2-7b": None, "rwkv6-7b": None, "deepseek-v3-671b": None,
           "qwen3-4b": "swa", "minicpm-2b": "swa"}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape like 'f32[16,128]' (tuples handled upstream)."""
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the partitioned HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        # e.g.:  %all-reduce.5 = f32[256,512]{1,0} all-reduce(...)
        m = re.match(r"%?[\w.\-]+ = (\(?)(.*?) ([a-z\-]+)\(", line)
        if not m:
            continue
        op = m.group(3)
        if op not in COLLECTIVES:
            continue
        shapes_part = m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(shapes_part):
            total += _shape_bytes(sm.group(0))
        out[op]["count"] += 1
        out[op]["bytes"] += total
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _shape_for(shape_name: str, debug: bool):
    from repro.models.config import InputShape
    if not debug:
        return INPUT_SHAPES[shape_name]
    kind = INPUT_SHAPES[shape_name].kind
    return InputShape("debug_" + shape_name, 64 if kind == "train" else 128,
                      8, kind)


def build_train_lowering(arch: str, shape_name: str, mesh, *,
                         clipping: str = "per_layer",
                         execution: str = "bk",
                         microbatches: int = 8,
                         rwkv_formulation: str = "chunked",
                         debug: bool = False,
                         moe_dispatch: str | None = None,
                         sharded: bool = False):
    shape = _shape_for(shape_name, debug)
    variant = LONG_OK.get(arch) if shape_name == "long_500k" else None
    cfg = get_config(arch, reduced=debug, variant=variant)
    lora_rank = LORA_TRAIN_ARCHS.get(arch, 0)
    if lora_rank and not debug:
        cfg = dataclasses.replace(cfg, lora_rank=lora_rank)
    if clipping == "per_shard":
        # per-device clipping analogue: blocked groups aligned with the
        # Megatron column shards; the DP mode itself is per_layer over the
        # finer (layer x shard) groups.
        cfg = dataclasses.replace(cfg, dp_blocks=int(mesh.shape["model"]))
        clipping = "per_shard_resolved"
    if moe_dispatch is not None:
        cfg = dataclasses.replace(cfg, moe_dispatch=moe_dispatch)
    model = build_model(cfg, rwkv_formulation=rwkv_formulation)

    from repro.launch.mesh import data_axes
    if clipping == "per_shard_resolved":
        clipping = "per_layer"
    assign, nsuper = None, None
    if clipping.startswith("per_group") and not sharded:
        # per-DEVICE supergroups from model-axis shard ownership — the SAME
        # helper the sharded executing path and bench_sharded use (under
        # `sharded` the factory derives this from the mesh itself)
        from repro.launch.sharding import group_shard_assignment
        nsuper = int(mesh.shape["model"])
        assign = group_shard_assignment(model.layout, nsuper)
    # backend="xla": dry-run lowering must stay on the reference paths (a
    # TPU pallas custom-call cannot lower on the CPU backend used here).
    # sharded: shard_map splits the batch manually, so the GSPMD microbatch
    # pin (batch_axes) does not apply inside the manual region.
    dpc = DPConfig(mode=clipping, sigma=1.0, sampling_rate=1e-3,
                   steps=1000, adaptive=True, init_threshold=1.0,
                   microbatches=microbatches, execution=execution,
                   group_assignment=assign, num_supergroups=nsuper,
                   batch_axes=None if sharded else data_axes(mesh),
                   backend="xla")
    init_fn, step_fn, plan = make_dp_train_step(
        model.loss_fn, getattr(model, "dp_spec", model.spec), model.layout,
        optim.adam(1e-4), dpc, batch_size=shape.global_batch,
        trainable_key=getattr(model, "trainable_key", None),
        mesh=mesh if sharded else None)

    params_abs = abstract_params(model.spec)
    opt_abs, dp_abs = jax.eval_shape(init_fn, params_abs)
    batch_abs = I.train_batch_specs(cfg, shape)
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)

    pshard = params_shardings(model.spec, mesh)
    oshard = opt_state_shardings(
        opt_abs, pshard if getattr(model, "trainable_key", None) is None
        else pshard["lora"], mesh)
    dshard = replicated(dp_abs, mesh)
    bshard = batch_shardings(batch_abs, mesh)
    kshard = replicated(key_abs, mesh)

    jitted = jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, dshard, bshard, kshard),
        out_shardings=(pshard, oshard, dshard, None),
        donate_argnums=(0, 1, 2),  # params/opt/dp buffers update in place
    )
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params_abs, opt_abs, dp_abs, batch_abs,
                               key_abs)
    return lowered, model, cfg


def build_serve_lowering(arch: str, shape_name: str, mesh, *,
                         debug: bool = False):
    shape = _shape_for(shape_name, debug)
    variant = LONG_OK.get(arch) if shape_name == "long_500k" else None
    cfg = get_config(arch, reduced=debug, variant=variant)
    model = build_model(cfg)
    params_abs = abstract_params(model.spec)
    # weight-FSDP only when model-axis sharding cannot hold the weights
    # (blanket FSDP re-gathers weights inside attention/scan loops and
    # multiplies prefill collectives ~10x — measured; EXPERIMENTS.md)
    import numpy as _np
    param_bytes = sum(
        int(_np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params_abs))
    per_dev = param_bytes / mesh.shape["model"]
    # decode only: prefill's remat/flash loops re-gather FSDP weights and
    # blow up both collectives and (analyzer-visible) compute; for prefill
    # the 671B case is honestly reported as not fitting single-pod v5e
    serving_fsdp = per_dev > 12 * 2**30 and shape.kind == "decode"
    pshard = params_shardings(model.spec, mesh, serving=serving_fsdp)

    if shape.kind == "prefill":
        batch_abs = I.train_batch_specs(cfg, shape)
        batch_abs.pop("targets")
        bshard = batch_shardings(batch_abs, mesh)
        jitted = jax.jit(model.prefill_step,
                         in_shardings=(pshard, bshard), out_shardings=None)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, batch_abs)
        return lowered, model, cfg

    cache_abs = model.abstract_cache(shape.global_batch, shape.seq_len)
    batch_abs = I.serve_batch_specs(cfg, shape)
    cshard = cache_shardings(cache_abs, mesh)
    bshard = batch_shardings(batch_abs, mesh)
    jitted = jax.jit(model.serve_step,
                     in_shardings=(pshard, cshard, bshard),
                     out_shardings=(None, cshard),
                     donate_argnums=(1,))  # KV/state cache updates in place
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params_abs, cache_abs, batch_abs)
    return lowered, model, cfg


def _layer_trip(cfg) -> int:
    """Depth of the model's dominant homogeneous scan run (the
    `known_trip_count` its layer loops carry in the compiled HLO)."""
    n = cfg.num_layers
    runs = [n]
    if getattr(cfg, "num_experts", 0) and getattr(cfg, "first_k_dense", 0):
        runs = [cfg.first_k_dense, n - cfg.first_k_dense]
    if getattr(cfg, "encoder_layers", 0):
        runs.append(cfg.encoder_layers)
    return max(r for r in runs)


def run_one(arch: str, shape_name: str, mesh_kind: str, *,
            clipping: str = "per_layer", execution: str = "bk",
            save: bool = True,
            rwkv_formulation: str = "chunked",
            microbatches: int | None = None, debug: bool = False,
            ghost_outer_cap: int | None = None,
            moe_dispatch: str | None = None,
            sharded: bool = False,
            audit: bool = False,
            tag: str = "") -> dict:
    shape = _shape_for(shape_name, debug)
    reason = None
    if shape_name == "long_500k" and arch not in LONG_OK:
        reason = ("full-attention arch; long_500k requires sub-quadratic "
                  "attention (DESIGN.md)")
    elif shape.kind == "train" and get_config(arch).tie_embeddings:
        why = tied_refusal(clipping, execution, sharded=sharded)
        if why is not None:
            reason = f"tied embedding, which {clipping} cannot clip: {why}"
    if reason is not None:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "skipped", "reason": reason}
        if save:
            os.makedirs(RESULTS_DIR, exist_ok=True)
            with open(os.path.join(
                    RESULTS_DIR,
                    f"{arch}__{shape_name}__{mesh_kind}.json"), "w") as f:
                json.dump(result, f, indent=1)
        return result
    if mesh_kind == "debug":
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 2)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    kind = shape.kind  # train | prefill | decode
    from contextlib import ExitStack

    from repro.kernels import backend as _backend
    # scoped engine config (not a module-global mutation): the step trace
    # inside build_*_lowering inherits the widened outer cap — see the
    # sharding note in repro.core.ghost.
    eng_scope = ExitStack()
    if ghost_outer_cap is not None:
        eng_scope.enter_context(
            _backend.scoped(outer_max_elems=ghost_outer_cap))
    try:
        if kind == "train":
            mb = microbatches if microbatches is not None else (2 if debug else 8)
            lowered, model, cfg = build_train_lowering(
                arch, shape_name, mesh, clipping=clipping,
                execution=execution, microbatches=mb,
                rwkv_formulation=rwkv_formulation, debug=debug,
                moe_dispatch=moe_dispatch, sharded=sharded)
        else:
            lowered, model, cfg = build_serve_lowering(arch, shape_name, mesh,
                                                       debug=debug)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        mem_d = {}
        if mem is not None:
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                mem_d[f] = int(getattr(mem, f, 0) or 0)
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # jax<=0.4 returns [dict]
            cost = cost[0] if cost else {}
        cost_d = {k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float)) and k in
                  ("flops", "bytes accessed", "transcendentals")}
        hlo = compiled.as_text()
        t0 = time.time()
        totals = analyze_hlo(hlo)  # trip-count-aware (scan bodies x L)
        t_analyze = time.time() - t0
        coll = {k: {"count": v["count"], "bytes": v["bytes"]}
                for k, v in totals.collectives.items()}
        coll["total_bytes"] = sum(v["bytes"] for v in coll.values())
        # assert (not assume) the pass structure: how many full backward
        # traversals of the layer stack did this step actually compile to?
        trip = _layer_trip(cfg)
        bw_passes = (backward_passes(hlo, trip)
                     if kind == "train" and trip >= 2 else None)
        audit_d = None
        if audit and kind == "train":
            from repro.analysis.findings import errors
            from repro.analysis.rules import StepExpectation, run_hlo_rules
            from repro.core.clipping import base_mode
            # donated_leaves=None: the dry-run varies donation with cache
            # settings; full donation coverage is audited by launch.audit
            expect = StepExpectation(
                mode=base_mode(clipping), execution=execution,
                sharded=sharded, layer_trip=trip, donated_leaves=None)
            fs = run_hlo_rules(hlo, expect, mesh if sharded else None)
            audit_d = {"findings": [f.to_dict() for f in fs],
                       "num_errors": len(errors(fs))}
        axis_coll = None
        if sharded and kind == "train":
            from repro.launch.hlo_analysis import (classify_collectives,
                                                   filter_model_norm_rows,
                                                   summarize_axis_rows)
            rows = classify_collectives(hlo, mesh)  # parse the HLO once
            axis_coll = {
                "by_axis": summarize_axis_rows(rows),
                "model_axis_norm_count": sum(
                    r["count"] for r in filter_model_norm_rows(rows)),
            }
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "kind": kind, "clipping": clipping if kind == "train" else None,
            "execution": execution if kind == "train" else None,
            "sharded": sharded if kind == "train" else None,
            "backward_passes": bw_passes,
            "collectives_by_axis": axis_coll,
            "audit": audit_d,
            "status": "ok",
            "num_params": model.num_params,
            "num_groups": model.layout.num_groups,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "analyze_s": round(t_analyze, 2),
            "memory": mem_d,
            "flops": totals.flops,                  # per device, loop-aware
            "bytes_accessed": totals.bytes,         # per device, loop-aware
            "transcendentals": totals.transcendentals,
            "xla_cost_analysis": cost_d,            # raw (loop bodies x1)
            "collectives": coll,
            "devices": int(np.prod(list(mesh.shape.values()))),
            "hlo_bytes": len(hlo),
        }
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "kind": kind, "status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    finally:
        eng_scope.close()
    if tag:
        result["tag"] = tag
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        suffix = "" if clipping == "per_layer" else f"__{clipping}"
        if execution != "bk":
            suffix += f"__{execution}"
        if sharded:
            suffix += "__sharded"
        if tag:
            suffix += f"__{tag}"
        fn = os.path.join(
            RESULTS_DIR, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both", "debug"],
                    default="single")
    ap.add_argument("--clipping", default="per_layer")
    ap.add_argument("--execution", default="bk", choices=["bk", "twopass"],
                    help="flat/group clipping execution: bk (single "
                         "backprop + book-keeping epilogue) or twopass "
                         "(reference two-backward driver)")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--sharded", action="store_true",
                    help="lower the shard_map executing path (manual-SPMD "
                         "clipping engine) instead of the GSPMD jit; "
                         "results gain a per-mesh-axis collective "
                         "breakdown (collectives_by_axis)")
    ap.add_argument("--audit", action="store_true",
                    help="run the static DP-safety HLO rules "
                         "(repro.analysis.rules) on each compiled train "
                         "step; any ERROR finding fails the run")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--cache", default="off", choices=["on", "off"],
                    help="persistent compilation cache for the lowered "
                         "programs (repro.launch.compile_cache). Default "
                         "OFF: the dry-run's compile_s numbers measure the "
                         "compiler, and a warm cache would zero them; turn "
                         "on to pre-warm a fleet cache from the production "
                         "program set")
    ap.add_argument("--cache-dir", default=None,
                    help="cache root for --cache on (default <repo>/.cache "
                         "or $REPRO_CACHE_DIR)")
    args = ap.parse_args()

    if args.cache != "off":
        from repro.launch import compile_cache
        compile_cache.enable(args.cache_dir)

    debug = args.mesh == "debug"
    combos = []
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for mk in meshes:
                combos.append((a, s, mk))

    failures = 0
    for a, s, mk in combos:
        suffix = "" if args.clipping == "per_layer" else f"__{args.clipping}"
        if args.execution != "bk":
            suffix += f"__{args.execution}"
        if args.sharded:
            suffix += "__sharded"
        fn = os.path.join(RESULTS_DIR, f"{a}__{s}__{mk}{suffix}.json")
        if args.skip_existing and os.path.exists(fn):
            with open(fn) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skipped"):
                print(f"[skip-existing] {a} {s} {mk}: {prev['status']}")
                continue
        r = run_one(a, s, mk, clipping=args.clipping,
                    execution=args.execution,
                    microbatches=args.microbatches, save=not debug,
                    debug=debug, sharded=args.sharded, audit=args.audit)
        if r["status"] == "ok" and (r.get("audit") or {}).get("num_errors"):
            failures += 1
            bad = [f for f in r["audit"]["findings"]
                   if f["severity"] == "ERROR"]
            print(f"[FAIL] {a:22s} {s:12s} {mk:6s} audit: "
                  + "; ".join(f"{f['rule']}: {f['message']}" for f in bad),
                  flush=True)
        elif r["status"] == "ok":
            gb = r["memory"].get("temp_size_in_bytes", 0) / 2**30
            print(f"[ok]   {a:22s} {s:12s} {mk:6s} "
                  f"flops={r['flops']:.3e} temp={gb:.2f}GiB "
                  f"coll={r['collectives']['total_bytes']/2**30:.2f}GiB "
                  f"(lower {r['lower_s']}s compile {r['compile_s']}s)",
                  flush=True)
        elif r["status"] == "skipped":
            print(f"[skip] {a:22s} {s:12s} {mk:6s} {r['reason']}", flush=True)
        else:
            failures += 1
            print(f"[FAIL] {a:22s} {s:12s} {mk:6s} {r['error']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

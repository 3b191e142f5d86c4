"""End-to-end training driver (runs for real on CPU at reduced scale; the
same code path jits under the production mesh on TPU).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \\
      --clipping per_layer --epsilon 8 --steps 50 --batch 16 --seq 64

`run(argv)` is the whole CLI as a function: it returns a `TrainRun` with the
compiled step program and the per-step readings, so a caller in the same
process (chip_smoke.py, tests) can check what the command did.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.checkpoint import save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.core.accounting import compute_epsilon
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import init_params
from repro.data import PoissonSampler, SyntheticLM, make_lm_batch, pack_documents
from repro.models.transformer import build_model


def parse_mesh(arg: str | None):
    """'--mesh DxM' -> a (data, model) mesh over the first D*M devices."""
    if not arg:
        return None
    from repro.launch.mesh import make_debug_mesh
    d, m = (int(x) for x in arg.lower().split("x"))
    return make_debug_mesh(d, m)


def config_from_args(args):
    """The model config the CLI flags select: `--layers N` cuts the depth
    and changes nothing else, so every width stays the published one."""
    cfg = get_config(args.arch, reduced=args.reduced,
                     variant=getattr(args, "variant", None))
    if args.layers is not None:
        if args.layers < 1:
            raise ValueError(f"--layers must be >= 1, got {args.layers}")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def build_everything(args):
    cfg = config_from_args(args)
    if args.lora_rank:
        cfg = dataclasses.replace(cfg, lora_rank=args.lora_rank)
    model = build_model(cfg)
    mesh = parse_mesh(args.mesh)

    src = SyntheticLM(vocab_size=cfg.vocab_size, num_docs=args.docs,
                      doc_len=args.seq * 2, seed=0)
    rows = pack_documents(src.documents(), args.seq)
    sampler = PoissonSampler(num_examples=rows.shape[0],
                             rate=args.batch / rows.shape[0],
                             max_batch=args.batch, seed=1)

    assign, nsuper = None, None
    if args.clipping.startswith("per_group") and mesh is None:
        # per-device semantics without a mesh: supergroup s = "what model
        # shard s would own" under the SAME ownership rule the sharded
        # engine and benchmarks use (launch.sharding); --group-count picks
        # the virtual shard count. (With --mesh the sharded factory derives
        # the assignment from the mesh itself.)
        from repro.launch.sharding import group_shard_assignment
        nsuper = args.group_count or 2
        assign = group_shard_assignment(model.layout, nsuper)
    dpc = DPConfig(
        mode=args.clipping,
        group_assignment=assign,
        num_supergroups=nsuper,
        epsilon=args.epsilon if args.sigma is None else None,
        sigma=args.sigma, delta=args.delta,
        sampling_rate=args.batch / rows.shape[0], steps=args.steps,
        autotune=getattr(args, "autotune", "on") != "off",
        adaptive=not args.fixed_thresholds,
        init_threshold=args.init_threshold,
        target_quantile=args.quantile,
        quantile_budget_fraction=args.quantile_budget,
        noise_strategy=args.noise_strategy,
        microbatches=args.microbatches,
        backend=args.backend,
        execution=args.execution,
    )
    sched = optim.linear_decay(args.lr, args.steps, warmup_steps=args.steps // 20)
    if args.optimizer == "adam":
        opt = optim.adam(sched)
    elif args.optimizer == "adamw":
        opt = optim.adamw(sched)
    else:
        opt = optim.sgd(sched, momentum=0.9)
    init_fn, step_fn, plan = make_dp_train_step(
        model.loss_fn, getattr(model, "dp_spec", model.spec), model.layout,
        opt, dpc, batch_size=args.batch,
        trainable_key=getattr(model, "trainable_key", None), mesh=mesh)
    return cfg, model, rows, sampler, init_fn, step_fn, plan, mesh


def build_arg_parser(**kwargs) -> argparse.ArgumentParser:
    """The training CLI surface, shared with the service daemon
    (repro.launch.service extends this parser with ledger/fault flags)."""
    ap = argparse.ArgumentParser(**kwargs)
    ap.add_argument("--arch", default="tiny",
                    choices=ARCH_IDS + ["tiny"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--variant", default=None)
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="keep only the first N layers of the config "
                         "(depth cut; every width stays as published)")
    ap.add_argument("--clipping", default="per_layer")
    ap.add_argument("--execution", default="bk", choices=["bk", "twopass"],
                    help="flat/group clipping execution: bk runs ONE "
                         "backprop and contracts cached ghost residuals in "
                         "an epilogue (core.bk); twopass is the reference "
                         "two-backward driver")
    ap.add_argument("--epsilon", type=float, default=8.0)
    ap.add_argument("--delta", type=float, default=1e-5)
    ap.add_argument("--sigma", type=float, default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--docs", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lora-rank", type=int, default=0)
    ap.add_argument("--fixed-thresholds", action="store_true")
    ap.add_argument("--init-threshold", type=float, default=1.0)
    ap.add_argument("--quantile", type=float, default=0.5)
    ap.add_argument("--quantile-budget", type=float, default=0.01)
    ap.add_argument("--noise-strategy", default="global")
    ap.add_argument("--group-count", type=int, default=None,
                    help="per_group clipping without --mesh: number of "
                         "virtual model shards whose ownership defines the "
                         "supergroups (launch.sharding."
                         "group_shard_assignment; default 2). With --mesh "
                         "the assignment always comes from the mesh.")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="execute the step under shard_map on a "
                         "(data=D, model=M) mesh (e.g. 2x4; needs D*M "
                         "devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8). "
                         "Batch shards over data; params are STORED "
                         "model-sharded per launch.sharding rules; "
                         "per_group becomes true per-device clipping.")
    ap.add_argument("--backend", default="auto",
                    choices=["xla", "pallas", "auto"],
                    help="ghost-op engine (repro.kernels.backend): xla "
                         "reference paths, pallas kernels (interpret mode "
                         "off-TPU — slow, validation only), or auto "
                         "measured/cost-model dispatch")
    ap.add_argument("--autotune", default="on", choices=["on", "off"],
                    help="on: auto consults the measured autotune table "
                         "for this topology (repro.kernels.autotune; "
                         "pre-warm with `python -m repro.kernels.autotune "
                         "--sweep`); off: static cost model only")
    ap.add_argument("--cache", default="on", choices=["on", "off"],
                    help="persistent compilation cache "
                         "(repro.launch.compile_cache): warm starts "
                         "deserialize compiled step programs instead of "
                         "recompiling")
    ap.add_argument("--cache-dir", default=None,
                    help="cache root for the autotune table AND the "
                         "compile cache (default <repo>/.cache or "
                         "$REPRO_CACHE_DIR)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest VERIFIED checkpoint in "
                         "--checkpoint-dir (params, opt state, thresholds, "
                         "and the Poisson sampler RNG state all restore, so "
                         "the run continues the exact sample stream; torn "
                         "checkpoints are skipped). For the full crash-safe "
                         "service with a persistent privacy ledger use "
                         "repro.launch.service.")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def jit_step(step_fn, model, mesh):
    """jit the step with donated carry state (and model-sharded params
    in/out when a mesh is given) — shared by train.py and the service."""
    if mesh is not None:
        # weights are STORED model-sharded between steps (memory: 1/M per
        # device); the shard_map entry all-gathers them — weight traffic,
        # classified separately from norm traffic by analysis.hlo
        from repro.launch.sharding import params_shardings
        pshard = params_shardings(model.spec, mesh)
        return jax.jit(step_fn,
                       in_shardings=(pshard, None, None, None, None),
                       out_shardings=(pshard, None, None, None),
                       donate_argnums=(0, 1, 2))
    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def setup_caches(args) -> None:
    """Enable the persistent compile cache and install the autotune table
    per the shared --cache/--autotune/--cache-dir flags (train, service,
    and serve all start here). An unusable cache root is reported and
    degrades to a cold compile; a missing or stale autotune table to the
    static cost model."""
    from repro.kernels import autotune
    from repro.launch import compile_cache
    if getattr(args, "cache", "on") != "off":
        compile_cache.enable(getattr(args, "cache_dir", None))
    if getattr(args, "autotune", "on") != "off":
        autotune.install_default(getattr(args, "cache_dir", None))


def record_cache_program(args, *, entry: str, arch: str) -> None:
    """Stamp this entry point's semantic program key into the cache index
    (observability: which programs a warmed image actually covers)."""
    from repro.launch import compile_cache
    if getattr(args, "cache", "on") == "off":
        return
    import jax as _jax
    compile_cache.record_program({
        "entry": entry, "arch": arch,
        "mesh": getattr(args, "mesh", None) or "none",
        "backend": getattr(args, "backend", "auto"),
        "execution": getattr(args, "execution", "bk"),
        "clipping": getattr(args, "clipping", None) or "none",
        "jax_version": _jax.__version__,
    }, root=getattr(args, "cache_dir", None))


@dataclasses.dataclass
class TrainRun:
    """What one run of the training CLI did."""

    cfg: Any
    mesh: Any
    compiled: Any  # the AOT-compiled step (`.as_text()` is its HLO)
    compile_s: float  # lower + compile (or cache load) of the step
    step_s: list  # wall seconds per step, each ended by its metrics fetch
    metrics: list  # per step: {loss, clip_fraction, mean_threshold, ...}
    params: Any  # the final parameters


def run(argv=None) -> TrainRun:
    """Parse `argv` like the CLI and train; returns what the run did."""
    args = build_arg_parser().parse_args(argv)
    setup_caches(args)

    (cfg, model, rows, sampler, init_fn, step_fn, plan,
     mesh) = build_everything(args)
    record_cache_program(args, entry="train", arch=cfg.name)
    params = init_params(model.spec, jax.random.PRNGKey(args.seed))
    opt_state, dp_state = init_fn(params)
    start_step = 0
    if args.resume:
        if not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        from repro.checkpoint import load_latest_checkpoint
        found = load_latest_checkpoint(
            args.checkpoint_dir,
            {"params": params, "opt_state": opt_state, "dp_state": dp_state})
        if found is not None:
            start_step, tree, manifest = found
            params, opt_state, dp_state = (
                tree["params"], tree["opt_state"], tree["dp_state"])
            meta = manifest.get("meta") or {}
            if "sampler" in meta:
                sampler.restore(meta["sampler"])
            print(f"# resumed from step {start_step}")
    # donate params/opt_state/dp_state: they update in place every step, so
    # XLA aliases them input->output instead of double-buffering the model
    step = jit_step(step_fn, model, mesh)
    key = jax.random.PRNGKey(args.seed + 1)

    print(f"# arch={cfg.name} layers={cfg.num_layers} "
          f"params={model.num_params:,} "
          f"groups={model.layout.num_groups} mode={plan.config.mode} "
          f"backend={plan.config.backend} "
          f"mesh={dict(mesh.shape) if mesh is not None else None} "
          f"sigma={plan.sigma:.3f} sigma_new={plan.sigma_new:.3f} "
          f"sigma_b={plan.sigma_b:.3f}")
    compiled, compile_s = None, 0.0
    step_s, metrics = [], []
    for i in range(start_step, args.steps):
        idx = sampler.next_indices()
        batch = make_lm_batch(rows, idx, args.batch)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state = (params, opt_state, dp_state, batch, key)
        if compiled is None:
            # compile ahead of the first step, so compile time and step
            # time are reported apart
            t0 = time.perf_counter()
            compiled = step.lower(*state).compile()
            compile_s = time.perf_counter() - t0
            print(f"# step compiled in {compile_s:.2f}s", flush=True)
        t0 = time.perf_counter()
        # the step's outputs may come back sharded differently from the
        # initial state: place every argument where the program expects it
        state = jax.device_put(state, compiled.input_shardings[0])
        params, opt_state, dp_state, met = compiled(*state)
        # the scalar readings only: the per-example norms stay on the device
        met = {k: float(v) for k, v in jax.device_get(
            met._replace(norms_sq=None, tied_cross=None))._asdict().items()
            if v is not None}
        step_s.append(time.perf_counter() - t0)
        metrics.append(met)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss {met['loss']:.4f} "
                  f"clip_frac {met['clip_fraction']:.3f} "
                  f"thr {met['mean_threshold']:.4f} "
                  f"gnorm {met['grad_norm']:.4f}", flush=True)
    ran = len(step_s)
    wall = sum(step_s)
    if plan.config.private and ran:
        eps = compute_epsilon(sigma=plan.sigma,
                              sampling_rate=plan.config.sampling_rate,
                              steps=args.steps, delta=args.delta)
        print(f"# spent epsilon={eps:.3f} (delta={args.delta}) "
              f"in {args.steps} steps, {wall:.1f}s "
              f"({wall/ran*1e3:.1f} ms/step)")
    if args.checkpoint_dir:
        path = save_checkpoint(
            args.checkpoint_dir, args.steps,
            {"params": params, "opt_state": opt_state, "dp_state": dp_state},
            meta={"sampler": sampler.state()})
        print(f"# checkpoint: {path}")
    return TrainRun(cfg=cfg, mesh=mesh, compiled=compiled,
                    compile_s=compile_s, step_s=step_s, metrics=metrics,
                    params=params)


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

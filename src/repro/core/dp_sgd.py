"""DP optimization: Algorithm 1 (adaptive per-layer DP-SGD) and friends.

Wires together:  clipping driver (core.clipping)  +  private quantile
estimation (core.quantile)  +  noise allocation (core.noise)  +  RDP
accounting incl. the Prop 3.1 budget split (core.accounting)  +  any
first-order optimizer with an optax-like (init, update) interface
(repro.optim) — the paper notes the scheme applies to DP-Adam etc.

The factory precomputes all python-float accounting at build time; the
returned step function is pure and jit/pjit-friendly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accounting, noise as noise_lib
from repro.core.clipping import (LossFn, base_mode, check_tied_mode,
                                 dp_clipped_gradients)
from repro.kernels import backend as ghost_backend
from repro.core.quantile import QuantileState, clip_counts, init_quantile_state, update_thresholds
from repro.core.spec import GroupLayout, P, SpecTree, _walk, stable_hash


@dataclasses.dataclass(frozen=True)
class DPConfig:
    """Configuration of the private learning run.

    Knob groups, with defaults and units (CLI spellings in parens refer
    to `repro.launch.train` / `repro.launch.service` flags):

    * **Clipping** — `mode` (`--clipping`, default `per_layer`) picks
      the paper's clipping granularity; `execution` (`--execution`,
      default `bk`) picks how the flat/group modes compute the clipped
      sums (one backprop + BK epilogue vs the two-backward reference).
      Accounting is identical across executions — the choice is purely
      compute/memory.
    * **Privacy budget** — `epsilon` (target, calibrated over `steps`
      optimizer steps at Poisson `sampling_rate` = B/N and `delta`);
      set `sigma` (noise multiplier, units of the clipping threshold)
      to skip calibration entirely. All python floats, resolved once at
      plan-build time.
    * **Thresholds** — `adaptive=True` tracks the `target_quantile` of
      per-example norms with learning rate `quantile_lr`, spending
      `quantile_budget_fraction` of the budget on the clip-count
      release (Prop 3.1 split); `init_threshold` is C(0) in gradient-
      norm units (also the fixed C when `adaptive=False`).
    * **per_group** — `group_assignment` maps each `GroupLayout` group
      to a supergroup; `num_supergroups` pads the count (the sharded
      engine sets it to the `--mesh` model-axis size so every shard
      owns a well-defined threshold slot).
    * **Ghost-op backend** — `backend` (`--backend`, default `auto`)
      and `autotune` (`--autotune`, default on) select the kernel
      engine for norms/clipped-sums; scoped around the step so jitted
      traces capture it statically. See `repro.kernels.backend`.
    * **Scale-out** — `microbatches` (default 1) accumulates gradients
      without changing the released quantity (clipping commutes with
      accumulation); `batch_axes` names the mesh axes of the batch dim,
      required when `microbatches > 1` under pjit (pins the microbatch
      split off the data plane). The `--mesh` itself is passed to
      `make_dp_train_step(mesh=...)`, not stored here.
    """

    mode: str = "per_layer"  # non_private|per_layer|ghost_flat|per_group|
    #   naive_flat (+ ghost_flat_twopass|per_group_twopass reference modes)
    execution: str = "bk"  # bk | twopass — how the flat/group modes run:
    #   bk (book-keeping, core.bk) caches ghost residuals during the single
    #   norm backprop and contracts them in an epilogue; twopass is the
    #   historical two-backward reference. Ignored by the other modes; a
    #   `*_twopass` mode name forces twopass.
    # --- privacy budget ---
    epsilon: float | None = 8.0
    delta: float = 1e-5
    sampling_rate: float = 0.01  # rho = B / N  (Poisson subsampling)
    steps: int = 1000  # T, for accounting
    sigma: float | None = None  # direct noise-multiplier override (skips calibration)
    # --- thresholds ---
    adaptive: bool = True  # adaptive (quantile-tracked) vs fixed thresholds
    init_threshold: float = 1.0  # C_k(0) (per-layer) or C (flat)
    target_quantile: float = 0.5  # q
    quantile_lr: float = 0.3  # eta (paper uses 0.3 everywhere)
    quantile_budget_fraction: float = 0.01  # r in (0,1)
    # --- noise allocation (Sec 3.3) ---
    noise_strategy: str = "global"  # global | equal_budget | weighted
    # Appendix A.1 protocol: rescale adaptive per-layer thresholds to an
    # equivalent GLOBAL threshold C, i.e. use C_k_eff = C * C_k / ||C||_2.
    # The tracker learns the cross-layer SHAPE; total clipping budget (and
    # hence noise scale) stays comparable to flat clipping at threshold C.
    threshold_rescale: float | None = None
    # --- per_group / per-device mode ---
    group_assignment: tuple[int, ...] | None = None  # layout-group -> supergroup
    num_supergroups: int | None = None  # explicit supergroup count G (else
    #   max(assignment)+1). The sharded engine sets G = model-axis size so a
    #   shard that owns no group still has a (well-defined, idle) threshold.
    # --- ghost-op backend (repro.kernels.backend) ---
    backend: str = "auto"  # xla | pallas | auto — engine for the ghost ops;
    #   scoped around the step function so jitted traces capture it
    #   statically. auto picks the measured argmin per (op, shape bucket)
    #   when an autotune table is installed (repro.kernels.autotune) and
    #   falls back to the static cost model (xla off-TPU) on unmeasured
    #   buckets. None-like inheritance of tunables (outer_max_elems, tile
    #   sizes) comes from the enclosing backend.scoped(...) if any.
    autotune: bool = True  # False pins auto to the static model even with
    #   a table installed (--autotune off)
    # --- misc ---
    noise_dtype: Any = jnp.float32
    microbatches: int = 1  # gradient accumulation (Algorithm 2 structure):
    #   per-example clipping commutes with microbatch accumulation, so the
    #   clipped sums and norms are EXACTLY those of the monolithic batch;
    #   noise is added once per minibatch (Alg. 2 line 6).
    batch_axes: tuple[str, ...] | None = None  # mesh axes of the batch dim.
    #   Needed when microbatches > 1 under pjit: the (B,) -> (nmb, mb) split
    #   is reshard-ambiguous and GSPMD may scatter the data axis across BOTH
    #   new dims (catastrophic per-iteration collectives); this pins the
    #   microbatch dim replicated and the example dim on the data plane.

    @property
    def private(self) -> bool:
        return self.mode != "non_private"


class DPState(NamedTuple):
    qstate: QuantileState  # K (or G) adaptive thresholds
    step: jax.Array  # scalar int32


class StepMetrics(NamedTuple):
    loss: jax.Array
    clip_fraction: jax.Array  # mean over groups of fraction clipped
    mean_threshold: jax.Array
    grad_norm: jax.Array  # norm of the (noised, averaged) update direction
    # what the step clipped with (single-device step; None under a mesh):
    norms_sq: Any = None  # (K, B) per-group per-example squared norms
    tied_cross: Any = None  # (B,) the tied groups' cross term, inside
    #   norms_sq (zeros without a tied group or off the BK capture)


# Named scopes of the step's phases: every op of a step lies under exactly
# one, so a profiler trace or the compiled HLO splits the step by phase
# (repro.analysis.hlo.op_phases). None contains "norm" (that marks norm
# collectives) or starts with the auditor's "dp_noise_add" prefix.
PHASE_CLIP = "dp_phase_clip"  # forward, backward, norms, clipped sums
PHASE_NOISE = "dp_phase_noise"  # clip counts, noise stds, the noise draw
PHASE_UPDATE = "dp_phase_update"  # keys, thresholds, optimizer, quantiles


@dataclasses.dataclass(frozen=True)
class DPPlan:
    """Everything precomputed at build time (python floats, accounting)."""

    config: DPConfig
    num_noise_groups: int  # K for per_layer, 1 for flat, G for per_group
    sigma: float  # total-budget noise multiplier (no quantile split)
    sigma_b: float  # clip-count noise multiplier (0 if not adaptive)
    sigma_new: float  # gradient noise multiplier after the Prop 3.1 split
    group_dims: np.ndarray  # (num_noise_groups,) parameter counts
    sens_mults: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.float32))


def build_plan(cfg: DPConfig, layout: GroupLayout) -> DPPlan:
    if not cfg.private:
        return DPPlan(cfg, 0, 0.0, 0.0, 0.0, np.zeros(0, np.int64))
    mults = layout.sens_mults
    mode = base_mode(cfg.mode)  # accounting is execution-independent
    if mode in ("ghost_flat", "naive_flat"):
        num_groups = 1
        dims = np.array([int(layout.dims.sum())], np.int64)
        mults = np.ones(1, np.float32)
    elif mode == "per_group":
        if cfg.group_assignment is None:
            raise ValueError("per_group mode requires group_assignment")
        assign = np.asarray(cfg.group_assignment)
        if assign.shape != (layout.num_groups,):
            raise ValueError(
                f"group_assignment must have shape ({layout.num_groups},)")
        num_groups = (cfg.num_supergroups if cfg.num_supergroups
                      else int(assign.max()) + 1)
        if num_groups <= int(assign.max()):
            raise ValueError("num_supergroups smaller than assignment range")
        dims = np.zeros(num_groups, np.int64)
        np.add.at(dims, assign, layout.dims)
        m = np.ones(num_groups, np.float32)
        np.maximum.at(m, assign, layout.sens_mults)
        mults = m
    else:  # per_layer (incl. per-shard blocked layouts)
        num_groups = layout.num_groups
        dims = layout.dims
    if cfg.sigma is not None:
        sigma = float(cfg.sigma)
    else:
        if cfg.epsilon is None:
            raise ValueError("need epsilon or sigma")
        sigma = accounting.calibrate_sigma(
            target_eps=cfg.epsilon, sampling_rate=cfg.sampling_rate,
            steps=cfg.steps, delta=cfg.delta)
    if cfg.adaptive:
        sigma_b = accounting.sigma_b_for_fraction(
            sigma, num_groups, cfg.quantile_budget_fraction)
        split = accounting.split_noise_multiplier(sigma, sigma_b, num_groups)
        sigma_new = split.sigma_new
    else:
        sigma_b, sigma_new = 0.0, sigma
    return DPPlan(cfg, num_groups, sigma, sigma_b, sigma_new, dims, mults)


def init_dp_state(plan: DPPlan) -> DPState:
    cfg = plan.config
    k = max(plan.num_noise_groups, 1)
    qstate = init_quantile_state(
        np.full((k,), cfg.init_threshold, np.float32),
        target_quantile=cfg.target_quantile,
        lr=cfg.quantile_lr,
        sigma_b=plan.sigma_b if cfg.adaptive else 0.0,
    )
    return DPState(qstate=qstate, step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Noise application (spec-aware: stacked and blocked leaves).
# ---------------------------------------------------------------------------


def add_noise_to_grads(
    spec: SpecTree,
    layout: GroupLayout,
    grads: Any,
    stds: jax.Array,  # (num_layout_groups,) per-LAYOUT-group std
    key: jax.Array,
    dtype=jnp.float32,
) -> Any:
    """grads + N(0, std_k²) with the right std per (possibly stacked/blocked)
    parameter leaf. `stds` is indexed by layout-group flat id."""

    def one_leaf(node, g, path):
        gname = layout._leaf_group[path]
        grp = layout.group(gname)
        piece = jax.lax.dynamic_slice_in_dim(stds, grp.offset, grp.count)
        piece = piece.reshape(grp.stack_shape or ())
        leaf_key = jax.random.fold_in(
            key, stable_hash("/".join(path)))
        z = jax.random.normal(leaf_key, g.shape, dtype)
        if node.blocks > 1:
            # std varies per column block of the last axis
            m = node.blocks
            rest = g.shape[node.stack:-1]
            std_full = piece.reshape(
                grp.stack_shape[:-1] + (1,) * len(rest) + (m, 1))
            zb = z.reshape(g.shape[:-1] + (m, g.shape[-1] // m))
            zb = zb * std_full
            z = zb.reshape(g.shape)
        else:
            std_full = piece.reshape(
                (grp.stack_shape or ()) + (1,) * (g.ndim - len(grp.stack_shape)))
            z = z * std_full
        return (g.astype(dtype) + z).astype(g.dtype)

    def walk(node, g, path):
        if isinstance(node, P):
            # dp_noise_add:<leaf> marks this leaf's (single) draw for the
            # static auditor (repro.analysis.jaxpr_taint); '.'-joined so
            # the leaf name stays one name-stack segment
            with jax.named_scope("dp_noise_add:" + ".".join(path)):
                return one_leaf(node, g, path)
        return {k2: walk(node[k2], g[k2], path + (k2,)) for k2 in node}

    return walk(spec, grads, ())


def _layout_stds(plan: DPPlan, layout: GroupLayout,
                 thresholds: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-layout-group noise stds + the per-noise-group thresholds used.

    For flat modes the single noise group covers every layout group; for
    per_group mode the supergroup std is broadcast to its members.
    """
    cfg = plan.config
    mode = base_mode(cfg.mode)
    dims = jnp.asarray(plan.group_dims, jnp.float32)
    mults = jnp.asarray(plan.sens_mults, jnp.float32)
    stds_group = noise_lib.group_noise_stds(
        cfg.noise_strategy, thresholds * mults, dims, plan.sigma_new)  # (G,)
    if mode in ("ghost_flat", "naive_flat"):
        return jnp.broadcast_to(stds_group, (layout.num_groups,)), thresholds
    if mode == "per_group":
        assign = jnp.asarray(np.asarray(cfg.group_assignment), jnp.int32)
        return stds_group[assign], thresholds
    return stds_group, thresholds


# ---------------------------------------------------------------------------
# The train-step factory.
# ---------------------------------------------------------------------------


def _effective_thresholds(cfg: DPConfig, plan: DPPlan, dp_state: DPState):
    """Tracked thresholds, with the Appendix-A.1 global rescale applied."""
    thresholds = dp_state.qstate.thresholds  # (G,)
    if cfg.threshold_rescale is not None and plan.num_noise_groups > 1:
        thresholds = (cfg.threshold_rescale * thresholds
                      / jnp.sqrt(jnp.sum(thresholds**2) + 1e-20))
    return thresholds


def _apply_update(cfg: DPConfig, plan: DPPlan, optimizer, trainable_key,
                  batch_size, params, opt_state, dp_state, noised, counts,
                  thresholds, loss, k_q, norms_sq=None, tied_cross=None):
    """Post-clipping tail shared by the single-device and sharded steps:
    gradient averaging, optimizer update, private quantile update, metrics.
    `noised` must be the (noised) SUMMED clipped grads over the full batch;
    `counts` the full-batch clip counts — both already globally reduced in
    the sharded case."""
    tgrads = noised if trainable_key is None else noised[trainable_key]
    tparams = params if trainable_key is None else params[trainable_key]
    grad_avg = jax.tree_util.tree_map(
        lambda g: (g / batch_size).astype(g.dtype), tgrads)
    updates, new_opt_state = optimizer.update(grad_avg, opt_state, tparams)
    new_tparams = jax.tree_util.tree_map(lambda p, u: p + u, tparams,
                                         updates)
    new_params = (new_tparams if trainable_key is None
                  else {**params, trainable_key: new_tparams})

    qstate = dp_state.qstate
    if cfg.private and cfg.adaptive:
        qstate = update_thresholds(qstate, counts, batch_size, k_q)
    new_dp_state = DPState(qstate=qstate, step=dp_state.step + 1)

    gn = jnp.sqrt(sum(
        jnp.sum(jnp.square(l.astype(jnp.float32)))
        for l in jax.tree_util.tree_leaves(grad_avg)))
    metrics = StepMetrics(
        loss=loss,
        clip_fraction=1.0 - jnp.mean(counts) / batch_size,
        mean_threshold=jnp.mean(thresholds),
        grad_norm=gn,
        norms_sq=norms_sq,
        tied_cross=tied_cross,
    )
    return new_params, new_opt_state, new_dp_state, metrics


def make_dp_train_step(
    loss_fn: LossFn,
    spec: SpecTree,
    layout: GroupLayout,
    optimizer: Any,  # repro.optim optimizer (init/update)
    cfg: DPConfig,
    *,
    batch_size: int,
    trainable_key: str | None = None,
    mesh: Any = None,
) -> tuple[Callable, Callable, DPPlan]:
    """Build the jittable private training step. Returns
    (init_fn, step_fn, plan).

    init_fn(params) -> (opt_state, dp_state)
    step_fn(params, opt_state, dp_state, batch, key)
        -> (params, opt_state, dp_state, StepMetrics)

    All accounting (sigma calibration, the Prop 3.1 quantile budget
    split, group dimensioning) happens HERE, once, in python floats —
    the returned `plan` records it and step_fn is pure. Refuses at
    build time to train a spec whose leaf paths crc32-collide into the
    same noise key.

    batch_size: the GLOBAL examples-per-step B (even under `mesh`),
    used for averaging and the sampling-rate check; must divide by
    `cfg.microbatches`.

    trainable_key: restrict training to `params[trainable_key]` (e.g.
    `"lora"` for DP-LoRA fine-tunes — the rest of the tree is frozen,
    carried through untouched, and spends no privacy budget). The
    training service publishes adapter-only checkpoints exactly when
    this is `"lora"`.

    mesh: a (data[, pod], model) device mesh. When given, step_fn is built
    under `shard_map` — batch sharded over the data plane, clipping
    bookkeeping distributed over the model axis by shard ownership
    (launch.sharding.group_shard_assignment), per-device (`per_group`)
    norms and clip factors shard-local, `ghost_flat` paying its one (B,)
    model-axis norm psum, and the BK epilogue interleaving each layer's
    gradient psum with the next layer's contraction. `batch_size` stays the
    GLOBAL batch. jit the returned step_fn as usual (optionally with
    launch.sharding params_shardings as in_shardings to keep the weights
    STORED model-sharded between steps).
    """
    # a tied embedding / LM head trains only where its exact norm exists
    check_tied_mode(layout, cfg.mode, cfg.execution,
                    trainable_key=trainable_key, sharded=mesh is not None)
    if cfg.private:
        # static PRNG-safety gate (see noise.check_leaf_key_collisions):
        # two leaf paths crc32-folding to the same key would draw
        # IDENTICAL noise — refuse at plan-build time, naming both
        noise_lib.check_leaf_key_collisions(
            ["/".join(p) for p, _ in _walk(spec)])
    if mesh is not None:
        return _make_sharded_step(loss_fn, spec, layout, optimizer, cfg,
                                  batch_size=batch_size,
                                  trainable_key=trainable_key, mesh=mesh)
    plan = build_plan(cfg, layout)
    assign = (jnp.asarray(np.asarray(cfg.group_assignment), jnp.int32)
              if cfg.group_assignment is not None else None)

    def init_fn(params):
        tp = params if trainable_key is None else params[trainable_key]
        return optimizer.init(tp), init_dp_state(plan)

    nmb = cfg.microbatches
    mb_size = batch_size // nmb
    if batch_size % nmb:
        raise ValueError("batch_size must divide by microbatches")

    mode = base_mode(cfg.mode)
    execution = "twopass" if cfg.mode.endswith("_twopass") else cfg.execution

    def _clip(params, batch, thresholds):
        """Clipped sums + norms + tied cross term, accumulated over
        microbatches (exact)."""
        def one(batch_mb):
            if mode == "non_private":
                return dp_clipped_gradients(
                    loss_fn, params, batch_mb, layout, mode="non_private",
                    batch_size=mb_size, trainable_key=trainable_key)
            if mode == "per_layer":
                return dp_clipped_gradients(
                    loss_fn, params, batch_mb, layout, mode="per_layer",
                    batch_size=mb_size, thresholds=thresholds,
                    trainable_key=trainable_key)
            if mode in ("ghost_flat", "naive_flat"):
                return dp_clipped_gradients(
                    loss_fn, params, batch_mb, layout, mode=mode,
                    batch_size=mb_size, flat_threshold=thresholds[0],
                    trainable_key=trainable_key, execution=execution)
            return dp_clipped_gradients(
                loss_fn, params, batch_mb, layout, mode="per_group",
                batch_size=mb_size, group_assignment=assign,
                group_thresholds=thresholds, trainable_key=trainable_key,
                execution=execution)

        if nmb == 1:
            return one(batch)

        def _split_leaf(x):
            y = x.reshape((nmb, mb_size) + x.shape[1:])
            if cfg.batch_axes is not None:
                from jax.sharding import PartitionSpec as _PS
                y = jax.lax.with_sharding_constraint(
                    y, _PS(None, cfg.batch_axes))
            return y

        split = jax.tree_util.tree_map(_split_leaf, batch)

        def body(acc, batch_mb):
            res = one(batch_mb)
            g_acc, loss_acc = acc
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, res.grads)
            return (g_acc, loss_acc + res.loss), (res.norms_sq,
                                                  res.tied_cross)

        tp = params if trainable_key is None else {
            trainable_key: params[trainable_key]}
        g0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), tp)
        (g_sum, loss_sum), (norms, cross) = jax.lax.scan(body, (g0, 0.0),
                                                         split)
        norms = jnp.moveaxis(norms, 0, 1).reshape(layout.num_groups,
                                                  batch_size)
        from repro.core.clipping import ClipResult
        g_sum = jax.tree_util.tree_map(
            lambda a, x: a.astype(x.dtype), g_sum, tp)
        return ClipResult(g_sum, norms, loss_sum / nmb,
                          cross.reshape(batch_size))

    def step_fn(params, opt_state, dp_state, batch, key):
        # scoped (not global) engine: the jitted trace of this function
        # captures cfg.backend statically; tunables inherit from any
        # enclosing backend.scoped(...) (e.g. the dry-run's outer cap).
        with ghost_backend.scoped(cfg.backend, autotune=cfg.autotune):
            return _step(params, opt_state, dp_state, batch, key)

    def _step(params, opt_state, dp_state, batch, key):
        with jax.named_scope(PHASE_UPDATE):
            k_noise, k_q = jax.random.split(
                jax.random.fold_in(key, dp_state.step))
            thresholds = _effective_thresholds(cfg, plan, dp_state)

        with jax.named_scope(PHASE_CLIP):
            res = _clip(params, batch, thresholds)
        with jax.named_scope(PHASE_NOISE):
            if mode == "non_private":
                noised = res.grads
                counts = jnp.zeros_like(thresholds)
            else:
                if mode == "per_layer":
                    counts = clip_counts(res.norms_sq, thresholds)
                elif mode in ("ghost_flat", "naive_flat"):
                    counts = clip_counts(jnp.sum(res.norms_sq, axis=0)[None],
                                         thresholds)
                else:  # per_group
                    super_norms = jax.ops.segment_sum(
                        res.norms_sq, assign,
                        num_segments=plan.num_noise_groups)
                    counts = clip_counts(super_norms, thresholds)
                stds, _ = _layout_stds(plan, layout, thresholds)
                noised = add_noise_to_grads(spec, layout, res.grads, stds,
                                            k_noise, cfg.noise_dtype)

        with jax.named_scope(PHASE_UPDATE):
            return _apply_update(cfg, plan, optimizer, trainable_key,
                                 batch_size, params, opt_state, dp_state,
                                 noised, counts, thresholds, res.loss, k_q,
                                 res.norms_sq, res.tied_cross)

    return init_fn, step_fn, plan


# ---------------------------------------------------------------------------
# The sharded (shard_map) train-step factory.
# ---------------------------------------------------------------------------


def _make_sharded_step(loss_fn, spec, layout, optimizer, cfg: DPConfig, *,
                       batch_size: int, trainable_key: str | None, mesh):
    """`make_dp_train_step` under manual SPMD on a (data[, pod], model) mesh.

    See `repro.core.clipping.sharded_clipped_gradients` for the per-mode
    communication contract. The quantile update, noise draw and optimizer
    run replicated (identical keys on every device), so outputs are
    replicated and out_specs are fully unsharded.
    """
    # lazy: keep core -> launch imports out of module import time
    from jax.sharding import PartitionSpec as PS
    from repro.core.clipping import sharded_clipped_gradients
    from repro.launch.mesh import data_axes as _data_axes, named_shard_map
    from repro.launch.sharding import group_shard_assignment

    dax = tuple(_data_axes(mesh))
    model_ax = "model"
    d_size = int(np.prod([mesh.shape[a] for a in dax]))
    m_size = int(mesh.shape[model_ax])
    if batch_size % d_size:
        raise ValueError(f"global batch {batch_size} must divide across the "
                         f"{d_size}-way data plane")
    b_local = batch_size // d_size
    nmb = cfg.microbatches
    if b_local % nmb:
        raise ValueError("per-shard batch must divide by microbatches")
    mb_local = b_local // nmb

    mode = base_mode(cfg.mode)
    execution = "twopass" if cfg.mode.endswith("_twopass") else cfg.execution
    if mode not in ("non_private", "per_layer", "ghost_flat", "per_group"):
        raise ValueError(
            f"sharded execution supports non_private/per_layer/ghost_flat/"
            f"per_group, not {mode!r} (naive_flat is a single-device oracle)")
    own_assign = group_shard_assignment(layout, m_size)
    if mode == "per_group":
        if (cfg.group_assignment is not None
                and tuple(cfg.group_assignment) != own_assign):
            raise ValueError(
                "sharded per_group IS per-device clipping: group_assignment "
                "must equal the model-axis shard ownership (leave it unset "
                "to derive it via launch.sharding.group_shard_assignment)")
        cfg = dataclasses.replace(cfg, group_assignment=own_assign,
                                  num_supergroups=m_size)
    plan = build_plan(cfg, layout)
    shard_assign = jnp.asarray(np.asarray(own_assign), jnp.int32)

    def init_fn(params):
        tp = params if trainable_key is None else params[trainable_key]
        return optimizer.init(tp), init_dp_state(plan)

    def _one(params, batch_mb, thresholds, bsz):
        kw = dict(batch_size=bsz, data_size=d_size, data_axes=dax,
                  model_axis=model_ax, trainable_key=trainable_key)
        if mode == "non_private":
            return sharded_clipped_gradients(loss_fn, params, batch_mb,
                                             layout, mode=mode, **kw)
        if mode == "per_layer":
            return sharded_clipped_gradients(
                loss_fn, params, batch_mb, layout, mode=mode,
                thresholds=thresholds, **kw)
        if mode == "ghost_flat":
            return sharded_clipped_gradients(
                loss_fn, params, batch_mb, layout, mode=mode,
                flat_threshold=thresholds[0], shard_assignment=shard_assign,
                execution=execution, **kw)
        return sharded_clipped_gradients(
            loss_fn, params, batch_mb, layout, mode="per_group",
            group_thresholds=thresholds, shard_assignment=shard_assign,
            execution=execution, **kw)

    def _clip(params, batch, thresholds):
        if nmb == 1:
            return _one(params, batch, thresholds, b_local)
        # microbatch accumulation: the per-microbatch grads come back
        # already globally psum'd, so plain accumulation stays exact
        split = jax.tree_util.tree_map(
            lambda x: x.reshape((nmb, mb_local) + x.shape[1:]), batch)
        tp = params if trainable_key is None else {
            trainable_key: params[trainable_key]}
        g0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), tp)
        c0 = jnp.zeros((max(plan.num_noise_groups, 1)
                        if mode != "per_layer" else layout.num_groups,),
                       jnp.float32)

        def body(acc, batch_mb):
            res = _one(params, batch_mb, thresholds, mb_local)
            g_acc, loss_acc, cnt_acc = acc
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, res.grads)
            return ((g_acc, loss_acc + res.loss, cnt_acc + res.counts),
                    res.norms_sq)

        (g_sum, loss_sum, counts), norms = jax.lax.scan(
            body, (g0, 0.0, c0), split)
        norms = jnp.moveaxis(norms, 0, 1).reshape(layout.num_groups, b_local)
        from repro.core.clipping import ShardedClipResult
        g_sum = jax.tree_util.tree_map(
            lambda a, x: a.astype(x.dtype), g_sum, tp)
        return ShardedClipResult(g_sum, norms, loss_sum / nmb, counts)

    def _body(params, opt_state, dp_state, batch, key):
        with ghost_backend.scoped(cfg.backend, autotune=cfg.autotune):
            with jax.named_scope(PHASE_UPDATE):
                k_noise, k_q = jax.random.split(
                    jax.random.fold_in(key, dp_state.step))
                thresholds = _effective_thresholds(cfg, plan, dp_state)

            with jax.named_scope(PHASE_CLIP):
                res = _clip(params, batch, thresholds)
            with jax.named_scope(PHASE_NOISE):
                if mode == "non_private":
                    noised = res.grads
                    counts = jnp.zeros_like(thresholds)
                else:
                    counts = res.counts  # globally reduced by the clip driver
                    stds, _ = _layout_stds(plan, layout, thresholds)
                    noised = add_noise_to_grads(spec, layout, res.grads,
                                                stds, k_noise,
                                                cfg.noise_dtype)

            with jax.named_scope(PHASE_UPDATE):
                return _apply_update(cfg, plan, optimizer, trainable_key,
                                     batch_size, params, opt_state, dp_state,
                                     noised, counts, thresholds, res.loss,
                                     k_q)

    step_fn = named_shard_map(
        _body, mesh,
        in_specs=(PS(), PS(), PS(), PS(dax), PS()),
        out_specs=(PS(), PS(), PS(), PS()))
    return init_fn, step_fn, plan

"""Clipping-mode drivers: one mechanism, five modes, two executions.

Every model exposes   loss_fn(params, batch, thresholds) -> (B,) per-example
losses, where `thresholds` is the GroupLayout dict of encoded per-example
threshold vectors consumed by the dp_* primitives. The drivers below turn
that into (clipped summed grads, per-example norms², clip counts):

  non_private : thresholds=+inf; one backward pass; standard summed grads.
  per_layer   : the paper's headline (Sec 3.1). ONE backward pass; each
                layer's custom bwd clips with its own C_k the moment the
                cotangent reaches it; norms² come back through the
                threshold cotangents for the quantile update.
  ghost_flat  : flat (ghost) clipping, Li et al. 2022b — the paper's honest
                efficiency baseline. Default execution is BOOK-KEEPING
                (`bk`, Bu et al. 2022 / repro.core.bk): ONE backward pass
                that reads norms² AND caches each layer's ghost residuals,
                then a scale-and-contract epilogue builds the clipped sums
                from the cache once the flat factor is known.
  per_group   : arbitrary partition of layout groups (per-device clipping —
                the paper's Sec 4 GPT-3 recipe: partition = pipeline stages
                / model shards). Same BK execution; pass-1 norms are
                segment-summed per supergroup before the epilogue.
  naive_flat  : Opacus-style oracle — materializes per-example grads with
                jacrev, clips, sums. O(B x params) memory; used as the
                correctness oracle and the Figure-1 "usual flat" baseline.

Executions for the flat/group modes (`execution=` kwarg, also reachable as
explicit `ghost_flat_twopass` / `per_group_twopass` reference modes):

  bk      : one backprop + epilogue (above). Falls back to twopass
            automatically when the layout cannot be captured (a threshold
            leaf consumed at >1 call sites, shared-site params with
            sensitivity_mult > 1 — see bk.probe_recipes). A tied embedding
            / LM head is captured exactly and never falls back: only BK
            sees the cross term of its two uses (`check_tied_mode`).
  twopass : the historical reference — pass 1 reads norms² only (weight
            contractions dead-code-eliminated), pass 2 applies the
            per-example factor via direct-scale thresholds.

per_shard is expressed through the layout itself (blocked groups, see
core.spec / dp_linear_blocked) and then driven as per_layer — each block is
simply its own group with a local norm.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bk
from repro.core.quantile import clip_counts
from repro.core.spec import GroupLayout, P
from repro.kernels import backend

MODES = ("non_private", "per_layer", "ghost_flat", "per_group", "naive_flat",
         "ghost_flat_twopass", "per_group_twopass")
EXECUTIONS = ("bk", "twopass")


def base_mode(mode: str) -> str:
    """Strip the `_twopass` reference-execution suffix off a mode name."""
    suffix = "_twopass"
    return mode[: -len(suffix)] if mode.endswith(suffix) else mode

LossFn = Callable[[Any, Any, dict], jax.Array]  # (params, batch, thresholds) -> (B,)


class ClipResult(NamedTuple):
    grads: Any            # pytree like params: clipped summed grads
    norms_sq: jax.Array   # (K, B) per-group per-example squared norms
    loss: jax.Array       # scalar mean per-example loss (pre-clipping)
    tied_cross: Any = None  # (B,) the tied groups' cross term, already
    #   inside norms_sq (zeros without a tied group or off the BK capture)


def check_tied_mode(layout: GroupLayout, mode: str, execution: str, *,
                    trainable_key: str | None = None,
                    sharded: bool = False) -> None:
    """Refuse, before anything is traced, a mode that cannot clip a tied
    embedding / LM head group (GroupLayout.tied_groups) by its exact
    per-example norm ‖G_e + G_hᵀ‖². The step factory and both clipping
    entry points call it; bk.probe_recipes refuses a tied layout that BK
    cannot capture.

    ghost_flat / per_group through BK see both uses' residuals and add the
    cross term (core.bk); non_private needs no norm; naive_flat
    differentiates each example's loss by the leaf itself, so its norm is
    exact. The others would clip with the per-use norms ‖G_e‖² + ‖G_h‖²,
    which leave the cross term out and misstate the sensitivity."""
    if not layout.tied_groups:
        return
    why = tied_refusal(mode, execution, sharded=sharded)
    if (why is None and base_mode(mode) in ("ghost_flat", "per_group")
            and not _bk_capture_ok(layout, trainable_key)):
        why = (f"BK cannot capture a layout that is not the trainable tree "
               f"{trainable_key!r}")
    if why is not None:
        raise ValueError(
            f"clipping mode {mode!r} (execution {execution!r}) cannot clip "
            f"the tied group(s) {list(layout.tied_groups)} by their exact "
            f"norm: {why}. Use ghost_flat or per_group with execution='bk' "
            "on one device.")


def tied_refusal(mode: str, execution: str, *,
                 sharded: bool = False) -> str | None:
    """Why `mode` cannot clip a tied group by its exact norm; None if it
    can (see check_tied_mode)."""
    if mode.endswith("_twopass"):
        mode, execution = base_mode(mode), "twopass"
    if mode in ("non_private", "naive_flat"):
        return None
    if mode in ("ghost_flat", "per_group"):
        if execution != "bk":
            return ("its norms-only pass returns each use's norm alone, "
                    "without the cross term of the two")
        if sharded:
            return ("the sharded step has no tested tied path (the cross "
                    "term per model shard, the tied epilogue's psum)")
        return None
    if mode == "per_layer":
        return ("it clips each use inside the backward, where the head's "
                "group norm would need the embedding's part, which arrives "
                "last")
    return "it has no exact norm for a leaf used twice"


def _sum_loss(loss_fn: LossFn, params, batch, thresholds) -> jax.Array:
    return jnp.sum(loss_fn(params, batch, thresholds))


def _grads_and_norms(loss_fn, params, batch, thresholds_tree, trainable_key):
    """One backward pass: clipped grads + norms² via threshold cotangents."""
    if trainable_key is None:
        def f(p, t):
            return _sum_loss(loss_fn, p, batch, t)

        val, (gp, gt) = jax.value_and_grad(f, argnums=(0, 1))(
            params, thresholds_tree)
        return val, gp, gt

    def f(sub, t):
        return _sum_loss(loss_fn, {**params, trainable_key: sub}, batch, t)

    val, (gs, gt) = jax.value_and_grad(f, argnums=(0, 1))(
        params[trainable_key], thresholds_tree)
    return val, {trainable_key: gs}, gt


def _norms_only(loss_fn, params, batch, thresholds_tree):
    def f(t):
        return _sum_loss(loss_fn, params, batch, t)

    # norms-only pass: disable the fused norm+clip kernel so the unused
    # clipped-sum contraction stays a separate op XLA can dead-code-eliminate
    with backend.scoped(prefer_fused=False):
        return jax.value_and_grad(f)(thresholds_tree)


def _grads_only(loss_fn, params, batch, thresholds_tree, trainable_key):
    if trainable_key is None:
        def g(p):
            return _sum_loss(loss_fn, p, batch, thresholds_tree)

        return jax.value_and_grad(g)(params)

    def g(sub):
        return _sum_loss(loss_fn, {**params, trainable_key: sub}, batch,
                         thresholds_tree)

    val, gs = jax.value_and_grad(g)(params[trainable_key])
    return val, {trainable_key: gs}


def group_clip_factors(norms_sq_groups: jax.Array, c: jax.Array) -> jax.Array:
    """min(1, C_g / ||g_g^(i)||) with 0-norm safety. (G, B) from (G, B), (G,).

    The `dp_clip_factor` scope marks the factor computation for the static
    auditor (repro.analysis.jaxpr_taint): per-example norms are CONSUMED
    here and what leaves is a bounded scaling factor."""
    with jax.named_scope("dp_clip_factor"):
        norm = jnp.sqrt(norms_sq_groups + 1e-12)
        return jnp.minimum(1.0, c[:, None] / norm)


def flat_clip_factors(total_norms_sq: jax.Array,
                      c: float | jax.Array) -> jax.Array:
    """min(1, C / ||g^(i)||): the flat-clipping per-example factor, (B,).

    Single marked implementation shared by ghost_flat, naive_flat and both
    sharded drivers — the `dp_clip_factor` scope is the auditor's anchor,
    so factor math must not be re-derived inline at call sites."""
    with jax.named_scope("dp_clip_factor"):
        c = jnp.asarray(c, jnp.float32)
        return jnp.minimum(1.0, c / jnp.sqrt(total_norms_sq + 1e-12))


def _bk_capture_ok(layout: GroupLayout, trainable_key: str | None) -> bool:
    """BK's epilogue rebuilds grads by walking the layout's spec, so the
    spec must cover exactly the trainable tree (it does for both the full-
    params case and the DP-LoRA {'lora': ...} sub-spec)."""
    return trainable_key is None or set(layout._spec) == {trainable_key}


def _norms_pass(loss_fn, params, batch, layout, batch_size, inf_tree,
                trainable_key, execution):
    """The shared first stage of ghost_flat / per_group: one backward pass
    for (sum loss, (K, B) norms²), capturing BK residuals when possible.

    Returns (val, norms, cap, cross) with cap = (residuals, recipes) under
    BK or None when running (or falling back to) the twopass reference, and
    cross the (B,) tied cross term (zeros off BK). A tied layout never falls
    back (check_tied_mode, bk.probe_recipes): the twopass norms leave the
    cross term out."""
    cap = (bk.capture_clipped(loss_fn, params, batch, layout, batch_size)
           if execution == "bk" and _bk_capture_ok(layout, trainable_key)
           else None)
    if cap is not None:
        val, norms, residuals, recipes, cross = cap
        return val, norms, (residuals, recipes), cross
    val, norm_tree = _norms_only(loss_fn, params, batch, inf_tree)
    return (val, layout.unpack(norm_tree), None,
            jnp.zeros((batch_size,), jnp.float32))


def _naive_group_norms(layout: GroupLayout, jac: Any, batch_size: int
                       ) -> jax.Array:
    """(K, B) per-layout-group norms² from materialized per-example grads.

    Gives the naive_flat oracle the same norms surface as every other mode
    (stacked leaves contribute one row per stack element, blocked leaves
    one row per column/row block), so group-wise parity tests can compare
    against it directly."""
    norms = jnp.zeros((layout.num_groups, batch_size), jnp.float32)

    def walk(node, j, path):
        nonlocal norms
        if isinstance(node, P):
            grp = layout.group(layout._leaf_group[path])
            x = j.astype(jnp.float32)  # (B,) + node.shape
            if node.blocks > 1:
                m = node.blocks
                x = x.reshape(x.shape[:-1] + (m, x.shape[-1] // m))
                x = jnp.moveaxis(x, -2, 1 + node.stack)  # blocks after stack
            sq = jnp.sum(
                x.reshape((batch_size,) + grp.stack_shape + (-1,)) ** 2,
                axis=-1)
            rows = sq.reshape(batch_size, grp.count).T  # (count, B)
            norms = norms.at[grp.offset: grp.offset + grp.count].add(rows)
            return
        for k in node:
            walk(node[k], j[k], path + (k,))

    walk(layout._spec, jac, ())
    return norms


def dp_clipped_gradients(
    loss_fn: LossFn,
    params: Any,
    batch: Any,
    layout: GroupLayout,
    *,
    mode: str,
    batch_size: int,
    thresholds: jax.Array | None = None,   # (K,) per_layer / per_shard
    flat_threshold: float | jax.Array = 1.0,  # scalar C for flat modes
    group_assignment: jax.Array | None = None,  # (K,) ints for per_group
    group_thresholds: jax.Array | None = None,  # (G,) for per_group
    trainable_key: str | None = None,  # top-level params subtree to train
    #   (DP LoRA: params = {'base': frozen, 'lora': adapters},
    #    trainable_key='lora'; grads come back as {'lora': ...})
    execution: str = "bk",  # bk | twopass, for ghost_flat / per_group
) -> ClipResult:
    """Clipped summed gradients + norms under the requested mode."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if execution not in EXECUTIONS:
        raise ValueError(f"execution {execution!r} not in {EXECUTIONS}")
    check_tied_mode(layout, mode, execution, trainable_key=trainable_key)
    if mode.endswith("_twopass"):
        mode, execution = base_mode(mode), "twopass"
    inf_tree = layout.pack_value(jnp.inf, batch_size)
    no_cross = jnp.zeros((batch_size,), jnp.float32)

    if mode == "non_private":
        val, grads = _grads_only(loss_fn, params, batch, inf_tree,
                                 trainable_key)
        norms = jnp.zeros((layout.num_groups, batch_size), jnp.float32)
        return ClipResult(grads, norms, val / batch_size, no_cross)

    if mode == "per_layer":
        if thresholds is None:
            raise ValueError("per_layer mode needs thresholds (K,)")
        th_tree = layout.pack(thresholds, batch_size)
        val, grads, norm_tree = _grads_and_norms(loss_fn, params, batch,
                                                 th_tree, trainable_key)
        norms = layout.unpack(norm_tree)
        return ClipResult(grads, norms, val / batch_size, no_cross)

    if mode == "ghost_flat":
        val, norms, cap, cross = _norms_pass(loss_fn, params, batch, layout,
                                             batch_size, inf_tree,
                                             trainable_key, execution)
        total = jnp.sum(norms, axis=0)  # (B,)
        f = flat_clip_factors(total, flat_threshold)  # (B,)
        if cap is not None:  # BK epilogue: contract the cached residuals
            residuals, recipes = cap
            f_rows = jnp.broadcast_to(f[None], (layout.num_groups,
                                                batch_size))
            grads = bk.contract_clipped(layout, recipes, residuals, f_rows)
        else:  # twopass reference (or BK fallback): second backward pass
            scale_tree = layout.pack_value(-f, batch_size)
            _, grads = _grads_only(loss_fn, params, batch, scale_tree,
                                   trainable_key)
        return ClipResult(grads, norms, val / batch_size, cross)

    if mode == "per_group":
        if group_assignment is None or group_thresholds is None:
            raise ValueError("per_group mode needs group_assignment + group_thresholds")
        val, norms, cap, cross = _norms_pass(loss_fn, params, batch, layout,
                                             batch_size, inf_tree,
                                             trainable_key, execution)
        num_super = group_thresholds.shape[0]
        super_norms = jax.ops.segment_sum(
            norms, group_assignment, num_segments=num_super)  # (G, B)
        f_super = group_clip_factors(super_norms, group_thresholds)  # (G, B)
        f_per_layer = f_super[group_assignment]  # (K, B)
        if cap is not None:
            residuals, recipes = cap
            grads = bk.contract_clipped(layout, recipes, residuals,
                                        f_per_layer)
        else:
            scale_tree = layout.pack_rows(-f_per_layer)
            _, grads = _grads_only(loss_fn, params, batch, scale_tree,
                                   trainable_key)
        return ClipResult(grads, norms, val / batch_size, cross)

    # naive_flat: the Opacus-style materializing oracle.
    if trainable_key is None:
        def per_example_losses(p):
            return loss_fn(p, batch, inf_tree)

        jac = jax.jacrev(per_example_losses)(params)
    else:
        def per_example_losses_sub(sub):
            return loss_fn({**params, trainable_key: sub}, batch, inf_tree)

        jac = {trainable_key: jax.jacrev(per_example_losses_sub)(
            params[trainable_key])}

        def per_example_losses(p):
            return loss_fn(p, batch, inf_tree)
    # real per-layout-group norms² (stacked/blocked aware) so group-wise
    # parity tests can compare every mode against this oracle
    norms = _naive_group_norms(layout, jac, batch_size)
    total = jnp.sum(norms, axis=0)  # (B,)
    f = flat_clip_factors(total, flat_threshold)
    grads = jax.tree_util.tree_map(
        lambda l: jnp.tensordot(f.astype(jnp.float32),
                                l.astype(jnp.float32).reshape(batch_size, -1),
                                axes=1).reshape(l.shape[1:]).astype(l.dtype),
        jac,
    )
    loss = jnp.mean(per_example_losses(params))
    return ClipResult(grads, norms, loss, no_cross)


# ---------------------------------------------------------------------------
# Sharded (shard_map) execution: per-device clipping that runs for real.
#
# Inside a `shard_map` body each device holds a LOCAL batch shard (data
# axes) and a model-axis coordinate m. `shard_assignment` maps every layout
# group to its owning model shard (launch.sharding.group_shard_assignment),
# and the driver keeps the paper's Sec-4 communication contract executable:
#
#   per_group (per-DEVICE clipping): shard m reduces norms² over ONLY the
#       groups it owns and computes its clip factor locally — zero
#       cross-model-axis collectives before scaling;
#   ghost_flat: the total per-example norm² needs every shard's partial —
#       exactly one (B_local,) psum over the model axis, named
#       `flat_norm_psum` so the HLO axis classifier can find it;
#   epilogue: each shard contracts only its owned groups' residuals (others
#       are masked to zero) and the clipped sums are joined by ONE psum over
#       (data + model) per layer, interleaved with the next layer's
#       contraction (bk.contract_clipped psum_axes) so gradient reduction
#       overlaps the book-keeping compute.
#
# The loss backward itself runs data-parallel (params replicated across the
# model axis at compute time; the launcher may still STORE them model-
# sharded per launch.sharding rules — the entry all-gather is weight
# traffic, not norm traffic, and classifies as such). What this engine
# distributes for real over the model axis is the clipping bookkeeping:
# norm reductions, clip factors, and the scale-and-contract epilogue.
# ---------------------------------------------------------------------------


class ShardedClipResult(NamedTuple):
    grads: Any           # GLOBALLY summed clipped grads (replicated)
    norms_sq: jax.Array  # (K, B_local) this data shard's examples
    loss: jax.Array      # scalar GLOBAL mean per-example loss
    counts: jax.Array    # (G,) global clip counts (replicated)


def _psum_tree(tree, axes):
    with jax.named_scope("grad_psum"):
        return jax.tree_util.tree_map(lambda l: jax.lax.psum(l, axes), tree)


def sharded_clipped_gradients(
    loss_fn: LossFn,
    params: Any,
    batch: Any,  # LOCAL batch shard
    layout: GroupLayout,
    *,
    mode: str,
    batch_size: int,       # LOCAL per-device-row batch size
    data_size: int,        # number of data-plane shards (global B = both)
    data_axes: tuple,      # mesh axis names of the data plane
    model_axis: str,       # mesh axis name of the model plane
    shard_assignment: jax.Array | None = None,  # (K,) group -> model shard
    thresholds: jax.Array | None = None,        # (K,) per_layer
    flat_threshold: float | jax.Array = 1.0,
    group_thresholds: jax.Array | None = None,  # (M,) per_group==per-device
    trainable_key: str | None = None,
    execution: str = "bk",
) -> ShardedClipResult:
    """`dp_clipped_gradients` under manual SPMD — see module comment above."""
    check_tied_mode(layout, mode, execution, trainable_key=trainable_key,
                    sharded=True)
    if mode.endswith("_twopass"):
        mode, execution = base_mode(mode), "twopass"
    all_axes = tuple(data_axes) + (model_axis,)
    inf_tree = layout.pack_value(jnp.inf, batch_size)
    global_b = batch_size * data_size

    def _mean_loss(val):
        with jax.named_scope("loss_psum"):
            return jax.lax.psum(val, tuple(data_axes)) / global_b

    if mode == "non_private":
        val, grads = _grads_only(loss_fn, params, batch, inf_tree,
                                 trainable_key)
        norms = jnp.zeros((layout.num_groups, batch_size), jnp.float32)
        return ShardedClipResult(_psum_tree(grads, tuple(data_axes)), norms,
                                 _mean_loss(val), jnp.zeros((1,)))

    if mode == "per_layer":
        if thresholds is None:
            raise ValueError("per_layer mode needs thresholds (K,)")
        th_tree = layout.pack(thresholds, batch_size)
        val, grads, norm_tree = _grads_and_norms(loss_fn, params, batch,
                                                 th_tree, trainable_key)
        norms = layout.unpack(norm_tree)
        with jax.named_scope("clip_count_psum"):
            counts = jax.lax.psum(clip_counts(norms, thresholds),
                                  tuple(data_axes))
        return ShardedClipResult(_psum_tree(grads, tuple(data_axes)), norms,
                                 _mean_loss(val), counts)

    if mode not in ("ghost_flat", "per_group"):
        raise ValueError(
            f"sharded execution supports non_private/per_layer/ghost_flat/"
            f"per_group, not {mode!r} (naive_flat is a single-device oracle)")
    if shard_assignment is None:
        raise ValueError("sharded flat/group modes need shard_assignment")

    val, norms, cap, _ = _norms_pass(loss_fn, params, batch, layout,
                                     batch_size, inf_tree, trainable_key,
                                     execution)
    midx = jax.lax.axis_index(model_axis)
    own = (shard_assignment == midx).astype(jnp.float32)  # (K,)
    # this shard's contribution: norms² of the groups it owns only
    with jax.named_scope("shardlocal_norms"):
        partial = jnp.sum(norms * own[:, None], axis=0)  # (B_local,)

    if mode == "ghost_flat":
        c = jnp.asarray(flat_threshold, jnp.float32)
        # THE flat-clipping model-axis collective: the total per-example
        # norm² crosses every model shard before any factor exists
        with jax.named_scope("flat_norm_psum"):
            total = jax.lax.psum(partial, model_axis)  # (B_local,)
        f = flat_clip_factors(total, c)
        f_rows = f[None, :] * own[:, None]  # masked: epilogue is per-owner
        with jax.named_scope("clip_count_psum"):
            counts = jax.lax.psum(
                jnp.sum((total <= c * c).astype(jnp.float32))[None],
                tuple(data_axes))
        f_full = jnp.broadcast_to(f[None], (layout.num_groups, batch_size))
    else:  # per_group == per-DEVICE: factors close over shard-local norms
        if group_thresholds is None:
            raise ValueError("per_group mode needs group_thresholds (M,)")
        num_super = group_thresholds.shape[0]
        c_m = group_thresholds[midx]
        f_m = flat_clip_factors(partial, c_m)  # (B_local,)
        f_rows = f_m[None, :] * own[:, None]
        with jax.named_scope("clip_count_psum"):
            slot = (jnp.arange(num_super) == midx).astype(jnp.float32)
            counts = jax.lax.psum(
                slot * jnp.sum((partial <= c_m * c_m).astype(jnp.float32)),
                all_axes)
        f_full = None  # gathered below only if the twopass fallback runs

    if cap is not None:  # BK: masked, collective-overlapped epilogue
        residuals, recipes = cap
        grads = bk.contract_clipped(layout, recipes, residuals, f_rows,
                                    psum_axes=all_axes)
        return ShardedClipResult(grads, norms, _mean_loss(val), counts)

    # twopass fallback: the second backward produces every group's grads on
    # every shard (replicated over model), so it needs the FULL factor rows;
    # gathering them is factor traffic AFTER scaling factors exist, not norm
    # traffic — named accordingly.
    if f_full is None:
        with jax.named_scope("factor_gather_psum"):
            f_full = jax.lax.psum(f_rows, model_axis)
    scale_tree = layout.pack_rows(-f_full)
    _, grads = _grads_only(loss_fn, params, batch, scale_tree, trainable_key)
    return ShardedClipResult(_psum_tree(grads, tuple(data_axes)), norms,
                             _mean_loss(val), counts)

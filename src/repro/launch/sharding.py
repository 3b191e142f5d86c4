"""Sharding rules: spec paths -> PartitionSpec (Megatron-style TP + DP).

Rules are name-based over the parameter spec tree (the same canonical paths
that name clipping groups), so every architecture gets coherent tensor
parallelism from one table:

  column-parallel (output dim -> model): qkv / gate_up / in_proj / rwkv
      r,k,v,g / lora b / mla q_b,kv_b / cross kv / head
  row-parallel   (input dim -> model): o / down / out_proj / rwkv o / cm v
  expert-parallel: moe w_gu / w_down shard the EXPERT dim over model
  replicated: norms, small vectors, routers, embed-adjacent gains

Non-divisible dims fall back to replication (uneven GSPMD sharding is legal
but wasteful for weights; we prefer predictable layouts — recorded per arch
in EXPERIMENTS.md)."""
from __future__ import annotations

import fnmatch
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core.spec import P, SpecTree, _walk
from repro.launch.mesh import data_axes

# pattern -> (axis_from_end, kind); kind: 'dim' shard that axis on model,
# 'replicate'
_RULES: list[tuple[str, Any]] = [
    ("embed/w", ("dim", 0)),            # vocab -> model
    ("head/w", ("dim", -1)),            # vocab -> model
    ("*moe/w_gu", ("expert", -1)),    # fallback: column-parallel in-expert
    ("*moe/w_down", ("expert", -2)),  # fallback: row-parallel in-expert
    ("*moe/router/w", ("replicate", None)),
    ("*moe/shared/gate_up/w", ("dim", -1)),
    ("*moe/shared/down/w", ("dim", -2)),
    ("*attn/qkv/w", ("dim", -1)),
    ("*attn/qkv/b", ("dim", -1)),
    ("*attn/kv/w", ("dim", -1)),
    ("*attn/kv/b", ("dim", -1)),
    ("*attn/o/w", ("dim", -2)),
    ("*attn/q/w", ("dim", -1)),
    ("*attn/q_a/w", ("replicate", None)),
    ("*attn/q_b/w", ("dim", -1)),
    ("*attn/kv_a/w", ("replicate", None)),
    ("*attn/kv_b/w", ("dim", -1)),
    ("*cross/qkv/w", ("dim", -1)),
    ("*cross/kv/w", ("dim", -1)),
    ("*cross/o/w", ("dim", -2)),
    ("*mlp/gate_up/w", ("dim", -1)),
    ("*mlp/down/w", ("dim", -2)),
    ("*in_proj/w", ("dim", -1)),
    ("*out_proj/w", ("dim", -2)),
    ("*tm/r/w", ("dim", -1)),
    ("*tm/k/w", ("dim", -1)),
    ("*tm/v/w", ("dim", -1)),
    ("*tm/g/w", ("dim", -1)),
    ("*tm/o/w", ("dim", -2)),
    ("*cm/k/w", ("dim", -1)),
    ("*cm/v/w", ("dim", -2)),
    ("*cm/r/w", ("dim", -1)),
    ("lora/*/b", ("dim", -1)),          # adapter B column-parallel
    ("lora/*/a", ("replicate", None)),
    ("mtp/proj/w", ("dim", -1)),
]


def _spec_for(path: str, p: P, model_size: int) -> PS:
    ndim = len(p.shape)
    for pattern, (kind, axis) in _RULES:
        if fnmatch.fnmatch(path, pattern):
            if kind == "replicate":
                return PS()
            if kind == "expert":
                # shape (..., E, d, f): expert dim is -3; when E doesn't
                # divide the model axis (e.g. granite's 40 experts on 16
                # shards) fall back to intra-expert tensor parallelism so
                # expert compute never replicates.
                e_axis = ndim - 3
                if p.shape[e_axis] % model_size == 0:
                    out = [None] * ndim
                    out[e_axis] = "model"
                    return PS(*out)
                ax = axis % ndim
                if p.shape[ax] % model_size == 0:
                    out = [None] * ndim
                    out[ax] = "model"
                    return PS(*out)
                return PS()
            ax = axis % ndim
            if p.shape[ax] % model_size == 0:
                out = [None] * ndim
                out[ax] = "model"
                return PS(*out)
            return PS()
    return PS()  # default: replicate (norm scales, small vectors)


def group_shard_assignment(layout, model_size: int) -> tuple[int, ...]:
    """Map every flat clipping group to its owning model-axis shard.

    This is what makes `per_group` clipping mean PER-DEVICE clipping (paper
    Sec 4): supergroup s = "everything shard s owns", so each shard's norm
    reductions and clip factors close over shard-local groups only. Shared
    by `launch/train.py` and `launch/dryrun.py` so the CLI, the lowering
    sweep and the executing sharded engine all agree on the partition.
    Ownership is derived from the SAME rule table that places the
    parameters (`_RULES`):

      * blocked groups (`P.blocks == model_size`) whose weight is
        column-parallel: block j lives on shard j — exact Megatron
        ownership, norm stays on the shard that holds the columns;
      * stacked groups (scanned layer runs): layer l -> shard
        l * model_size // L — contiguous pipeline-stage ownership (the
        paper's GPT-3 recipe partitions by pipeline stage);
      * singleton groups (embed / head / final norm / replicated scales):
        deterministic round-robin in sorted-name order, balancing the
        bookkeeping across shards.

    Returns a tuple of ints in [0, model_size) of length
    `layout.num_groups`, directly usable as `DPConfig.group_assignment`
    (with `num_supergroups=model_size`: a shard may own nothing).
    """
    spec = layout._spec
    leaves_by_group: dict[str, list] = {}
    for path, p in _walk(spec):
        leaves_by_group.setdefault(layout._leaf_group[path], []).append(
            (path, p))
    assign = np.zeros(layout.num_groups, dtype=np.int64)
    rr = 0  # round-robin counter for singleton groups
    for g in layout.groups:
        members = leaves_by_group.get(g.name, [])
        # primary leaf: the largest member (the weight, not the bias)
        path, p = max(members, key=lambda kv: int(
            np.prod(kv[1].shape, dtype=np.int64)))
        ps = _spec_for("/".join(path), p, model_size)
        axes = list(ps) + [None] * (len(p.shape) - len(ps))
        col_parallel = bool(axes) and axes[-1] == "model"
        ids = np.arange(g.count, dtype=np.int64)
        if g.count == 1:
            assign[g.offset] = rr % model_size
            rr += 1
            continue
        if p.blocks == model_size and p.blocks > 1 and col_parallel:
            # stack_shape ends in the block dim: element (.., j) -> shard j
            owners = ids % model_size
        else:
            first = g.stack_shape[0]
            owners = (ids // max(g.count // first, 1)) * model_size // first
        assign[g.offset: g.offset + g.count] = owners % model_size
    return tuple(int(a) for a in assign)


def params_shardings(spec: SpecTree, mesh, *, serving: bool = False) -> Any:
    """Pytree of NamedSharding parallel to the params.

    serving=True additionally shards the largest unsharded dim of every
    sizable weight over the DATA plane (weight-FSDP for inference): a
    training step needs params replicated across data for the gradient
    psum, but a serve step has no gradients and a 671B MoE simply does not
    fit 16 GB/chip at model-axis-only sharding (84 GB/device measured);
    fully-sharded weights are all-gathered per layer by XLA instead."""
    model_size = mesh.shape["model"]
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))

    def walk(node, prefix):
        if isinstance(node, P):
            ps = _spec_for("/".join(prefix), node, model_size)
            if serving and int(np.prod(node.shape)) >= (1 << 20):
                axes = list(ps) + [None] * (len(node.shape) - len(ps))
                # largest still-unsharded dim -> data plane
                cands = [(node.shape[i], i) for i in range(len(axes))
                         if axes[i] is None and node.shape[i] % dp_size == 0]
                if cands:
                    _, i = max(cands)
                    axes[i] = dp
                    ps = PS(*axes)
            return NamedSharding(mesh, ps)
        return {k: walk(v, prefix + (k,)) for k, v in node.items()}

    return walk(spec, ())


def batch_shardings(batch_abstract: Any, mesh) -> Any:
    """Batch leaves shard dim 0 over the data(+pod) plane."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))

    def one(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] % dp_size == 0:
            return NamedSharding(mesh, PS(dp))
        return NamedSharding(mesh, PS())

    return jax.tree_util.tree_map(one, batch_abstract)


def cache_shardings(cache_abstract: Any, mesh) -> Any:
    """Decode caches: (L, B, S, heads, hd)-style leaves.

    dim 1 (batch) -> data plane when divisible; otherwise the SEQUENCE dim
    (2) shards over data (long-context, batch=1). Head/expert dims shard
    over model when divisible."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    model_size = mesh.shape["model"]

    def one(leaf):
        nd = leaf.ndim
        if nd <= 1:
            return NamedSharding(mesh, PS())
        ax = [None] * nd
        if leaf.shape[1] % dp_size == 0:
            ax[1] = dp
        elif nd >= 3 and leaf.shape[2] % dp_size == 0:
            ax[2] = dp
        # try a model axis on one of the trailing dims (prefer heads)
        for cand in range(nd - 2, 1, -1):
            if ax[cand] is None and leaf.shape[cand] % model_size == 0 \
                    and leaf.shape[cand] >= model_size:
                ax[cand] = "model"
                break
        return NamedSharding(mesh, PS(*ax))

    return jax.tree_util.tree_map(one, cache_abstract)


def replicated(tree: Any, mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, PS()), tree)


def opt_state_shardings(opt_state_abstract: Any, pshard: Any, mesh) -> Any:
    """Optimizer state: moment leaves shard like their parameter; scalars
    replicate. Matches by shape against the param shardings tree."""
    pshard_leaves = {}
    for path, s in jax.tree_util.tree_flatten_with_path(pshard)[0]:
        pshard_leaves.setdefault(None, []).append(s)

    # mu/nu have the same treedef as params: map by structure when possible
    params_treedef = jax.tree_util.tree_structure(pshard)

    def assign(node):
        if jax.tree_util.tree_structure(node) == params_treedef:
            return pshard
        return jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, PS()), node)

    # opt states are NamedTuples whose fields are either scalars or
    # param-shaped pytrees
    if hasattr(opt_state_abstract, "_fields"):
        return type(opt_state_abstract)(*[
            assign(getattr(opt_state_abstract, f))
            for f in opt_state_abstract._fields])
    return assign(opt_state_abstract)

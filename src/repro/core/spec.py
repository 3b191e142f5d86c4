"""Parameter/group specification framework.

Group-wise clipping needs global bookkeeping that PyTorch gets from module
objects and JAX has to carry explicitly:

  * which parameters form a clipping group (paper: a "layer", e.g. the
    {W, b} of one linear; per-device mode: one Megatron block of W),
  * each group's size d_k (noise allocation needs it),
  * a flat enumeration k = 1..K of groups so thresholds C_k, per-example
    norms² n_k(i), clip counts b_k and quantile trackers line up,
  * the map param-leaf -> group id (noise std lookup per leaf).

Models declare their parameters as a nested dict of `P` leaves; everything
else (init, layout, packing thresholds, unpacking norms) is derived here.

Stacked layers: a spec whose shape carries leading scan dims sets
`stack=<n leading dims>`; each stack element is its own clipping group
(adaptive per-layer clipping tracks a separate C_k per depth). Blocked
weights (`blocks=M`) split one weight into M per-shard groups (per-device
clipping analogue).
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np


def stable_hash(name: str) -> int:
    """Process-independent 31-bit string hash.

    Python's builtin hash() is randomized per process (PYTHONHASHSEED), so
    folding it into PRNG keys makes param init and noise draws differ
    between processes — fatal for the training service's crash/resume
    bitwise-parity guarantee. Everything that derives a key from a name
    must use this instead."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


@dataclasses.dataclass(frozen=True)
class P:
    """Specification of one parameter tensor."""

    shape: tuple[int, ...]
    init: str = "normal"  # zeros | ones | normal | embed | uniform
    scale: float | None = None  # stddev override (normal) / range (uniform)
    dtype: Any = jnp.float32
    group: str | None = None  # explicit group path (shared / joint groups)
    blocks: int = 1  # split into M per-shard clipping groups (weights only)
    stack: int = 0  # number of leading scan/stack dims in `shape`
    fan_in_axis: int = -2  # axis used for fan-in init scaling
    sensitivity_mult: float = 1.0  # >1 for params SHARED across use sites
    #   (each site clips to C_k separately; the summed contribution of one
    #   example is bounded by n_sites * C_k, which noise calibration must use)
    tied: bool = False  # the leaf is BOTH the input embedding and the LM
    #   head (dp_embed + dp_tied_head): one example's gradient is the sum of
    #   the two uses', so its group's exact norm carries their cross term,
    #   which only the BK capture computes (core.bk, core.clipping)


SpecTree = Any  # nested dict[str, P | SpecTree]


def _walk(spec: SpecTree, prefix: tuple[str, ...] = ()) -> Iterator[tuple[tuple[str, ...], P]]:
    for name in sorted(spec):
        node = spec[name]
        path = prefix + (name,)
        if isinstance(node, P):
            yield path, node
        else:
            yield from _walk(node, path)


# Canonical param leaf names that join their parent module's group
# ({w, b} of a linear, {s} of a norm, {a, b} of a LoRA adapter pair).
_PARENT_GROUP_NAMES = frozenset({"w", "b", "s", "a"})


def _group_path(path: tuple[str, ...], p: P) -> str:
    if p.group is not None:
        return p.group
    if len(path) > 1 and path[-1] in _PARENT_GROUP_NAMES:
        return "/".join(path[:-1])
    return "/".join(path)


def init_params(spec: SpecTree, key: jax.Array) -> Any:
    """Initialize a param pytree from a spec tree."""

    def build(node, key, path):
        if isinstance(node, P):
            return _init_leaf(node, key)
        out = {}
        for name in sorted(node):
            out[name] = build(node[name],
                              jax.random.fold_in(key, stable_hash(name)),
                              path + (name,))
        return out

    return build(spec, key, ())


def _init_leaf(p: P, key: jax.Array) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, p.dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, p.dtype)
    if p.init == "normal":
        fan_in = p.shape[p.fan_in_axis] if len(p.shape) >= 2 else max(p.shape[-1], 1)
        std = p.scale if p.scale is not None else 1.0 / math.sqrt(fan_in)
        return (std * jax.random.normal(key, p.shape)).astype(p.dtype)
    if p.init == "embed":
        std = p.scale if p.scale is not None else 0.02
        return (std * jax.random.normal(key, p.shape)).astype(p.dtype)
    if p.init == "uniform":
        r = p.scale if p.scale is not None else 0.02
        return jax.random.uniform(key, p.shape, p.dtype, -r, r)
    raise ValueError(f"unknown init {p.init!r}")


def abstract_params(spec: SpecTree) -> Any:
    """ShapeDtypeStruct pytree (for dry-run lowering, no allocation)."""

    def build(node):
        if isinstance(node, P):
            return jax.ShapeDtypeStruct(node.shape, node.dtype)
        return {k: build(v) for k, v in node.items()}

    return build(spec)


@dataclasses.dataclass(frozen=True)
class Group:
    name: str
    stack_shape: tuple[int, ...]  # e.g. (L,) for scanned layers, (L, M) blocked
    dim: int  # parameters per group element (d_k)
    offset: int  # flat id of element (0,...,0)
    sensitivity_mult: float = 1.0

    @property
    def count(self) -> int:
        return int(np.prod(self.stack_shape, dtype=np.int64)) if self.stack_shape else 1


class GroupLayout:
    """Flat enumeration of clipping groups + pack/unpack helpers."""

    def __init__(self, spec: SpecTree):
        groups: dict[str, dict] = {}
        leaf_group: dict[tuple[str, ...], str] = {}
        tied: set[str] = set()
        for path, p in _walk(spec):
            gname = _group_path(path, p)
            if p.tied:
                tied.add(gname)
            stack_shape = tuple(p.shape[: p.stack])
            if p.blocks > 1:
                stack_shape = stack_shape + (p.blocks,)
            per_elem = int(np.prod(p.shape[p.stack:], dtype=np.int64)) // p.blocks
            if gname in groups:
                g = groups[gname]
                g["mult"] = max(g["mult"], p.sensitivity_mult)
                if g["stack_shape"] != stack_shape:
                    # bias joining a blocked weight group: allow scalar-per-
                    # element membership only when stack shapes are compatible
                    raise ValueError(
                        f"group {gname!r}: stack shape mismatch "
                        f"{g['stack_shape']} vs {stack_shape} at {path}"
                    )
                g["dim"] += per_elem
            else:
                groups[gname] = {"stack_shape": stack_shape, "dim": per_elem,
                                 "mult": p.sensitivity_mult}
            leaf_group[path] = gname
        self.groups: list[Group] = []
        self._by_name: dict[str, Group] = {}
        offset = 0
        for name in sorted(groups):
            g = groups[name]
            grp = Group(name=name, stack_shape=g["stack_shape"], dim=g["dim"],
                        offset=offset, sensitivity_mult=g["mult"])
            self.groups.append(grp)
            self._by_name[name] = grp
            offset += grp.count
        self.num_groups = offset
        # groups holding a tied embedding / LM head leaf (see P.tied)
        self.tied_groups: tuple[str, ...] = tuple(sorted(tied))
        self._leaf_group = leaf_group
        self._spec = spec

    # -- flat vectors -------------------------------------------------------

    def group(self, name: str) -> Group:
        return self._by_name[name]

    @property
    def dims(self) -> np.ndarray:
        """(K,) parameter count per group."""
        out = np.empty(self.num_groups, dtype=np.int64)
        for g in self.groups:
            out[g.offset: g.offset + g.count] = g.dim
        return out

    @property
    def sens_mults(self) -> np.ndarray:
        """(K,) sensitivity multipliers (shared-parameter sites)."""
        out = np.ones(self.num_groups, dtype=np.float32)
        for g in self.groups:
            out[g.offset: g.offset + g.count] = g.sensitivity_mult
        return out

    def flat_names(self) -> list[str]:
        out = []
        for g in self.groups:
            if g.count == 1:
                out.append(g.name)
            else:
                for idx in np.ndindex(g.stack_shape):
                    out.append(g.name + "[" + ",".join(map(str, idx)) + "]")
        return out

    # -- threshold packing ---------------------------------------------------

    def pack(self, flat: jax.Array, batch: int) -> dict[str, jax.Array]:
        """(K,) encoded thresholds -> {group name: stack_shape + (B,)} dict."""
        out = {}
        for g in self.groups:
            piece = jax.lax.dynamic_slice_in_dim(flat, g.offset, g.count)
            piece = piece.reshape(g.stack_shape + (1,))
            out[g.name] = jnp.broadcast_to(piece, g.stack_shape + (batch,))
        return out

    def pack_value(self, value: jax.Array | float, batch: int) -> dict[str, jax.Array]:
        """Same encoded scalar (or (B,) vector) for every group."""
        out = {}
        v = jnp.asarray(value, jnp.float32)
        for g in self.groups:
            if v.ndim == 0:
                out[g.name] = jnp.full(g.stack_shape + (batch,), v)
            else:
                out[g.name] = jnp.broadcast_to(v, g.stack_shape + (batch,))
        return out

    def pack_rows(self, rows: jax.Array) -> dict[str, jax.Array]:
        """(K, B) per-group per-example values -> thresholds dict."""
        out = {}
        batch = rows.shape[-1]
        for g in self.groups:
            piece = jax.lax.dynamic_slice_in_dim(rows, g.offset, g.count, axis=0)
            out[g.name] = piece.reshape(g.stack_shape + (batch,))
        return out

    def unpack(self, tree: dict[str, jax.Array]) -> jax.Array:
        """{group: stack_shape + (B,)} norms -> (K, B) flat matrix."""
        rows = []
        for g in self.groups:
            leaf = tree[g.name]
            rows.append(leaf.reshape(g.count, leaf.shape[-1]))
        return jnp.concatenate(rows, axis=0)

    # -- param-leaf -> group ids (noise lookup) ------------------------------

    def param_group_ids(self) -> Any:
        """Pytree parallel to params: leaves are int arrays of the leaf's
        group stack shape holding flat group ids (broadcastable against the
        param leaf for per-depth noise stds)."""

        def build(node, prefix):
            if isinstance(node, P):
                g = self._by_name[self._leaf_group[prefix]]
                ids = g.offset + np.arange(g.count, dtype=np.int64).reshape(
                    g.stack_shape or ())
                return ids
            return {k: build(v, prefix + (k,)) for k, v in node.items()}

        return build(self._spec, ())

    def zeros_thresholds(self, value: float = 1.0) -> jax.Array:
        return jnp.full((self.num_groups,), value, dtype=jnp.float32)


def subth(th: dict, prefix: str) -> dict:
    """Select the threshold-dict subtree under `prefix` (strip 'prefix/')."""
    plen = len(prefix) + 1
    return {k[plen:]: v for k, v in th.items() if k.startswith(prefix + "/")}

"""Book-keeping (BK) execution engine: one backprop for the two-pass modes.

Flat and per-group clipping need clip factors that depend on the TOTAL
per-example norm across groups, which is only known after backpropagation
completes — the reason `ghost_flat`/`per_group` historically ran TWO full
backward passes (norms first, clipped grads second). Bu et al.,
*Differentially Private Optimization on Large Model at Small Cost*
(arXiv:2210.00038), observe the second pass is redundant: cache each
layer's ghost residuals — the activations A_i and output cotangents G_i —
during the single norm-computing backprop, then produce every clipped
weight gradient with one lightweight scale-and-contract per layer,

    dW = Σ_i f_i · A_iᵀ G_i,

building on the fast per-example clipping of Lee & Kifer (arXiv:2009.03106).

The JAX realization here piggybacks on the encoded-threshold side channel
that already threads one leaf per clipping group through every model
(including through `lax.scan` layer stacks): a `BkChannel` pytree leaf
bundles the encoded thresholds with zero-initialized residual *sinks*.
The dp primitives' custom VJPs, when handed a BkChannel inside a
`backend.scoped(capture_residuals=True)` extent, emit their per-example
norms² through the threshold cotangent as usual AND return the (a, g)
residuals through the sink cotangent — so a single `jax.grad` over the
channel tree yields norms and residuals together, with zero extra forward
or backward work. Scanned layer stacks need no special handling: scan
slices the sink leaves per iteration and stacks their cotangents back,
exactly as it already does for thresholds and norms.

Pipeline (driven by `core.clipping.dp_clipped_gradients`):

  1. `probe_recipes`   — trace-time `jax.eval_shape` pass over the loss
                         with sink-less probe channels; each primitive
                         records its residual shapes/dtypes per group.
                         Returns None (-> two-pass fallback) for layouts
                         BK cannot capture: a group consumed more than
                         once per step (e.g. the MTP head) or shared-site
                         parameters (sensitivity_mult > 1), whose single
                         threshold leaf would sum residuals across sites.
                         A tied embedding / LM head is the one leaf used
                         twice that BK captures: its two uses merge into
                         one `tied` recipe. Any other probe failure raises.
  2. `capture_clipped` — ONE `value_and_grad` over the channel tree:
                         per-group norms² + cached residuals; a tied
                         group's norm² gains its cross term here.
  3. driver computes the per-example clip factors from the norms.
  4. `contract_clipped`— the epilogue: per layer, one scale-and-contract
                         over the cached residuals (`scale_contract` in
                         the backend engine — Pallas kernel on TPU) builds
                         the clipped summed gradient pytree.

A capture pass returns ZERO parameter cotangents (the epilogue owns the
weight gradients), so the primitives refuse BkChannels outside the scoped
`capture_residuals` flag.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ghost
from repro.core.spec import GroupLayout, P
from repro.kernels import backend

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# The channel leaf.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BkChannel:
    """Threshold leaf + residual sink, with the group name as static aux.

    `c` is the usual encoded-threshold array (stack_shape + (B,)); `sink`
    is a dict of zero arrays whose COTANGENTS carry the ghost residuals
    back out of the backward pass (None during the shape probe). The group
    name rides in the treedef, so a primitive receiving a (possibly
    scan-sliced) channel knows statically which clipping group it serves.
    """

    c: Any
    sink: Any = None
    group: str = ""

    def tree_flatten(self):
        return (self.c, self.sink), (self.group,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    @property
    def T(self):
        """Transpose the threshold child only (models reorder thresholds
        before blocked primitives; sinks are positional, not transposed)."""
        return BkChannel(self.c.T, self.sink, self.group)


def thresholds_of(c):
    """The encoded-threshold array of a maybe-channel threshold arg."""
    return c.c if isinstance(c, BkChannel) else c


def _require_capture_scope(channel: BkChannel) -> None:
    if not backend.active().config.capture_residuals:
        raise RuntimeError(
            f"BkChannel for group {channel.group!r} reached a dp primitive "
            "outside backend.scoped(capture_residuals=True); capture passes "
            "return zero parameter cotangents and must only be driven by "
            "repro.core.bk.capture_clipped")


# a tied table's channel carries the sinks of both its uses: the
# embedding's (record_embed) and the head's (record_tied_head)
_EMBED_SINKS = frozenset(("g", "ids"))
_TIED_HEAD_SINKS = frozenset(("a", "gh", "kst"))


def emit(channel: BkChannel, norms_sq, **sink_vals) -> BkChannel:
    """Build the channel cotangent: norms² + residuals cast to sink dtypes.

    Every call writes every sink of its channel, except on a tied group's:
    there each use writes its own sinks and gives the other's a zero
    cotangent, so the two cotangents add up to the whole residual set."""
    _require_capture_scope(channel)
    have, want = frozenset(sink_vals), frozenset(channel.sink)
    if (have != want and want == _EMBED_SINKS | _TIED_HEAD_SINKS
            and have in (_EMBED_SINKS, _TIED_HEAD_SINKS)):
        sink_vals = {k: sink_vals.get(k, jnp.zeros(s.shape, s.dtype))
                     for k, s in channel.sink.items()}
    sink_ct = jax.tree_util.tree_map(
        lambda s, v: v.astype(s.dtype), channel.sink, dict(sink_vals))
    return BkChannel(norms_sq.astype(jnp.float32), sink_ct, channel.group)


# ---------------------------------------------------------------------------
# Trace-time shape probe.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Recipe:
    """What one dp-primitive call site stashes for one clipping group."""

    kind: str          # linear|linear_blocked|embed|scale|shift|broadcast|
    #                    lora|expert|expert_grouped|tied (embed + tied_head)
    c_ndim: int        # rank of the PER-CALL threshold (after scan slicing)
    sinks: dict        # sink name -> ShapeDtypeStruct (per-call shapes)
    extras: dict       # kind-specific statics (has_bias, vocab, ...)
    count: int = 1     # consumptions per step; >1 -> BK unsupported


_RECORDER: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "bk_recorder", default=None)


@contextlib.contextmanager
def _recording():
    rec: dict[str, Recipe] = {}
    token = _RECORDER.set(rec)
    try:
        yield rec
    finally:
        _RECORDER.reset(token)


def _record(channel, kind, sinks, **extras):
    rec = _RECORDER.get()
    if rec is None or not isinstance(channel, BkChannel):
        return
    name = channel.group
    if name in rec:
        prev = rec[name]
        if {prev.kind, kind} == {"embed", "tied_head"}:
            # a tied table: the lookup and the head are one group's two uses
            prev.kind = "tied"
            prev.sinks = {**prev.sinks, **sinks}
            prev.extras = {**prev.extras, **extras}
            return
        prev.count += 1
        return
    rec[name] = Recipe(kind, channel.c.ndim, sinks, extras)


def _tfold(x) -> int:
    """Rows per example after the primitives' (B, -1, d) reshape."""
    return int(np.prod(x.shape[1:-1], dtype=np.int64)) if x.ndim > 2 else 1


# -- kind-specific recorders, called from the dp primitives' primals -------


def record_linear(c, w, b, x):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz, tf, din, dout = x.shape[0], _tfold(x), x.shape[-1], w.shape[-1]
    gdt = jnp.result_type(x.dtype, w.dtype)
    _record(c, "linear", {"a": SDS((bsz, tf, din), x.dtype),
                          "g": SDS((bsz, tf, dout), gdt)},
            has_bias=b is not None)


def record_linear_blocked(c, w, b, x, block_axis):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz, tf, din, dout = x.shape[0], _tfold(x), x.shape[-1], w.shape[-1]
    gdt = jnp.result_type(x.dtype, w.dtype)
    _record(c, "linear_blocked", {"a": SDS((bsz, tf, din), x.dtype),
                                  "g": SDS((bsz, tf, dout), gdt)},
            has_bias=b is not None, block_axis=block_axis,
            m=thresholds_of(c).shape[-1])


def record_embed(c, table, ids):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz = ids.shape[0]
    tf = int(np.prod(ids.shape[1:], dtype=np.int64)) if ids.ndim > 1 else 1
    _record(c, "embed", {"g": SDS((bsz, tf, table.shape[-1]), table.dtype),
                         # token ids ride the float cotangent channel;
                         # exact for vocab < 2^24
                         "ids": SDS((bsz, tf), jnp.float32)},
            vocab=table.shape[0])


def record_tied_head(c, table, x, ids):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz, tf, d, vocab = x.shape[0], _tfold(x), x.shape[-1], table.shape[0]
    gdt = jnp.result_type(x.dtype, table.dtype)
    _record(c, "tied_head", {"a": SDS((bsz, tf, d), x.dtype),
                             "gh": SDS((bsz, tf, vocab), gdt),
                             # gl[b, s, ids[b, t]]: the head's logit
                             # gradient at the example's own tokens
                             "kst": SDS((bsz, tf, ids.shape[-1]), gdt)},
            vocab=vocab)


def record_scale(c, s, xhat):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    _record(c, "scale",
            {"pg": SDS((xhat.shape[0], xhat.shape[-1]), jnp.float32)})


def record_shift(c, x):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    _record(c, "shift",
            {"pg": SDS((x.shape[0], x.shape[-1]), jnp.float32)})


def record_broadcast(c, p):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz = thresholds_of(c).shape[0]
    _record(c, "broadcast", {"pg": SDS((bsz,) + tuple(p.shape), jnp.float32)})


def record_lora(c, a, b, x):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz, tf = x.shape[0], _tfold(x)
    din, r, dout = a.shape[-2], a.shape[-1], b.shape[-1]
    gdt = jnp.result_type(x.dtype, b.dtype)
    _record(c, "lora", {"a1": SDS((bsz, tf, din), x.dtype),
                        "g1": SDS((bsz, tf, r), gdt),
                        "a2": SDS((bsz, tf, r), x.dtype),
                        "g2": SDS((bsz, tf, dout), gdt)})


def record_expert(c, w, x):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    e, cap, din = x.shape
    gdt = jnp.result_type(x.dtype, w.dtype)
    _record(c, "expert", {"x": SDS((e, cap, din), x.dtype),
                          "g": SDS((e, cap, w.shape[-1]), gdt),
                          "seg": SDS((e, cap), jnp.float32)})


def record_expert_grouped(c, w, x):
    if _RECORDER.get() is None or not isinstance(c, BkChannel):
        return
    bsz, e, cap, din = x.shape
    gdt = jnp.result_type(x.dtype, w.dtype)
    _record(c, "expert_grouped", {"x": SDS((bsz, e, cap, din), x.dtype),
                                  "g": SDS((bsz, e, cap, w.shape[-1]), gdt)})


def probe_recipes(loss_fn, params, batch, layout: GroupLayout,
                  batch_size: int) -> dict | None:
    """Discover per-group residual shapes; None when BK cannot apply. A
    tied group is captured or refused here, never left to the twopass
    fallback, whose norms would leave out the cross term of its uses."""
    rec, why = None, None
    if any(g.sensitivity_mult > 1 for g in layout.groups):
        # shared-site params (e.g. Zamba2's shared attention block): one
        # threshold leaf is consumed at several runtime sites inside a scan,
        # so sink cotangents would SUM residuals across sites — invalid.
        why = "shared-site parameters (sensitivity_mult > 1)"
    else:
        inf_tree = layout.pack_value(jnp.inf, batch_size)
        probe = {k: BkChannel(v, None, k) for k, v in inf_tree.items()}
        # any other failure to trace with channel leaves is a bug (in a
        # model that handles thresholds as raw arrays, or in a record_*
        # recorder): it raises rather than silently doubling the backward
        # passes
        with _recording() as rec:
            jax.eval_shape(lambda p, b, t: jnp.sum(loss_fn(p, b, t)),
                           params, batch, probe)
        if any(r.count > 1 for r in rec.values()):
            # one leaf, several call sites (e.g. MTP reuses head)
            rec, why = None, "a leaf consumed at several call sites"
    for name in layout.tied_groups:
        r = rec.get(name) if rec is not None else why
        if not (isinstance(r, Recipe) and r.kind == "tied"
                and r.c_ndim == inf_tree[name].ndim):
            raise ValueError(
                f"tied group {name!r}: BK captures a tied table as one "
                "unstacked embedding lookup plus one dp_tied_head per step, "
                f"found {r}")
    return rec


# ---------------------------------------------------------------------------
# Capture: one backward pass -> norms + residuals.
# ---------------------------------------------------------------------------


def build_channels(layout: GroupLayout, recipes: dict, batch_size: int):
    """Threshold tree with +inf thresholds and zero residual sinks.

    The sink prefix (scan/stack dims the model slices off before the
    primitive sees the leaf) is inferred from rank: leaf rank minus the
    recorded per-call threshold rank.
    """
    inf_tree = layout.pack_value(jnp.inf, batch_size)
    out = {}
    for g in layout.groups:
        leaf = inf_tree[g.name]
        r = recipes.get(g.name)
        if r is None:  # group never consumed by the loss: plain leaf,
            out[g.name] = leaf  # zero norms and zero grads fall out
            continue
        prefix = leaf.shape[:leaf.ndim - r.c_ndim]
        sink = {k: jnp.zeros(prefix + tuple(s.shape), s.dtype)
                for k, s in r.sinks.items()}
        out[g.name] = BkChannel(leaf, sink, g.name)
    return out


TIED_CROSS = "dp_tied_cross"  # named scope of the tied groups' cross term


def tied_cross(g, a, kst):
    """(B,) cross term Σ_{t,s} (g_t · a_s) kst[s, t] of a tied group: the
    inner product of one example's embedding gradient (gy rows g_t into
    rows ids_t) with its head gradient (Σ_s gl_s a_sᵀ), kst[s, t] =
    gl_s[ids_t]. H_s = Σ_t kst[s, t] g_t takes O(T² d) on the MXU with the
    operands in their own dtype; the last product sums in f32."""
    h = jnp.einsum("bst,btd->bsd", kst, g.astype(kst.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(a.astype(jnp.float32) * h, axis=(1, 2))


def capture_clipped(loss_fn, params, batch, layout: GroupLayout,
                    batch_size: int):
    """One backprop: (sum loss, (K, B) norms², residuals, recipes, (B,)
    tied cross term) or None.

    A tied group's norm² comes out of the backward as ‖G_e‖² + ‖G_h‖², the
    two uses' own; twice their cross term is added here, so the norm the
    factors see is the exact ‖G_e + G_hᵀ‖². The cross term returned is
    summed over the tied groups (zeros without one)."""
    recipes = probe_recipes(loss_fn, params, batch, layout, batch_size)
    if recipes is None:
        return None
    channels = build_channels(layout, recipes, batch_size)

    def f(t):
        return jnp.sum(loss_fn(params, batch, t))

    # prefer_fused off: the capture backward consumes norms + residuals
    # only; the composed ops keep the (unused) clipped-sum contraction a
    # separate op XLA dead-code-eliminates.
    with backend.scoped(prefer_fused=False, capture_residuals=True):
        val, grads = jax.value_and_grad(f)(channels)
    norm_tree = {k: (v.c if isinstance(v, BkChannel) else v)
                 for k, v in grads.items()}
    residuals = {k: v.sink for k, v in grads.items()
                 if isinstance(v, BkChannel)}
    cross = jnp.zeros((batch_size,), jnp.float32)
    for name in layout.tied_groups:
        sink = residuals[name]
        with jax.named_scope(TIED_CROSS):
            c = tied_cross(sink["g"], sink["a"], sink["kst"])
            norm_tree[name] = norm_tree[name] + 2.0 * c
        cross = cross + c
    norms = layout.unpack(norm_tree)
    return val, norms, residuals, recipes, cross


# ---------------------------------------------------------------------------
# Epilogue: scale-and-contract the cached residuals into clipped grads.
# ---------------------------------------------------------------------------


def _fold(x, per_call_ndim: int):
    """Collapse the stack prefix into one leading axis of size S (>= 1)."""
    prefix = x.shape[:x.ndim - per_call_ndim]
    s = int(np.prod(prefix, dtype=np.int64)) if prefix else 1
    return x.reshape((s,) + x.shape[x.ndim - per_call_ndim:]), prefix


def _leaf_grad(layout, recipes, residuals, f_rows, node: P, path, eng):
    gname = layout._leaf_group[path]
    grp = layout.group(gname)
    r = recipes.get(gname)
    if r is None:
        return jnp.zeros(node.shape, node.dtype)
    sink = residuals[gname]
    bsz = f_rows.shape[-1]
    f = jax.lax.dynamic_slice_in_dim(f_rows, grp.offset, grp.count, axis=0)
    f = f.reshape(grp.stack_shape + (bsz,)).astype(jnp.float32)
    per_elem = len(node.shape) - node.stack  # leaf rank below the stack dims
    kind = r.kind

    if kind in ("linear", "lora"):
        if kind == "lora":
            # adapter pair: leaf 'a' <- (x, g·scale @ Bᵀ); 'b' <- (x·A, g·scale)
            a_s, g_s = (("a1", "g1") if path[-1] == "a" else ("a2", "g2"))
            a, g = sink[a_s], sink[g_s]
        else:
            a, g = sink["a"], sink["g"]
        a4, _ = _fold(a, 3)
        g4, _ = _fold(g, 3)
        f2, _ = _fold(f, 1)
        if kind == "lora" or per_elem == 2:  # weight (or adapter factor)
            dw = eng.scale_contract(a4, g4, f2)
            return dw.reshape(node.shape).astype(node.dtype)
        db = jnp.einsum("sbto,sb->so", g4.astype(jnp.float32), f2)
        return db.reshape(node.shape).astype(node.dtype)

    if kind == "linear_blocked":
        m, ax = r.extras["m"], r.extras["block_axis"]
        a4, _ = _fold(sink["a"], 3)
        g4, _ = _fold(sink["g"], 3)
        f3 = f.reshape(-1, m, bsz)  # (S, M, B): stack_shape ends in (M,)
        if per_elem == 2:
            def per_el(a3, g3, fmb):
                aa, gg = ghost.fold_block_factors(a3, g3, fmb.T, ax)
                return jnp.einsum("bti,bto->io", aa, gg)

            dw = jax.vmap(per_el)(a4, g4, f3)
            return dw.reshape(node.shape).astype(node.dtype)
        if ax == "out":  # bias columns live with the 'out' blocks
            s_, b_, t_, dout = g4.shape
            gb = g4.reshape(s_, b_, t_, m, dout // m).astype(jnp.float32)
            db = jnp.einsum("sbtmo,smb->smo", gb, f3)
        else:  # 'in': whole bias folded into block 0 (see dp_linear_blocked)
            db = jnp.einsum("sbto,sb->so", g4.astype(jnp.float32), f3[:, 0])
        return db.reshape(node.shape).astype(node.dtype)

    if kind == "embed":
        vocab = r.extras["vocab"]
        g4, _ = _fold(sink["g"], 3)
        ids4, _ = _fold(jnp.round(sink["ids"]).astype(jnp.int32), 2)
        f2, _ = _fold(f, 1)
        dt = jax.vmap(
            lambda i2, g3, fb: ghost.clipped_sum_embed(i2, g3, fb, vocab)
        )(ids4, g4, f2)
        return dt.reshape(node.shape).astype(node.dtype)

    if kind == "tied":
        # the head's Σ_i f_i G_iᵀ A_i lands in the table's (V, d) layout
        # with no transposed copy; the embedding's clipped rows are
        # scattered into it in place (probe_recipes: unstacked, f is (B,))
        out = eng.scale_contract(sink["gh"], sink["a"], f)
        ids = jnp.round(sink["ids"]).astype(jnp.int32).reshape(-1)
        rows = (sink["g"].astype(jnp.float32) * f[:, None, None]
                ).reshape(-1, node.shape[-1])
        return out.at[ids].add(rows).astype(node.dtype)

    if kind in ("scale", "shift", "broadcast"):
        pg = sink["pg"]  # prefix + (B,) + per-call param shape
        lead = pg.ndim - (1 + per_elem)
        s_ = (int(np.prod(pg.shape[:lead], dtype=np.int64)) if lead else 1)
        pg2 = pg.reshape(s_, bsz, -1).astype(jnp.float32)
        out = jnp.einsum("sbr,sb->sr", pg2, f.reshape(s_, bsz))
        return out.reshape(node.shape).astype(node.dtype)

    if kind == "expert":
        # sinks carry prefix + (E, C, d): the expert axis is part of the
        # per-call shape, and the group stack_shape ends in (E,) — so
        # folding everything down to per-expert slices aligns with factors
        x4, _ = _fold(sink["x"], 3)  # (S, E, C, din), S = prod(scan prefix)
        g4, _ = _fold(sink["g"], 3)
        seg4, _ = _fold(jnp.round(sink["seg"]).astype(jnp.int32), 2)
        f3 = f.reshape(-1, bsz)  # (S·E, B): stack_shape ends in (E,)

        def per_el(xe, ge, se, fe):  # (C, din), (C, dout), (C,), (B,)
            fpad = jnp.concatenate([fe, jnp.zeros((1,), fe.dtype)])
            fslot = fpad[se]
            return jnp.einsum("cd,cf->df",
                              xe.astype(jnp.float32) * fslot[:, None],
                              ge.astype(jnp.float32))

        dw = jax.vmap(per_el)(x4.reshape((-1,) + x4.shape[-2:]),
                              g4.reshape((-1,) + g4.shape[-2:]),
                              seg4.reshape((-1,) + seg4.shape[-1:]), f3)
        return dw.reshape(node.shape).astype(node.dtype)

    if kind == "expert_grouped":
        x5, _ = _fold(sink["x"], 4)  # (S, B, E, C, din)
        g5, _ = _fold(sink["g"], 4)
        f3 = f.reshape(x5.shape[0], -1, bsz)  # (S, E, B)
        dw = jnp.einsum("sbecd,sbecf,seb->sedf", x5.astype(jnp.float32),
                        g5.astype(jnp.float32), f3)
        return dw.reshape(node.shape).astype(node.dtype)

    raise ValueError(f"unknown BK recipe kind {kind!r}")


def contract_clipped(layout: GroupLayout, recipes: dict, residuals: dict,
                     f_rows, *, eng=None, psum_axes=None):
    """Clipped summed grads from cached residuals + (K, B) clip factors.

    Returns a pytree matching the layout's spec (== the trainable params
    tree the two-pass drivers produce), in the spec leaf dtypes.

    psum_axes: when set (sharded execution, inside `shard_map`), every
    leaf's contraction is followed by a `lax.psum` over those mesh axes —
    and the epilogue is emitted INTERLEAVED: leaf i's contraction is issued
    before leaf i-1's psum, so the latency-hiding scheduler overlaps each
    layer's gradient reduction with the next layer's `scale_contract`
    instead of serializing one big tree-reduce after all the compute.
    """
    eng = eng or backend.active()

    def build(node, path):
        if isinstance(node, P):
            return _leaf_grad(layout, recipes, residuals, f_rows, node,
                              path, eng)
        return {k: build(v, path + (k,)) for k, v in node.items()}

    if psum_axes is None:
        with jax.named_scope("bk_epilogue_contract"):
            return build(layout._spec, ())

    leaves: list[tuple[tuple, P]] = []

    def collect(node, path):
        if isinstance(node, P):
            leaves.append((path, node))
            return
        for k in node:
            collect(node[k], path + (k,))

    collect(layout._spec, ())
    reduced: dict[tuple, Any] = {}
    prev = None  # (path, unreduced contraction)
    for path, node in leaves:
        with jax.named_scope("bk_epilogue_contract"):
            cur = _leaf_grad(layout, recipes, residuals, f_rows, node,
                             path, eng)
        if prev is not None:
            with jax.named_scope("bk_epilogue_grad_psum"):
                reduced[prev[0]] = jax.lax.psum(prev[1], psum_axes)
        prev = (path, cur)
    if prev is not None:
        with jax.named_scope("bk_epilogue_grad_psum"):
            reduced[prev[0]] = jax.lax.psum(prev[1], psum_axes)

    def rebuild(node, path):
        if isinstance(node, P):
            return reduced[path]
        return {k: rebuild(v, path + (k,)) for k, v in node.items()}

    return rebuild(layout._spec, ())

"""DP layer primitives: clipping fused into backpropagation via custom_vjp.

This module is the JAX realization of the paper's Sec. 3.1: "gradient
clipping for any layer can be performed as soon as backpropagation reaches
that layer". Each parametric primitive carries a `jax.custom_vjp` whose
backward rule

  1. computes per-example gradient norms² WITHOUT materializing per-example
     gradients (ghost trick, `repro.core.ghost` / Pallas kernels),
  2. forms clip factors and emits the already-clipped, already-summed
     parameter gradient in one fused contraction,
  3. passes the UNCLIPPED input cotangent downstream (Algorithm 1 line 11),
  4. reports the per-example norms² through the *threshold cotangent*:
     the threshold is passed as a per-example vector c (B,), and we define
     dL/dc := norms². A single jax.grad over (params, thresholds) therefore
     yields clipped gradients AND every group's norms in one backward pass.

Threshold encoding (one mechanism drives every clipping mode):
    c > 0      : clip to threshold c        -> factor min(1, c / ||g_i||)
    c == +inf  : no clipping                -> factor 1
    c < 0      : direct scale               -> factor |c|
The direct-scale encoding is what makes two-pass (flat / per-group /
per-device) clipping reuse the same primitives: pass 1 reads norms with
c=+inf (XLA dead-code-eliminates the unused weight contractions), the driver
computes group factors f_i, and pass 2 runs with c = -f_i which yields
exactly the group-clipped sums.

Every ghost op below resolves through the backend engine
(`repro.kernels.backend.active()`) at trace time — `xla` reference paths,
`pallas` kernels, or `auto` cost-model dispatch. Select with
`backend.scoped(...)` (done by `make_dp_train_step` from `DPConfig.backend`).

Book-keeping capture (repro.core.bk): when the threshold argument arrives
as a `bk.BkChannel` (only inside `backend.scoped(capture_residuals=True)`,
driven by `bk.capture_clipped`), the backward rule emits per-example norms²
through the threshold cotangent as usual but, instead of contracting the
clipped weight gradient, stashes the ghost residuals (activations + output
cotangents) through the channel's sink cotangent. Parameter cotangents are
ZERO in that mode — the BK epilogue (`bk.contract_clipped`) owns them; the
input cotangent stays the real one so backprop continues downstream.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bk
from repro.core.ghost import clip_factor  # noqa: F401  (re-export, public API)
from repro.kernels import backend


def _int_zero_cotangent(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# dp_linear: y = x @ w (+ b); group = {w, b}.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_linear(w: jax.Array, b: jax.Array | None, x: jax.Array, c: jax.Array
              ) -> jax.Array:
    bk.record_linear(c, w, b, x)
    y = x @ w
    if b is not None:
        y = y + b
    return y


def _dp_linear_fwd(w, b, x, c):
    return dp_linear(w, b, x, c), (w, b, x, c)


def _dp_linear_bwd(res, gy):
    w, b, x, c = res
    has_bias = b is not None
    eng = backend.active()
    dx = gy @ w.T
    bsz = x.shape[0]
    a3 = x.reshape(bsz, -1, x.shape[-1])
    g3 = gy.reshape(bsz, -1, gy.shape[-1])
    extra = eng.bias_norms_sq(g3) if has_bias else None
    if isinstance(c, bk.BkChannel):  # BK capture: norms + residual stash
        n = eng.linear_norms_sq(a3, g3)
        if has_bias:
            n = n + extra
        dc = bk.emit(c, n, a=a3, g=g3)
        db = jnp.zeros_like(b) if has_bias else None
        return jnp.zeros_like(w), db, dx, dc
    n, f, dw = eng.linear_clip(a3, g3, c, extra)
    dw = dw.astype(w.dtype)
    db = eng.clipped_sum_bias(g3, f).astype(w.dtype) if has_bias else None
    dc = n  # norms² through the threshold side channel
    return dw, db, dx, dc


dp_linear.defvjp(_dp_linear_fwd, _dp_linear_bwd)


# ---------------------------------------------------------------------------
# dp_linear_blocked: per-shard clipping (groups = Megatron weight blocks).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def dp_linear_blocked(w, b, x, c, block_axis: str = "out"):
    """Linear layer whose weight grad is clipped per column/row block.

    c: (B, M) encoded thresholds, one per block. This is the TPU analogue of
    the paper's per-device clipping: block m lives on model-shard m, its norm
    and clip factor are computed from shard-local data only, so no norm
    all-reduce appears in the partitioned HLO.
    """
    bk.record_linear_blocked(c, w, b, x, block_axis)
    y = x @ w
    if b is not None:
        y = y + b
    return y


def _dp_linear_blocked_fwd(w, b, x, c, block_axis):
    return dp_linear_blocked(w, b, x, c, block_axis), (w, b, x, c)


def _dp_linear_blocked_bwd(block_axis, res, gy):
    w, b, x, c = res
    has_bias = b is not None
    eng = backend.active()
    dx = gy @ w.T
    bsz = x.shape[0]
    a3 = x.reshape(bsz, -1, x.shape[-1])
    g3 = gy.reshape(bsz, -1, gy.shape[-1])
    m = bk.thresholds_of(c).shape[-1]
    n = eng.linear_norms_sq_blocked(a3, g3, m, block_axis=block_axis)
    if has_bias:
        # bias columns live with the 'out' blocks; for 'in' blocking the bias
        # is whole on every shard -> fold into block 0 to keep accounting
        # conservative and simple.
        if block_axis == "out":
            gb = g3.reshape(bsz, g3.shape[1], m, -1)
            sb = jnp.sum(gb, axis=1)
            n = n + jnp.sum(sb.astype(jnp.float32) ** 2, axis=-1)
        else:
            n = n.at[:, 0].add(eng.bias_norms_sq(g3))
    if isinstance(c, bk.BkChannel):
        dc = bk.emit(c, n, a=a3, g=g3)
        db = jnp.zeros_like(b) if has_bias else None
        return jnp.zeros_like(w), db, dx, dc
    f = clip_factor(c, n)  # (B, M)
    dw = eng.clipped_sum_linear_blocked(a3, g3, f, block_axis=block_axis
                                        ).astype(w.dtype)
    if has_bias:
        if block_axis == "out":
            gb = g3.reshape(bsz, g3.shape[1], m, -1)
            db = jnp.einsum("btmo,bm->mo", gb,
                            f.astype(g3.dtype)).reshape(-1).astype(w.dtype)
        else:
            db = eng.clipped_sum_bias(g3, f[:, 0]).astype(w.dtype)
    else:
        db = None
    return dw, db, dx, n


dp_linear_blocked.defvjp(_dp_linear_blocked_fwd, _dp_linear_blocked_bwd)


# ---------------------------------------------------------------------------
# dp_embed: y = table[ids]; collision-exact ghost norms.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_embed(table: jax.Array, ids: jax.Array, c: jax.Array) -> jax.Array:
    bk.record_embed(c, table, ids)
    return jnp.take(table, ids, axis=0)


def _dp_embed_fwd(table, ids, c):
    # zero-size sentinel carries (vocab, dtype) without keeping the table alive
    sentinel = jnp.zeros((table.shape[0], 0), table.dtype)
    return dp_embed(table, ids, c), (sentinel, ids, c)


def _dp_embed_bwd(res, gy):
    sentinel, ids, c = res
    vocab, dtype = sentinel.shape[0], sentinel.dtype
    eng = backend.active()
    bsz = ids.shape[0]
    ids2 = ids.reshape(bsz, -1)
    g3 = gy.reshape(bsz, -1, gy.shape[-1])
    n = eng.embed_norms_sq(ids2, g3)
    if isinstance(c, bk.BkChannel):
        # token ids ride the float sink channel (exact below 2^24)
        dc = bk.emit(c, n, g=g3, ids=ids2.astype(jnp.float32))
        return (jnp.zeros((vocab, g3.shape[-1]), dtype),
                _int_zero_cotangent(ids), dc)
    f = clip_factor(c, n)
    dtable = eng.clipped_sum_embed(ids2, g3, f, vocab).astype(dtype)
    return dtable, _int_zero_cotangent(ids), n


dp_embed.defvjp(_dp_embed_fwd, _dp_embed_bwd)


# ---------------------------------------------------------------------------
# dp_tied_head: logits = x @ tableᵀ, the LM head reading the embedding table.
#
# The table is one leaf used twice, so one example's gradient of it is the
# embedding's scatter G_e (rows ids_t <- gy_t) plus the head's G_hᵀ =
# Σ_s gl_s x_sᵀ, and its exact squared norm is
#     ‖G_e‖² + ‖G_h‖² + 2 Σ_{t,s} (gy_t · x_s) gl_s[ids_t].
# The head's backward keeps the last factor, kst[b, s, t] = gl[b, s,
# ids[b, t]], for the BK capture, which adds the cross term once the
# embedding's gy is known (bk.capture_clipped).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_tied_head(table: jax.Array, x: jax.Array, ids: jax.Array,
                 c: jax.Array) -> jax.Array:
    """table (V, d), x (B, T, d), ids (B, T) the tokens the same examples'
    embedding looked up; c: the embedding group's threshold leaf."""
    bk.record_tied_head(c, table, x, ids)
    return jnp.einsum("btd,vd->btv", x, table)


def _dp_tied_head_fwd(table, x, ids, c):
    return dp_tied_head(table, x, ids, c), (table, x, ids, c)


def _dp_tied_head_bwd(res, gy):
    table, x, ids, c = res
    eng = backend.active()
    dx = jnp.einsum("btv,vd->btd", gy, table)
    bsz = x.shape[0]
    a3 = x.reshape(bsz, -1, x.shape[-1])
    g3 = gy.reshape(bsz, -1, gy.shape[-1])
    if isinstance(c, bk.BkChannel):
        n = eng.linear_norms_sq(a3, g3)
        with jax.named_scope(bk.TIED_CROSS):
            # one column of S rows per token, the example's ids shared by
            # every row: an index per element (take_along_axis) reads ~100x
            # the bytes (21.7 GB against 0.22 GB at B=2, T=2048, V=122,753)
            kst = jax.vmap(lambda gb, ib: jnp.take(gb, ib, axis=1))(
                g3, ids.reshape(bsz, -1))
        dc = bk.emit(c, n, a=a3, gh=g3, kst=kst)
        return (jnp.zeros_like(table), dx, _int_zero_cotangent(ids), dc)
    # Σ_i f_i G_iᵀ A_i lands in the table's (V, d) layout; its norms are
    # those of A_iᵀ G_i. This clips the head's use alone, sound only at
    # c = +inf: clipping.check_tied_mode refuses every mode that would
    # arrive here with a finite threshold, leaving non_private and the
    # materializing naive_flat
    n, _, dt = eng.linear_clip(g3, a3, c)
    return dt.astype(table.dtype), dx, _int_zero_cotangent(ids), n


dp_tied_head.defvjp(_dp_tied_head_fwd, _dp_tied_head_bwd)


# ---------------------------------------------------------------------------
# dp_scale / dp_shift: elementwise gain / bias parameters (norm layers).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_scale(s: jax.Array, xhat: jax.Array, c: jax.Array) -> jax.Array:
    bk.record_scale(c, s, xhat)
    return xhat * s


def _dp_scale_fwd(s, xhat, c):
    return dp_scale(s, xhat, c), (s, xhat, c)


def _dp_scale_bwd(res, gy):
    s, xhat, c = res
    eng = backend.active()
    dxhat = gy * s
    n = eng.scale_norms_sq(xhat, gy)
    if isinstance(c, bk.BkChannel):
        # the per-example grad itself is small ((B, d)): stash it directly
        pg = jnp.sum((gy * xhat).reshape(gy.shape[0], -1, gy.shape[-1])
                     .astype(jnp.float32), axis=1)
        return jnp.zeros_like(s), dxhat, bk.emit(c, n, pg=pg)
    f = clip_factor(c, n)
    ds = eng.clipped_sum_scale(xhat, gy, f).astype(s.dtype)
    return ds, dxhat, n


dp_scale.defvjp(_dp_scale_fwd, _dp_scale_bwd)


@jax.custom_vjp
def dp_shift(b: jax.Array, x: jax.Array, c: jax.Array) -> jax.Array:
    bk.record_shift(c, x)
    return x + b


def _dp_shift_fwd(b, x, c):
    sentinel = jnp.zeros((0,), b.dtype)
    return dp_shift(b, x, c), (sentinel, c)


def _dp_shift_bwd(res, gy):
    sentinel, c = res
    dtype = sentinel.dtype
    eng = backend.active()
    bsz = gy.shape[0]
    g3 = gy.reshape(bsz, -1, gy.shape[-1])
    n = eng.bias_norms_sq(g3)
    if isinstance(c, bk.BkChannel):
        pg = jnp.sum(g3.astype(jnp.float32), axis=1)  # (B, d) per-ex grad
        return (jnp.zeros((g3.shape[-1],), dtype), gy,
                bk.emit(c, n, pg=pg))
    f = clip_factor(c, n)
    db = eng.clipped_sum_bias(g3, f).astype(dtype)
    return db, gy, n


dp_shift.defvjp(_dp_shift_fwd, _dp_shift_bwd)


# ---------------------------------------------------------------------------
# dp_broadcast: the broadcast-trick fallback for arbitrary small parameters
# (SSM decay vectors, RWKV time-mix params, ...). Returns the parameter with
# a leading batch dim; the cotangent arriving back IS the per-example grad.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_broadcast(p: jax.Array, c: jax.Array) -> jax.Array:
    bk.record_broadcast(c, p)
    bsz = bk.thresholds_of(c).shape[0]
    return jnp.broadcast_to(p, (bsz,) + p.shape)


def _dp_broadcast_fwd(p, c):
    sentinel = jnp.zeros((0,), p.dtype)
    return dp_broadcast(p, c), (sentinel, c)


def _dp_broadcast_bwd(res, gy):
    sentinel, c = res
    dtype = sentinel.dtype
    n = backend.active().vector_norms_sq(gy)
    if isinstance(c, bk.BkChannel):
        # the cotangent arriving here IS the (B, ...) per-example grad
        return (jnp.zeros(gy.shape[1:], dtype),
                bk.emit(c, n, pg=gy.astype(jnp.float32)))
    f = clip_factor(c, n)
    dp = jnp.tensordot(f.astype(jnp.float32),
                       gy.astype(jnp.float32), axes=1).astype(dtype)
    return dp, n


dp_broadcast.defvjp(_dp_broadcast_fwd, _dp_broadcast_bwd)


# ---------------------------------------------------------------------------
# dp_expert_linear: exact per-example clipping through MoE token dispatch.
#
# Dispatched expert buffers mix tokens from different examples, so the
# per-example norm of expert e's weight gradient needs example-masked grams:
#     n_{e,i} = sum_{slots s,s' of e with ex(s)=ex(s')=i} <x_s,x_s'> <g_s,g_s'>
# computed per expert as rowsums of (X Xᵀ ⊙ G Gᵀ ⊙ EqMask) segment-summed by
# example id. Each expert is its own clipping group (the MoE analogue of
# "a layer"), so thresholds arrive as (E, B).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_expert_linear(w: jax.Array, x: jax.Array, exids: jax.Array,
                     c: jax.Array) -> jax.Array:
    """w: (E, din, dout); x: (E, C, din) dispatched slots; exids: (E, C)
    example id per slot (-1 for empty slots); c: (E, B) encoded thresholds."""
    bk.record_expert(c, w, x)
    return jnp.einsum("ecd,edf->ecf", x, w)


def _dp_expert_fwd(w, x, exids, c):
    return dp_expert_linear(w, x, exids, c), (w, x, exids, c)


def _dp_expert_bwd(res, gy):
    w, x, exids, c = res
    bsz = bk.thresholds_of(c).shape[-1]
    dx = jnp.einsum("ecf,edf->ecd", gy, w)
    valid = exids >= 0
    seg = jnp.where(valid, exids, bsz)  # invalid -> overflow bucket

    def per_expert(carry, inp):
        xe, ge, se = inp  # (C, din), (C, dout), (C,)
        xf = xe.astype(jnp.float32)
        gf = ge.astype(jnp.float32)
        gram = (xf @ xf.T) * (gf @ gf.T)  # (C, C)
        eq = (se[:, None] == se[None, :]).astype(jnp.float32)
        rows = jnp.sum(gram * eq, axis=-1)  # (C,)
        n_e = jax.ops.segment_sum(rows, se, num_segments=bsz + 1)[:bsz]
        return carry, n_e

    _, n = jax.lax.scan(per_expert, 0, (x, gy, seg))  # n: (E, B)
    if isinstance(c, bk.BkChannel):
        dc = bk.emit(c, n, x=x, g=gy, seg=seg.astype(jnp.float32))
        return jnp.zeros_like(w), dx, _int_zero_cotangent(exids), dc
    f = clip_factor(c, n)  # (E, B)
    fpad = jnp.concatenate([f, jnp.zeros((f.shape[0], 1), f.dtype)], axis=-1)
    fslot = jnp.take_along_axis(fpad, seg, axis=-1)  # (E, C)
    dw = jnp.einsum("ecd,ecf->edf", x * fslot[..., None].astype(x.dtype), gy
                    ).astype(w.dtype)
    return dw, dx, _int_zero_cotangent(exids), n


dp_expert_linear.defvjp(_dp_expert_fwd, _dp_expert_bwd)


# ---------------------------------------------------------------------------
# dp_expert_linear_grouped: per-(example, expert) dispatch buffers.
#
# Beyond-paper optimization (EXPERIMENTS.md §Perf): when the dispatch buffer
# is laid out (B, E, cap_pe, d) — every example owns its slots — per-example
# norms need NO example-masked (C, C) grams: the per-(b, e) gradient block
# is Σ_s x_s g_sᵀ over that example's own slots, so the norm uses the same
# gram/outer dual as plain linears, at per-example slot counts
# (≈ T·top_k/E instead of B·T·top_k/E). Flops drop ~B× vs the masked-gram
# exact path of dp_expert_linear.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def dp_expert_linear_grouped(w: jax.Array, x: jax.Array, c: jax.Array
                             ) -> jax.Array:
    """w: (E, din, dout); x: (B, E, C, din) per-example dispatch buffers
    (empty slots zero); c: (E, B) encoded thresholds."""
    bk.record_expert_grouped(c, w, x)
    return jnp.einsum("becd,edf->becf", x, w)


def _dp_expert_grouped_fwd(w, x, c):
    return dp_expert_linear_grouped(w, x, c), (w, x, c)


def _dp_expert_grouped_bwd(res, gy):
    w, x, c = res
    bsz, e, cap, din = x.shape
    dout = gy.shape[-1]
    dx = jnp.einsum("becf,edf->becd", gy, w)
    if isinstance(c, bk.BkChannel):
        gram_x = jnp.einsum("becd,beCd->becC", x.astype(jnp.float32),
                            x.astype(jnp.float32))
        gram_g = jnp.einsum("becf,beCf->becC", gy.astype(jnp.float32),
                            gy.astype(jnp.float32))
        n = jnp.sum(gram_x * gram_g, axis=(2, 3)).T  # (E, B)
        dc = bk.emit(c, n, x=x, g=gy)
        return jnp.zeros_like(w), dx, dc
    gram_cost = cap * cap * (din + dout)
    outer_cost = cap * din * dout
    use_outer = (outer_cost < gram_cost) and (din * dout <= (1 << 22))
    if use_outer:
        # VECTORIZED over B: the (B, E, din, dout) transient shards over the
        # data axis (b) AND the expert/ff model axis — a lax.scan over
        # examples here would serialize the batch and force GSPMD to gather
        # every other device's examples each iteration (measured: 80 TB/step
        # of all-reduces on granite; see EXPERIMENTS.md §Perf A1/A2).
        dw_be = jnp.einsum("becd,becf->bedf", x.astype(jnp.float32),
                           gy.astype(jnp.float32))
        n = jnp.sum(dw_be * dw_be, axis=(2, 3)).T  # (E, B)
        f = clip_factor(c, n)  # (E, B)
        dw = jnp.einsum("bedf,be->edf", dw_be, f.T).astype(w.dtype)
        return dw, dx, n
    gram_x = jnp.einsum("becd,beCd->becC", x.astype(jnp.float32),
                        x.astype(jnp.float32))
    gram_g = jnp.einsum("becf,beCf->becC", gy.astype(jnp.float32),
                        gy.astype(jnp.float32))
    n = jnp.sum(gram_x * gram_g, axis=(2, 3)).T  # (E, B)
    f = clip_factor(c, n)  # (E, B)
    gs = gy * f.T[:, :, None, None].astype(gy.dtype)
    dw = jnp.einsum("becd,becf->edf", x, gs).astype(w.dtype)
    return dw, dx, n


dp_expert_linear_grouped.defvjp(_dp_expert_grouped_fwd,
                                _dp_expert_grouped_bwd)

"""Per-example gradient norms WITHOUT materializing per-example gradients.

This is the computational core of the paper's fused per-layer clipping
(Sec. 3.1), built on the "ghost norm" identity (Goodfellow 2015;
Li et al. 2022b Sec. 4): for a linear layer y = x @ W with per-example
activations A_i in R^{T x d_in} and output cotangents G_i in R^{T x d_out},
the per-example weight gradient is A_i^T G_i and

    || A_i^T G_i ||_F^2  =  < A_i A_i^T ,  G_i G_i^T >        (gram path)
                         =  sum_{t,t'} <a_t, a_t'> <g_t, g_t'>

which costs O(T^2 (d_in + d_out)) instead of O(T d_in d_out) and never forms
the (d_in x d_out) per-example matrix. When T^2 > d_in * d_out the outer
path (materialize per-example grad, but only transiently inside the fused
op) is cheaper; `linear_norms_sq` picks automatically, mirroring the mixed
ghost-clipping dispatch of Bu et al. (2022).

These are the pure-jnp reference implementations; `repro.kernels.ops`
provides Pallas TPU kernels with identical semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ACC_DTYPE = jnp.float32


def _as3d(x: jax.Array) -> jax.Array:
    """(B, d) -> (B, 1, d); (B, T, d) unchanged; higher ranks folded into T."""
    if x.ndim == 2:
        return x[:, None, :]
    if x.ndim == 3:
        return x
    return x.reshape(x.shape[0], -1, x.shape[-1])


def gram_path_cost(t: int, din: int, dout: int) -> int:
    return t * t * (din + dout + 1)


def outer_path_cost(t: int, din: int, dout: int) -> int:
    return t * din * dout + din * dout


# Memory guardrails for path selection (elements, not bytes).
# NOTE (§Perf): these reason about LOGICAL shapes; under model-axis sharding
# the outer path's (B, din, dout) transient is sharded on dout and the cap
# can safely be raised ~model_size x (scoped engine config — see
# repro.kernels.backend), which also avoids the gram path's un-shardable
# T² work — a large win at long sequence.
DEFAULT_OUTER_MAX_ELEMS = 1 << 22  # per-example materialized grad cap
DEFAULT_GRAM_CHUNK = 1024  # row-block size for the chunked gram path
_OUTER_MAX_ELEMS = DEFAULT_OUTER_MAX_ELEMS
_GRAM_CHUNK = DEFAULT_GRAM_CHUNK

_EPS = 1e-12


def configure(*, outer_max_elems: int | None = None,
              gram_chunk: int | None = None) -> dict:
    """Set module-global ghost-path policy (returns the previous values).

    DEPRECATED for engine users: prefer `repro.kernels.backend.scoped(...)`,
    which threads the policy through without mutating globals — jitted step
    functions then capture their policy statically at trace time. Direct
    callers of this module still honor these globals as defaults.
    """
    global _OUTER_MAX_ELEMS, _GRAM_CHUNK
    prev = {"outer_max_elems": _OUTER_MAX_ELEMS, "gram_chunk": _GRAM_CHUNK}
    if outer_max_elems is not None:
        _OUTER_MAX_ELEMS = outer_max_elems
    if gram_chunk is not None:
        _GRAM_CHUNK = gram_chunk
    return prev


def clip_factor(c: jax.Array, norms_sq: jax.Array) -> jax.Array:
    """Per-example clip factor from encoded thresholds.

    Encoding (one mechanism drives every clipping mode — see
    core.dp_layers module doc):
        c > 0     -> min(1, c / ||g_i||)   (clip to threshold)
        c == +inf -> 1                     (no clipping)
        c < 0     -> |c|                   (direct scale, two-pass modes)
    """
    # dp_clip_factor: the static auditor's anchor (repro.analysis) — norm
    # data is consumed here; what leaves is a bounded scaling factor
    with jax.named_scope("dp_clip_factor"):
        c = c.astype(jnp.float32)
        n = norms_sq.astype(jnp.float32)
        clipped = jnp.minimum(1.0, c * jax.lax.rsqrt(n + _EPS))
        factor = jnp.where(jnp.isinf(c), 1.0, clipped)
        return jnp.where(c < 0, -c, factor)


def linear_norms_sq(a: jax.Array, g: jax.Array, *,
                    force_path: str | None = None,
                    outer_max_elems: int | None = None,
                    gram_chunk: int | None = None) -> jax.Array:
    """(B,) squared Frobenius norms of per-example grads A_i^T G_i.

    a: (B, T, d_in) or (B, d_in) activations into the layer.
    g: (B, T, d_out) or (B, d_out) cotangents w.r.t. the layer output.
    force_path: 'gram' | 'gram_chunked' | 'outer' | None (auto).
    outer_max_elems / gram_chunk: explicit policy (None -> module globals).

    Auto selection minimizes flops subject to a memory cap: the outer path
    transiently materializes (B, d_in, d_out) so it is only allowed for
    small weights; the gram path materializes (B, T, T), chunked into
    (B, chunk, T) row blocks when T is large — the same blocking the Pallas
    kernel uses in VMEM.
    """
    outer_cap = (_OUTER_MAX_ELEMS if outer_max_elems is None
                 else outer_max_elems)
    chunk = _GRAM_CHUNK if gram_chunk is None else gram_chunk
    a3, g3 = _as3d(a).astype(ACC_DTYPE), _as3d(g).astype(ACC_DTYPE)
    b, t, din = a3.shape
    dout = g3.shape[-1]
    if t == 1:
        # rank-1: ||a_i g_i^T||_F^2 = ||a_i||^2 ||g_i||^2
        return (jnp.sum(a3 * a3, axis=(1, 2)) * jnp.sum(g3 * g3, axis=(1, 2)))
    path = force_path
    if path is None:
        outer_ok = din * dout <= outer_cap
        if outer_ok and outer_path_cost(t, din, dout) < gram_path_cost(t, din, dout):
            path = "outer"
        elif t > chunk:
            path = "gram_chunked"
        else:
            path = "gram"
    if path == "gram":
        gram_a = jnp.einsum("bti,bsi->bts", a3, a3)
        gram_g = jnp.einsum("bto,bso->bts", g3, g3)
        return jnp.sum(gram_a * gram_g, axis=(1, 2))
    if path == "gram_chunked":
        nb = -(-t // chunk)
        pad = nb * chunk - t
        ap = jnp.pad(a3, ((0, 0), (0, pad), (0, 0)))
        gp = jnp.pad(g3, ((0, 0), (0, pad), (0, 0)))
        ac = ap.reshape(b, nb, chunk, din)
        gc = gp.reshape(b, nb, chunk, dout)

        def body(acc, blk):
            ablk, gblk = blk  # (B, chunk, d)
            ga = jnp.einsum("bci,bti->bct", ablk, ap)
            gg = jnp.einsum("bco,bto->bct", gblk, gp)
            return acc + jnp.sum(ga * gg, axis=(1, 2)), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros((b,), ACC_DTYPE),
            (jnp.moveaxis(ac, 1, 0), jnp.moveaxis(gc, 1, 0)))
        return acc
    if path == "outer":
        pg = jnp.einsum("bti,bto->bio", a3, g3)
        return jnp.sum(pg * pg, axis=(1, 2))
    raise ValueError(f"unknown path {path!r}")


def bias_norms_sq(g: jax.Array) -> jax.Array:
    """(B,) squared norms of per-example bias grads sum_t g_t."""
    g3 = _as3d(g).astype(ACC_DTYPE)
    s = jnp.sum(g3, axis=1)
    return jnp.sum(s * s, axis=-1)


def embed_norms_sq(ids: jax.Array, g: jax.Array, *,
                   gram_chunk: int | None = None) -> jax.Array:
    """(B,) squared norms of per-example embedding grads (collision-exact).

    Per-example grad of the embedding table is the scatter-add of cotangent
    rows g_t into rows ids_t; repeated tokens within an example collide, so

        ||grad_i||^2 = sum_{t,t'} 1[ids_t == ids_t'] <g_t, g_t'>
                     = < EqualityMask_i , G_i G_i^T >.
    """
    chunk = _GRAM_CHUNK if gram_chunk is None else gram_chunk
    ids2 = ids.reshape(ids.shape[0], -1)
    g3 = _as3d(g).astype(ACC_DTYPE)
    b, t, d = g3.shape
    if t <= chunk:
        eq = (ids2[:, :, None] == ids2[:, None, :]).astype(ACC_DTYPE)
        gram_g = jnp.einsum("btd,bsd->bts", g3, g3)
        return jnp.sum(eq * gram_g, axis=(1, 2))
    # chunked: row blocks against the full sequence
    nb = -(-t // chunk)
    pad = nb * chunk - t
    gp = jnp.pad(g3, ((0, 0), (0, pad), (0, 0)))
    # pad ids with -1 (padded g rows are zero, so their matches contribute 0)
    ip = jnp.pad(ids2, ((0, 0), (0, pad)), constant_values=-1)
    gc = gp.reshape(b, nb, chunk, d)
    ic = ip.reshape(b, nb, chunk)

    def body(acc, blk):
        gblk, iblk = blk
        gram = jnp.einsum("bcd,btd->bct", gblk, gp)
        eq = (iblk[:, :, None] == ip[:, None, :]).astype(ACC_DTYPE)
        return acc + jnp.sum(gram * eq, axis=(1, 2)), None

    acc, _ = jax.lax.scan(body, jnp.zeros((b,), ACC_DTYPE),
                          (jnp.moveaxis(gc, 1, 0), jnp.moveaxis(ic, 1, 0)))
    return acc


def scale_norms_sq(xhat: jax.Array, g: jax.Array) -> jax.Array:
    """(B,) squared norms for an elementwise-scale parameter y = s * xhat.

    Per-example grad ds_i = sum_t (g ⊙ xhat)_t, a (d,)-vector — cheap to
    materialize per example.
    """
    gx = _as3d(g * xhat).astype(ACC_DTYPE)
    s = jnp.sum(gx, axis=1)
    return jnp.sum(s * s, axis=-1)


def vector_norms_sq(per_example_grad: jax.Array) -> jax.Array:
    """(B,) norms² for the broadcast-trick fallback: grads already (B, ...)."""
    g = per_example_grad.astype(ACC_DTYPE)
    return jnp.sum(g * g, axis=tuple(range(1, g.ndim)))


# ---------------------------------------------------------------------------
# Blocked (per-shard) norms: norms of column/row blocks of the weight grad.
# ---------------------------------------------------------------------------


def linear_norms_sq_blocked(
    a: jax.Array, g: jax.Array, num_blocks: int, *, block_axis: str = "out"
) -> jax.Array:
    """(B, M) squared norms of per-example grads of M weight blocks.

    Used by per-shard (per-device) clipping: the weight is Megatron-sharded
    into M column blocks (block_axis='out', column parallel) or M row blocks
    (block_axis='in', row parallel); each block is its own clipping group so
    the norm reduction never crosses shards.
    """
    a3, g3 = _as3d(a).astype(ACC_DTYPE), _as3d(g).astype(ACC_DTYPE)
    b, t, din = a3.shape
    dout = g3.shape[-1]
    m = num_blocks
    if block_axis == "out":
        if dout % m:
            raise ValueError(f"dout={dout} not divisible by num_blocks={m}")
        gb = g3.reshape(b, t, m, dout // m)
        gram_a = jnp.einsum("bti,bsi->bts", a3, a3)
        gram_gb = jnp.einsum("btmo,bsmo->bmts", gb, gb)
        return jnp.einsum("bts,bmts->bm", gram_a, gram_gb)
    if block_axis == "in":
        if din % m:
            raise ValueError(f"din={din} not divisible by num_blocks={m}")
        ab = a3.reshape(b, t, m, din // m)
        gram_g = jnp.einsum("bto,bso->bts", g3, g3)
        gram_ab = jnp.einsum("btmi,bsmi->bmts", ab, ab)
        return jnp.einsum("bts,bmts->bm", gram_g, gram_ab)
    raise ValueError(f"block_axis must be 'out' or 'in', got {block_axis!r}")


# ---------------------------------------------------------------------------
# Fused clipped sums.
# ---------------------------------------------------------------------------


def clipped_sum_linear(a: jax.Array, g: jax.Array, factors: jax.Array
                       ) -> jax.Array:
    """sum_i c_i A_i^T G_i as one scaled contraction. factors: (B,).

    f32 accumulation throughout (like every clipped sum here): quantizing
    the clip factor to bf16 would let clipped contributions exceed the
    sensitivity bound. The Pallas clip_reduce kernel orders it otherwise
    but keeps the factor in f32: bf16 operands go into the MXU as they are
    (their products are exact in f32), each example's partial sum
    accumulates in f32, and the f32 factor scales that sum. The two differ
    only by the order of f32 rounding.
    """
    a3, g3 = _as3d(a).astype(ACC_DTYPE), _as3d(g).astype(ACC_DTYPE)
    gs = g3 * factors[:, None, None].astype(ACC_DTYPE)
    return jnp.einsum("bti,bto->io", a3, gs)


def fold_block_factors(a3: jax.Array, g3: jax.Array, factors: jax.Array,
                       block_axis: str = "out"
                       ) -> tuple[jax.Array, jax.Array]:
    """Fold per-block clip factors (B, M) into the blocked operand.

    Returns (a3, g3) in f32 with the factor absorbed into the tensor whose
    feature axis is blocked — shared by the jnp path below and the Pallas
    backend (which then runs the big contraction with unit row factors).
    The f32 fold keeps clip factors unquantized (sensitivity bound) and
    matches the kernels' accumulation dtype.
    """
    a3 = a3.astype(ACC_DTYPE)
    g3 = g3.astype(ACC_DTYPE)
    b, t, din = a3.shape
    dout = g3.shape[-1]
    m = factors.shape[-1]
    if block_axis == "out":
        g3 = (g3.reshape(b, t, m, dout // m)
              * factors[:, None, :, None].astype(ACC_DTYPE)
              ).reshape(b, t, dout)
    else:
        a3 = (a3.reshape(b, t, m, din // m)
              * factors[:, None, :, None].astype(ACC_DTYPE)
              ).reshape(b, t, din)
    return a3, g3


def clipped_sum_linear_blocked(
    a: jax.Array, g: jax.Array, factors: jax.Array, *, block_axis: str = "out"
) -> jax.Array:
    """sum_i A_i^T diag-blocked(c_i) G_i; factors: (B, M) per block."""
    a3, g3 = fold_block_factors(_as3d(a), _as3d(g), factors, block_axis)
    return jnp.einsum("bti,bto->io", a3, g3)


def clipped_sum_bias(g: jax.Array, factors: jax.Array) -> jax.Array:
    # accumulate in f32: the B*T reduction and the clip factors must not
    # quantize to bf16 or clipped contributions can exceed the sensitivity
    # bound (callers cast the result back to the param dtype)
    g3 = _as3d(g).astype(ACC_DTYPE)
    return jnp.einsum("bto,b->o", g3, factors.astype(ACC_DTYPE))


def clipped_sum_embed(ids: jax.Array, g: jax.Array, factors: jax.Array,
                      vocab: int) -> jax.Array:
    ids2 = ids.reshape(ids.shape[0], -1)
    g3 = _as3d(g).astype(ACC_DTYPE)  # f32 factors + accumulation, as above
    gs = (g3 * factors[:, None, None].astype(ACC_DTYPE)
          ).reshape(-1, g3.shape[-1])
    out = jnp.zeros((vocab, g3.shape[-1]), dtype=ACC_DTYPE)
    return out.at[ids2.reshape(-1)].add(gs)


def clipped_sum_scale(xhat: jax.Array, g: jax.Array, factors: jax.Array
                      ) -> jax.Array:
    gx = _as3d(g * xhat).astype(ACC_DTYPE)  # f32 accumulation, as bias
    return jnp.einsum("btd,b->d", gx, factors.astype(ACC_DTYPE))

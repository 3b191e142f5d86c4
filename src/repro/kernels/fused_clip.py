"""Pallas TPU kernel: fused ghost-norm + clip + reduce in ONE pass over A, G.

The paper's Sec. 3.1 fused per-layer clipping, taken one step further: the
separate norm kernel (`ghost_norm`) and clipped-sum kernel (`clip_reduce`)
each stream A and G from HBM. This kernel computes, per example b,

    n_b  = <A_b A_bᵀ, G_b G_bᵀ>                     (ghost norm²)
    f_b  = clip_factor(c_b, n_b + extra_b)          (threshold encoding)
    dW  += f_b · A_bᵀ G_b                           (clipped summed grad)

with A and G read from HBM ONCE. `extra_b` carries norm² contributions of
co-grouped parameters (the bias of the layer) so the factor matches the
whole clipping group.

Grid = (B, T/bt, T/bt), b outermost, sequentially executed:
  * (i, j) with j >= i accumulate the gram contraction into a VMEM norm
    accumulator (off-diagonal doubled — symmetry, as in `ghost_norm`);
  * diagonal steps (i == j) also accumulate A_iᵀ G_i into a VMEM dW
    accumulator — the unscaled per-example grad, built from blocks already
    resident in VMEM for the gram pass;
  * the last step for b computes f_b from the completed norm and adds
    f_b · dW_b into the kernel output (fixed output block, revisited per b).

Feature dims are NOT tiled: the VMEM budget is 2·din·dout f32 (acc + out
block) + 4 sequence blocks, so this kernel is for din·dout up to ~1-2M
elements; the backend engine guards on `vmem_limit_bytes` and falls back to
the two-kernel composition for larger layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256
# Per-example scalars (threshold, extra norm², norm²) travel broadcast over
# one f32 (8, 128) vreg tile: Mosaic needs the last two block dims aligned
# to (8, 128) or equal to the array's, so (1, 1) blocks cannot compile.
TILE = (8, 128)


def padded_dims(din: int, dout: int) -> tuple[int, int]:
    """Feature-dim padding this kernel applies (f32 sublane/lane tiles).

    Shared with the backend engine's VMEM guard so footprint estimates and
    actual kernel buffers stay in lockstep.
    """
    dip = -(-din // 8) * 8
    djp = -(-dout // 128) * 128 if dout > 128 else dout
    return dip, djp


def _kernel(a_i, a_j, g_i, g_j, c_ref, e_ref, n_out, dw_out, n_acc, dw_acc,
            *, nt):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    upper = j >= i

    @pl.when((i == 0) & (j == 0))
    def _init():
        n_acc[...] = jnp.zeros_like(n_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(upper)
    def _norm():
        gram_a = jax.lax.dot_general(
            a_i[0].astype(jnp.float32), a_j[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        gram_g = jax.lax.dot_general(
            g_i[0].astype(jnp.float32), g_j[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        n_acc[...] += (jnp.sum(gram_a * gram_g)
                       * jnp.where(i == j, 1.0, 2.0))

    @pl.when(i == j)
    def _grad():
        dw_acc[...] += jax.lax.dot_general(
            a_i[0].astype(jnp.float32), g_i[0].astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((i == nt - 1) & (j == nt - 1))
    def _emit():
        # lazy import: core.__init__ transitively imports this module, so a
        # top-level import would see it partially initialized. The shared
        # encoded-threshold helper is plain jnp and runs on the VPU over the
        # broadcast (8, 128) tiles; every lane holds the same value.
        from repro.core.ghost import clip_factor
        n = n_acc[...]
        n_out[0] = n
        f = jnp.max(clip_factor(c_ref[0], n + e_ref[0]))
        scaled = f * dw_acc[...]
        dw_out[...] = jnp.where(b == 0, scaled, dw_out[...] + scaled)


def fused_norm_clip(a: jax.Array, g: jax.Array, c: jax.Array,
                    extra_norms_sq: jax.Array | None = None, *,
                    bt: int = DEFAULT_BT, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """Returns (norms_sq (B,), clipped summed grad (din, dout) f32).

    a: (B, T, din); g: (B, T, dout); c: (B,) ENCODED thresholds (see
    core.dp_layers: +inf = no clip, negative = direct scale |c|);
    extra_norms_sq: (B,) norm² of co-grouped params folded into the factor
    (e.g. the layer bias), or None. The returned norms_sq is the WEIGHT
    contribution only (caller adds extra back for the side channel).
    """
    b, t, din = a.shape
    dout = g.shape[-1]
    bt = min(bt, t)
    tp = -(-t // bt) * bt
    # pad feature dims to the f32 lane/sublane tile so MXU shapes align
    dip, djp = padded_dims(din, dout)
    a_p = jnp.pad(a, ((0, 0), (0, tp - t), (0, dip - din)))
    g_p = jnp.pad(g, ((0, 0), (0, tp - t), (0, djp - dout)))
    c2 = jnp.broadcast_to(c.astype(jnp.float32)[:, None, None], (b,) + TILE)
    e2 = (jnp.zeros((b,) + TILE, jnp.float32) if extra_norms_sq is None
          else jnp.broadcast_to(
              extra_norms_sq.astype(jnp.float32)[:, None, None], (b,) + TILE))
    nt = tp // bt

    grid = (b, nt, nt)
    norms, dw = pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, dip), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((1, bt, dip), lambda bb, i, j: (bb, j, 0)),
            pl.BlockSpec((1, bt, djp), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((1, bt, djp), lambda bb, i, j: (bb, j, 0)),
            pl.BlockSpec((1,) + TILE, lambda bb, i, j: (bb, 0, 0)),
            pl.BlockSpec((1,) + TILE, lambda bb, i, j: (bb, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1,) + TILE, lambda bb, i, j: (bb, 0, 0)),
            pl.BlockSpec((dip, djp), lambda bb, i, j: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b,) + TILE, jnp.float32),
            jax.ShapeDtypeStruct((dip, djp), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM(TILE, jnp.float32),        # per-example norm² acc
            pltpu.VMEM((dip, djp), jnp.float32),  # per-example grad acc
        ],
        interpret=interpret,
        name="fused_norm_clip",
    )(a_p, a_p, g_p, g_p, c2, e2)
    return norms[:, 0, 0], dw[:din, :dout]

"""Pallas TPU kernel for the book-keeping (BK) epilogue:  Σ_i f_i A_iᵀ G_i
per stack element, in ONE pass over the cached ghost residuals.

The BK execution engine (Bu et al. 2022, arXiv:2210.00038; see
`repro.core.bk`) replaces the second backward pass of flat / per-group
clipping with a cheap contraction over residuals (a, g) cached during the
single norm-computing backprop. This kernel is that contraction for linear
layers, including the scanned-layer case where residuals carry a leading
stack axis S (one slice per scanned layer):

    out[s] = Σ_i f[s, i] · A[s, i]ᵀ G[s, i]        (din × dout, f32)

Layout: rows r = flattened (B·T) per stack slice, grid = (S, din/bi,
dout/bj, R/bt) with r innermost and sequential; the per-row factor is
fused into the RHS load so the scaled G never exists in HBM. VMEM per
step: (bt×bi) + (bt×bj) + (bt×1) inputs + (bi×bj) f32 accumulator ≈ 0.8
MiB at the 256-tile defaults, once per stack slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BI = 256
DEFAULT_BJ = 256
DEFAULT_BT = 256


def _kernel(a_ref, g_ref, f_ref, out_ref, acc, *, nr):
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a_blk = a_ref[0].astype(jnp.float32)  # (bt, bi)
    g_blk = g_ref[0].astype(jnp.float32)  # (bt, bj)
    f_blk = f_ref[0].astype(jnp.float32)  # (bt, 1)
    acc[...] += jax.lax.dot_general(
        a_blk, g_blk * f_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(r == nr - 1)
    def _emit():
        out_ref[0] = acc[...]


def scale_contract(a: jax.Array, g: jax.Array, factors: jax.Array, *,
                   bi: int = DEFAULT_BI, bj: int = DEFAULT_BJ,
                   bt: int = DEFAULT_BT, interpret: bool = False) -> jax.Array:
    """(S, din, dout) = Σ_i f[s,i] A[s,i]ᵀ G[s,i] from cached BK residuals.

    a: (S, B, T, din) or (B, T, din); g: same leading shape with dout;
    factors: (S, B) or (B,). The 3-D form returns (din, dout).
    """
    squeeze = a.ndim == 3
    if squeeze:
        a, g, factors = a[None], g[None], factors[None]
    s, b, t, din = a.shape
    dout = g.shape[-1]
    rows = b * t
    a2 = a.reshape(s, rows, din)
    g2 = g.reshape(s, rows, dout)
    f2 = jnp.repeat(factors.astype(jnp.float32), t, axis=-1)[..., None]
    bi = min(bi, din)
    bj = min(bj, dout)
    bt = min(bt, rows)
    dip = -(-din // bi) * bi
    djp = -(-dout // bj) * bj
    rp = -(-rows // bt) * bt
    a2 = jnp.pad(a2, ((0, 0), (0, rp - rows), (0, dip - din)))
    g2 = jnp.pad(g2, ((0, 0), (0, rp - rows), (0, djp - dout)))
    f2 = jnp.pad(f2, ((0, 0), (0, rp - rows), (0, 0)))
    nr = rp // bt
    grid = (s, dip // bi, djp // bj, nr)
    out = pl.pallas_call(
        functools.partial(_kernel, nr=nr),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bi), lambda ss, i, j, r: (ss, r, i)),
            pl.BlockSpec((1, bt, bj), lambda ss, i, j, r: (ss, r, j)),
            pl.BlockSpec((1, bt, 1), lambda ss, i, j, r: (ss, r, 0)),
        ],
        out_specs=pl.BlockSpec((1, bi, bj), lambda ss, i, j, r: (ss, i, j)),
        out_shape=jax.ShapeDtypeStruct((s, dip, djp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
        name="bk_scale_contract",
    )(a2, g2, f2)
    out = out[:, :din, :dout]
    return out[0] if squeeze else out

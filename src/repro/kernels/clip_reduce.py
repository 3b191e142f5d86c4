"""Pallas TPU kernel: fused clip-scale-accumulate  Σ_i c_i A_iᵀ G_i.

The second half of the paper's fused per-layer clipping op: once clip
factors c_i are known, the clipped summed weight gradient is one scaled
contraction. The kernel fuses the per-row scaling into the matmul's RHS
load so the scaled G is never written to HBM:

  rows r = flattened (B·T);    grid = (din/bi, dout/bj, R/bt)  (r innermost)
  acc(bi, bj) f32 scratch; acc += A[r-block]ᵀ (G[r-block] ⊙ c[r-block])

VMEM: (bt x bi) + (bt x bj) + (bt x 1) + acc (bi x bj) f32
  = 256·256·4·3 + 256·4 ≈ 0.8 MiB.  MXU dims (bi, bj, bt) all 128-aligned.
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


def _kernel(a_ref, g_ref, c_ref, out_ref, acc, *, nr):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a_blk = a_ref[...].astype(jnp.float32)  # (bt, bi)
    g_blk = g_ref[...].astype(jnp.float32)  # (bt, bj)
    c_blk = c_ref[...].astype(jnp.float32)  # (bt, 1)
    acc[...] += jax.lax.dot_general(
        a_blk, g_blk * c_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(r == nr - 1)
    def _emit():
        out_ref[...] = acc[...]


def clip_reduce(a: jax.Array, g: jax.Array, factors: jax.Array, *,
                bi: int = DEFAULT_BI, bj: int = DEFAULT_BJ,
                bt: int = DEFAULT_BT, interpret: bool = False) -> jax.Array:
    """(din, dout) = Σ_i c_i A_iᵀ G_i.  a: (B,T,din); g: (B,T,dout);
    factors: (B,)."""
    b, t, din = a.shape
    dout = g.shape[-1]
    rows = b * t
    a2 = a.reshape(rows, din)
    g2 = g.reshape(rows, dout)
    c2 = jnp.repeat(factors.astype(jnp.float32), t)[:, None]  # (rows, 1)
    bi = min(bi, din)
    bj = min(bj, dout)
    bt = min(bt, rows)
    dip = -(-din // bi) * bi
    djp = -(-dout // bj) * bj
    rp = -(-rows // bt) * bt
    a2 = jnp.pad(a2, ((0, rp - rows), (0, dip - din)))
    g2 = jnp.pad(g2, ((0, rp - rows), (0, djp - dout)))
    c2 = jnp.pad(c2, ((0, rp - rows), (0, 0)))
    nr = rp // bt
    grid = (dip // bi, djp // bj, nr)
    out = pl.pallas_call(
        functools.partial(_kernel, nr=nr),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bi), lambda i, j, r: (r, i)),
            pl.BlockSpec((bt, bj), lambda i, j, r: (r, j)),
            pl.BlockSpec((bt, 1), lambda i, j, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dip, djp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
        name="clip_reduce",
    )(a2, g2, c2)
    return out[:din, :dout]

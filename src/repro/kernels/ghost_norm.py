"""Pallas TPU kernel: per-example gradient norms² without materialization.

Computes n_b = <A_b A_bᵀ, G_b G_bᵀ> for every example b — the ghost-norm
identity at the heart of the paper's fused per-layer clipping — with the
(T, T) grams built BLOCK BY BLOCK in VMEM and never written to HBM:

  grid = (B, T/bt, T/bt, max(din, dout)/dk)   (k innermost, sequential)

  for each (b, i, j) with j >= i: two f32 VMEM scratch accumulators hold the
  (bt, bt) gram blocks A_i A_jᵀ and G_i G_jᵀ, accumulated over feature chunks
  k (the MXU contraction dim stays hardware-aligned); on the last chunk the
  blocks are multiplied elementwise, reduced, and accumulated into out[b].
  The summand <A_iA_jᵀ, G_iG_jᵀ> is SYMMETRIC in (i, j), so tile pairs with
  j < i are skipped and off-diagonal contributions doubled — ~2x fewer MXU
  flops at large T (the j < i grid steps issue no dots).

VMEM footprint: 4 input blocks (bt x dk) + 2 scratch (bt x bt) f32
  = 4·256·512·4B + 2·256·256·4B ≈ 2.6 MiB  « 16 MiB v5e VMEM.

HBM traffic: A and G are each read (T/bt) times (once per row-block pass) —
vs. the XLA path which writes/reads the (B, T, T) grams to HBM. For
T=4096, d=2560: kernel moves 2·T·d·(T/bt) ≈ 0.7 GB/example of reads and no
gram writes; XLA moves ≥ 2·T²·4 = 134 MB/example of gram writes + reads
plus the same input reads. The win grows with T — exactly the regime the
paper's per-layer clipping targets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256  # sequence tile
DEFAULT_DK = 512  # feature-chunk tile
# Each example's norm² is written broadcast over one f32 (8, 128) vreg tile:
# Mosaic needs the last two block dims aligned to (8, 128) or equal to the
# array's, so a (1, 1) block over a (B, 1) output cannot compile for TPU.
OUT_TILE = (8, 128)


def _kernel(a_i, a_j, g_i, g_j, out_ref, acc_a, acc_g, *, nda, ndg, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    upper = j >= i  # symmetry: skip the strict lower triangle of tile pairs

    @pl.when(k == 0)
    def _init():
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_g[...] = jnp.zeros_like(acc_g)

    @pl.when(upper & (k < nda))
    def _acc_a():
        ab_i = a_i[0].astype(jnp.float32)
        ab_j = a_j[0].astype(jnp.float32)
        acc_a[...] += jax.lax.dot_general(
            ab_i, ab_j, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(upper & (k < ndg))
    def _acc_g():
        gb_i = g_i[0].astype(jnp.float32)
        gb_j = g_j[0].astype(jnp.float32)
        acc_g[...] += jax.lax.dot_general(
            gb_i, gb_j, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _emit():
        # off-diagonal (i, j) tiles stand in for (j, i) as well -> double
        val = (jnp.sum(acc_a[...] * acc_g[...])
               * jnp.where(i == j, 1.0, 2.0)
               * jnp.where(upper, 1.0, 0.0))
        first = (i == 0) & (j == 0)
        out_ref[...] = jnp.where(first, val, out_ref[...] + val)


def ghost_norm(a: jax.Array, g: jax.Array, *, bt: int = DEFAULT_BT,
               dk: int = DEFAULT_DK, interpret: bool = False) -> jax.Array:
    """(B,) squared per-example grad norms. a: (B,T,din); g: (B,T,dout).

    interpret=True executes the kernel body on the host (validation mode
    off-TPU); the default compiles it through Mosaic.
    """
    b, t, din = a.shape
    dout = g.shape[-1]
    bt = min(bt, t)
    # pad T to a multiple of bt and features to multiples of dk
    tp = -(-t // bt) * bt
    dap = -(-din // dk) * dk if din > dk else din
    dgp = -(-dout // dk) * dk if dout > dk else dout
    dka = min(dk, dap)
    dkg = min(dk, dgp)
    a_p = jnp.pad(a, ((0, 0), (0, tp - t), (0, dap - din)))
    g_p = jnp.pad(g, ((0, 0), (0, tp - t), (0, dgp - dout)))
    nda, ndg = dap // dka, dgp // dkg
    nk = max(nda, ndg)
    nt = tp // bt

    grid = (b, nt, nt, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nda=nda, ndg=ndg, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, dka), lambda bb, i, j, k: (bb, i, jnp.minimum(k, nda - 1))),
            pl.BlockSpec((1, bt, dka), lambda bb, i, j, k: (bb, j, jnp.minimum(k, nda - 1))),
            pl.BlockSpec((1, bt, dkg), lambda bb, i, j, k: (bb, i, jnp.minimum(k, ndg - 1))),
            pl.BlockSpec((1, bt, dkg), lambda bb, i, j, k: (bb, j, jnp.minimum(k, ndg - 1))),
        ],
        out_specs=pl.BlockSpec((1,) + OUT_TILE,
                               lambda bb, i, j, k: (bb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b,) + OUT_TILE, jnp.float32),
        scratch_shapes=[
            # two gram-block accumulators held in VMEM across the k loop
            pltpu.VMEM((bt, bt), jnp.float32),
            pltpu.VMEM((bt, bt), jnp.float32),
        ],
        interpret=interpret,
        name="ghost_norm",
    )(a_p, a_p, g_p, g_p)
    return out[:, 0, 0]


# ---------------------------------------------------------------------------
# Blocked (per-shard) ghost norms: (B, M) per-block norms² in one kernel.
# ---------------------------------------------------------------------------


def _blocked_kernel(s_i, s_j, x_i, x_j, out_ref, acc_s, acc_x, *,
                    nds, ndx, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)
    k = pl.program_id(4)
    upper = j >= i

    @pl.when(k == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        acc_x[...] = jnp.zeros_like(acc_x)

    @pl.when(upper & (k < nds))
    def _acc_s():
        acc_s[...] += jax.lax.dot_general(
            s_i[0].astype(jnp.float32), s_j[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(upper & (k < ndx))
    def _acc_x():
        acc_x[...] += jax.lax.dot_general(
            x_i[0, 0].astype(jnp.float32), x_j[0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _emit():
        val = (jnp.sum(acc_s[...] * acc_x[...])
               * jnp.where(i == j, 1.0, 2.0)
               * jnp.where(upper, 1.0, 0.0))
        first = (i == 0) & (j == 0)
        out_ref[...] = jnp.where(first, val, out_ref[...] + val)


def ghost_norm_blocked(a: jax.Array, g: jax.Array, num_blocks: int, *,
                       block_axis: str = "out", bt: int = DEFAULT_BT,
                       dk: int = DEFAULT_DK, interpret: bool = False
                       ) -> jax.Array:
    """(B, M) squared per-example norms of M weight blocks — the per-shard
    (per-device) clipping hot path. a: (B, T, din); g: (B, T, dout).

    block_axis='out': block m is columns [m*dout/M, (m+1)*dout/M) of W
    (Megatron column parallel); 'in' blocks rows of W (row parallel). The
    ghost identity per block needs the SHARED tensor's full gram and the
    blocked tensor's per-block gram:

        n[b, m] = <S_b S_bᵀ, X_b^m (X_b^m)ᵀ>,  S = a, X = g for 'out'
                                                (roles swap for 'in').

    grid = (B, M, T/bt, T/bt, nk), j >= i via the same symmetry trick as
    `ghost_norm`; the shared gram block is recomputed per m (reads stay in
    HBM->VMEM streams; nothing is duplicated in HBM).
    """
    b, t, din = a.shape
    dout = g.shape[-1]
    m = num_blocks
    if block_axis == "out":
        if dout % m:
            raise ValueError(f"dout={dout} not divisible by num_blocks={m}")
        shared, ds = a, din
        blocked = g.reshape(b, t, m, dout // m).transpose(0, 2, 1, 3)
        dx = dout // m
    elif block_axis == "in":
        if din % m:
            raise ValueError(f"din={din} not divisible by num_blocks={m}")
        shared, ds = g, dout
        blocked = a.reshape(b, t, m, din // m).transpose(0, 2, 1, 3)
        dx = din // m
    else:
        raise ValueError(f"block_axis must be 'out' or 'in', got {block_axis!r}")

    bt = min(bt, t)
    tp = -(-t // bt) * bt
    dsp = -(-ds // dk) * dk if ds > dk else ds
    dxp = -(-dx // dk) * dk if dx > dk else dx
    dks = min(dk, dsp)
    dkx = min(dk, dxp)
    s_p = jnp.pad(shared, ((0, 0), (0, tp - t), (0, dsp - ds)))
    x_p = jnp.pad(blocked, ((0, 0), (0, 0), (0, tp - t), (0, dxp - dx)))
    nds, ndx = dsp // dks, dxp // dkx
    nk = max(nds, ndx)
    nt = tp // bt

    grid = (b, m, nt, nt, nk)
    out = pl.pallas_call(
        functools.partial(_blocked_kernel, nds=nds, ndx=ndx, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, dks),
                         lambda bb, mm, i, j, k: (bb, i, jnp.minimum(k, nds - 1))),
            pl.BlockSpec((1, bt, dks),
                         lambda bb, mm, i, j, k: (bb, j, jnp.minimum(k, nds - 1))),
            pl.BlockSpec((1, 1, bt, dkx),
                         lambda bb, mm, i, j, k: (bb, mm, i, jnp.minimum(k, ndx - 1))),
            pl.BlockSpec((1, 1, bt, dkx),
                         lambda bb, mm, i, j, k: (bb, mm, j, jnp.minimum(k, ndx - 1))),
        ],
        out_specs=pl.BlockSpec((1, 1) + OUT_TILE,
                               lambda bb, mm, i, j, k: (bb, mm, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m) + OUT_TILE, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bt, bt), jnp.float32),
            pltpu.VMEM((bt, bt), jnp.float32),
        ],
        interpret=interpret,
        name="ghost_norm_blocked",
    )(s_p, s_p, x_p, x_p)
    return out[:, :, 0, 0]

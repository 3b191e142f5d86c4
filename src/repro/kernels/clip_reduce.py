"""Pallas TPU kernel: the scaled contraction  Σ_b c_b A_bᵀ G_b.

One kernel body serves two contractions:

  `clip_reduce`     the second half of the paper's fused per-layer clipping
                    op: once clip factors c_b are known, the clipped summed
                    weight gradient of one layer, (B, T, din) x (B, T, dout)
                    -> (din, dout), inside the backward.
  `scale_contract`  the book-keeping (BK) epilogue (Bu et al. 2022,
                    arXiv:2210.00038; `repro.core.bk`): the same sum over
                    the residuals cached by the single norm-computing
                    backprop, with a leading stack axis S (one slice per
                    scanned layer), (S, B, T, din) x (S, B, T, dout) ->
                    (S, din, dout), after the backward. The pallas_call is
                    named `bk_scale_contract`.

The stack axis appears only in `scale_contract`'s grid and index maps; the
body and the tiles are the same. The scaled G is never written to HBM:

  rows r = flattened (B·T'), T' = T rounded up to a multiple of bt (zero
  rows, which add nothing, only where T is not one already); so a row
  block belongs to one example, b = r // (T'/bt).
  grid = ([S,] cdiv(din, bi), cdiv(dout, bj), B·T'/bt)   (r innermost)
  out(bi, bj) f32, resident over r:  out += c_b · (A[r-block]ᵀ G[r-block])

The operands enter the MXU in their own dtype (bf16 in training: its
products are exact in f32), the MXU accumulates in f32, and c_b, an
unquantized f32 read from SMEM, scales the f32 partial sum of its example.
Mixed operand dtypes are promoted in VMEM; f32 operands stay f32.

Ragged edges cost no copies: the last block along din or dout reads past
the array's edge, and what it reads there lands only in output rows or
columns past the edge, which the write-back masks. The contracted row
axis is exact.

Tiles come from the shape (`tiles`): bt = T split into equal blocks of at
most 512 rows; bi, bj = din, dout split into the fewest equal 128-aligned
blocks of at most 2560 (the whole axis where it fits). Larger tiles won at
every shape of the qwen3-4b cell on a TPU v5e (PERF.md): each grid
step's bi·bj/(bi + bj) FLOP per operand byte is past the chip's ridge, and
the per-step cost of the accumulate and of transposing the A block
shrinks. VMEM, with the output block as the accumulator:
  2·bt·(bi + bj)·itemsize     double-buffered operand blocks
  + 2·bi·bj·4                 double-buffered f32 output block
  + bi·bj·4 + bt·bi·itemsize  the dot's result and the transposed A block
= 10 + 50 + 27.5 MiB at (512, 2560, 2560) in bf16 (and 100 MiB at most in
f32), past the default scoped limit, so the kernel asks Mosaic for it and a
quarter more (`vmem_limit_bytes`), within a v5e core's 128 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BT_MAX = 512
BF_MAX = 2560


def _split(n: int, cap: int, align: int) -> int:
    """The tile that cuts n into the fewest equal blocks of at most `cap`,
    rounded up to `align`; the whole axis where it fits."""
    if n <= cap:
        return n
    k = pl.cdiv(n, cap)
    return pl.cdiv(pl.cdiv(n, k), align) * align


def tiles(t: int, din: int, dout: int, dtype) -> tuple[int, int, int]:
    """The derived (bi, bj, bt) for operands of `dtype` at (T, din, dout).

    bt is a multiple of the dtype's sublane tile (8 rows of f32, 16 of
    bf16), so the zero-row pad of T up to a multiple of bt is at most a
    few rows per example, and none at T = 512 or 2048."""
    sub = 32 // jnp.dtype(dtype).itemsize
    bt = pl.cdiv(pl.cdiv(t, pl.cdiv(t, BT_MAX)), sub) * sub
    return _split(din, BF_MAX, 128), _split(dout, BF_MAX, 128), bt


def _kernel(c_ref, a_ref, g_ref, out_ref, *, per_example, dtype, stacked):
    r = pl.program_id(3 if stacked else 2)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    part = jax.lax.dot_general(
        a_ref[...].astype(dtype), g_ref[...].astype(dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    if stacked:
        out_ref[...] += c_ref[pl.program_id(0), r // per_example] * part
    else:
        out_ref[...] += c_ref[r // per_example] * part


def _contract(a, g, factors, *, bi, bj, bt, interpret, name):
    """a: (*S, B, T, din); g: (*S, B, T, dout); factors: (*S, B), where *S
    is nothing or one stack axis -> (*S, din, dout) f32."""
    lead = a.shape[:-3]
    b, t, din = a.shape[-3:]
    dout = g.shape[-1]
    dtype = jnp.promote_types(a.dtype, g.dtype)
    di, dj, dt = tiles(t, din, dout, dtype)
    bi = di if bi is None else min(bi, din)
    bj = dj if bj is None else min(bj, dout)
    bt = dt if bt is None else bt
    tp = pl.cdiv(t, bt) * bt
    if tp != t:
        pad = ((0, 0),) * (a.ndim - 2) + ((0, tp - t), (0, 0))
        a = jnp.pad(a, pad)
        g = jnp.pad(g, pad)
    a2 = a.reshape(*lead, b * tp, din)
    g2 = g.reshape(*lead, b * tp, dout)
    isz = jnp.dtype(dtype).itemsize
    vmem = (2 * bt * (bi + bj) * isz + 3 * bi * bj * 4 + bt * bi * isz)
    n = len(lead)
    squeezed = (None,) * n  # the stack slice, absent from the body's blocks

    def spec(shape, index):
        # the stack index leads each block's index
        return pl.BlockSpec(squeezed + shape,
                            lambda *ix: ix[:n] + index(*ix[n:]))

    return pl.pallas_call(
        functools.partial(_kernel, per_example=tp // bt, dtype=dtype,
                          stacked=bool(n)),
        grid=(*lead, pl.cdiv(din, bi), pl.cdiv(dout, bj), b * tp // bt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec((bt, bi), lambda i, j, r: (r, i)),
            spec((bt, bj), lambda i, j, r: (r, j)),
        ],
        out_specs=spec((bi, bj), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((*lead, din, dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (n + 2) + ("arbitrary",),
            vmem_limit_bytes=max(32 << 20, vmem * 5 // 4)),
        interpret=interpret,
        name=name,
    )(factors.astype(jnp.float32), a2, g2)


def clip_reduce(a: jax.Array, g: jax.Array, factors: jax.Array, *,
                bi: int | None = None, bj: int | None = None,
                bt: int | None = None, interpret: bool = False) -> jax.Array:
    """(din, dout) f32 = Σ_b c_b A_bᵀ G_b.  a: (B,T,din); g: (B,T,dout);
    factors: (B,). A tile left `None` comes from `tiles`."""
    return _contract(a, g, factors, bi=bi, bj=bj, bt=bt,
                     interpret=interpret, name="clip_reduce")


def scale_contract(a: jax.Array, g: jax.Array, factors: jax.Array, *,
                   bi: int | None = None, bj: int | None = None,
                   bt: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """(S, din, dout) f32 = Σ_b f[s,b] A[s,b]ᵀ G[s,b] from cached BK
    residuals.  a: (S, B, T, din); g: (S, B, T, dout); factors: (S, B).
    The 3-D form (B, T, din), (B,) is one slice and returns (din, dout).
    A tile left `None` comes from `tiles`."""
    if a.ndim == 3:
        out = scale_contract(a[None], g[None], factors[None], bi=bi, bj=bj,
                             bt=bt, interpret=interpret)
        return out.reshape(out.shape[1:])
    return _contract(a, g, factors, bi=bi, bj=bj, bt=bt,
                     interpret=interpret, name="bk_scale_contract")

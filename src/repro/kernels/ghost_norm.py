"""Pallas TPU kernel: per-example gradient norms² without materialization.

Computes n_b = <A_b A_bᵀ, G_b G_bᵀ> for every example b — the ghost-norm
identity at the heart of the paper's fused per-layer clipping — with the
(T, T) grams built block by block in VMEM and never written to HBM.

The summand <A_iA_jᵀ, G_iG_jᵀ> of a pair of row blocks is symmetric in
(i, j), so only the upper triangle is walked and off-diagonal terms are
doubled:

  rows of T in blocks of bt (all of T in one block up to BT_MAX rows);
  pairs p = the nt(nt+1)/2 block pairs (i, j), j >= i, from scalar-
  prefetched tables; feature chunks k, innermost:
  grid = (B, pairs, max(din/dka, dout/dkg))

  Two f32 VMEM accumulators hold the pair's gram blocks A_i A_jᵀ and
  G_i G_jᵀ over the chunks, in (bs, bs) sub-tiles: a diagonal pair builds
  only the sub-tiles on and above its diagonal from one read of its row
  block, an off-diagonal pair all of them. On the last chunk the two are
  multiplied elementwise, summed and added into out[b].

A diagonal pair reads its blocks once: the j-side operands (present only
where T spans several blocks) keep the block index of the step before, so
the pipeline issues no copy for them. A chunk index past an operand's last
chunk is held at the last one, so the narrower operand is not read again
while the wider one finishes.

The operands enter the MXU in their own dtype (bf16 in training: its
products are exact in f32) and the MXU accumulates in f32. Mixed operand
dtypes are promoted in VMEM; f32 operands stay f32.

Ragged edges cost no copies. The last feature chunk of an operand whose
width no chunk divides, and the last row block where T spans several
blocks and no block divides it, read past the array's edge; an iota compare
zeroes what lies past it, on that chunk or block only, so it adds nothing
to either gram.

Tiles come from the shape (`tiles`): bt = all of T up to BT_MAX rows, else
equal blocks of whole sub-tiles; bs = bt split into equal sub-tiles of at
most BS_MAX rows (128-aligned where there are several, so the block may
reach past T); dka, dkg = din, dout split into the fewest equal 128-aligned
chunks of at most DK_MAX (the whole axis where it fits), halved until the
working set fits VMEM_BUDGET. With all of T in one block each operand is
read from HBM once, and a step does (nsub + 1)·bs/2 FLOPs per byte it
reads (nsub = bt/bs), past a v5e's ridge of about 240. On a TPU v5e,
summed over each benchmark cell's linears, these tiles came within 5 % of
the best tried (128- and 512-row sub-tiles, 4096-wide chunks, 1024-row
blocks walked in pairs) and took 2.6 to 2.7 times less device time than
256 x 512 tiles over padded operands (PERF.md). VMEM (`_vmem`):
  (1 or 2)·2·bt·(dka + dkg)·itemsize    double-buffered operand blocks
  + 2·bt²·4                             the two f32 gram accumulators
  + 2·bt·max(dka, dkg)·itemsize + 4·bs²·4   loaded sub-tiles and products
= 25 + 32 + 16 + 1 MiB at MiniCPM's tied head (T = 2048, chunks of 1152
and 2048 in bf16), past the default scoped limit, so the kernel asks
Mosaic for it and a quarter more (`vmem_limit_bytes`), within a v5e
core's 128 MiB.

HBM traffic: with T in one block, each of A and G is read once, B·T·(din +
dout)·itemsize bytes; with nt blocks, nt times. The XLA path writes
and reads the (B, T, T) grams besides.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BT_MAX = 2048  # rows of T held in one block: two f32 grams of 16 MiB
BS_MAX = 256   # rows of a gram sub-tile
DK_MAX = 2048  # feature-chunk cap
VMEM_BUDGET = 96 << 20  # of a v5e core's 128 MiB
# ghost_norm_blocked's tiles
DEFAULT_BT = 256  # sequence tile
DEFAULT_DK = 512  # feature-chunk tile
# Each example's norm² is written broadcast over one f32 (8, 128) vreg tile:
# Mosaic needs the last two block dims aligned to (8, 128) or equal to the
# array's, so a (1, 1) block over a (B, 1) output cannot compile for TPU.
OUT_TILE = (8, 128)


class Tiles(NamedTuple):
    bt: int   # rows of a block of T
    bs: int   # rows of a gram sub-tile inside a block
    dka: int  # A's feature chunk
    dkg: int  # G's feature chunk


def _split(n: int, cap: int, align: int) -> int:
    """The tile that cuts n into the fewest equal blocks of at most `cap`,
    rounded up to `align`; the whole axis where it fits."""
    if n <= cap:
        return n
    k = pl.cdiv(n, cap)
    return pl.cdiv(pl.cdiv(n, k), align) * align


def _vmem(tl: Tiles, t: int, itemsize: int) -> int:
    """Bytes of VMEM the kernel needs at tiles `tl` over a sequence of t."""
    sides = 1 if tl.bt >= t else 2
    return (sides * 2 * tl.bt * (tl.dka + tl.dkg) * itemsize
            + 2 * tl.bt * tl.bt * 4
            + 2 * tl.bt * max(tl.dka, tl.dkg) * itemsize
            + 4 * tl.bs * tl.bs * 4)


def tiles(t: int, din: int, dout: int, dtype, *, bt: int | None = None,
          dk: int | None = None) -> Tiles:
    """The tiles of `ghost_norm` for operands of `dtype` at (T, din, dout).

    An explicit `bt` is the row block and its own sub-tile; an explicit
    `dk` is both operands' feature chunk. A derived sub-tile that does not
    span its block is a multiple of 128 rows, since it indexes the lanes of
    the f32 grams too; the block may then reach past T."""
    isz = jnp.dtype(dtype).itemsize
    if bt is not None:
        bt = bs = min(bt, t)
    else:
        rows = pl.cdiv(t, pl.cdiv(t, BT_MAX))
        nsub = pl.cdiv(rows, BS_MAX)
        bs = rows if nsub == 1 else pl.cdiv(pl.cdiv(rows, nsub), 128) * 128
        bt = nsub * bs
    if dk is not None:
        return Tiles(bt, bs, min(dk, din), min(dk, dout))
    cap = DK_MAX
    while True:
        tl = Tiles(bt, bs, _split(din, cap, 128), _split(dout, cap, 128))
        if cap <= 128 or _vmem(tl, t, isz) <= VMEM_BUDGET:
            return tl
        cap //= 2


def vmem_bytes(t: int, din: int, dout: int, dtype, *, bt: int | None = None,
               dk: int | None = None) -> int:
    """VMEM `ghost_norm` needs at (T, din, dout) for operands of `dtype`."""
    return _vmem(tiles(t, din, dout, dtype, bt=bt, dk=dk), t,
                 jnp.dtype(dtype).itemsize)


def _pairs(nt: int) -> np.ndarray:
    """(4, P) int32 tables over the upper-triangle block pairs, in walk
    order: i, j, and the row block and batch offset (0 or 1) the j-side
    operands fetch. An off-diagonal pair fetches (j, this example); a
    diagonal pair, which reads only its i-side block, repeats the last
    off-diagonal pair's j-side fetch (the previous example's for the first
    pair), so the pipeline sees an unchanged block index and copies
    nothing."""
    pairs = [(i, j) for i in range(nt) for j in range(i, nt)]
    last = max((j for i, j in pairs if i != j), default=0)
    rows, back = [], []
    prev, prev_back = last, 1
    for i, j in pairs:
        if i != j:
            prev, prev_back = j, 0
        rows.append(prev)
        back.append(prev_back)
    pi, pj = zip(*pairs)
    return np.array([pi, pj, rows, back], np.int32)


def _dot_nt(u, v):
    return jax.lax.dot_general(u, v, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(tab, *refs, t, bt, bs, nk, nka, nkg, ra, rg, two, dtype):
    if two:
        a_i, a_j, g_i, g_j, out_ref, acc_a, acc_g = refs
    else:
        (a_i, g_i, out_ref, acc_a, acc_g), a_j, g_j = refs, None, None
    p, k = pl.program_id(1), pl.program_id(2)
    i, j = tab[0, p], tab[1, p]
    nsub = bt // bs
    ragged_rows = t % bt != 0

    def sub(r):
        if isinstance(r, int):
            return slice(r * bs, (r + 1) * bs)
        return pl.ds(pl.multiple_of(r * bs, bs), bs)

    def loop(body, init):
        """body over the sub-tile pairs q = (r, c) of a block."""
        if nsub == 1:
            return body(0, init)
        return jax.lax.fori_loop(0, nsub * nsub, body, init)

    @pl.when((p == 0) & (k == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == 0)
    def _init_acc():
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_g[...] = jnp.zeros_like(acc_g)

    def load(ref, blk, r, cols):
        """Sub-tile r of a row block as `dtype`, zero past T and past
        `cols` valid columns (None: all are)."""
        x = ref[0, sub(r), :].astype(dtype)
        keep = None
        if cols is not None:
            keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < cols
        if ragged_rows:
            rk = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                  < t - blk * bt - r * bs)
            keep = rk if keep is None else keep & rk
        return x if keep is None else jnp.where(keep, x, jnp.zeros_like(x))

    def gram(x_i, x_j, acc, cols):
        """acc's sub-tiles (r, c) += X_i[r] X_j[c]ᵀ over this chunk: those
        with c >= r of a diagonal pair, all of an off-diagonal one."""
        def body(q, carry):
            r, c = q // nsub, q % nsub
            upper = c >= r if nsub > 1 else None
            if two:
                upper = i == j if upper is None else upper & (i == j)

                @pl.when(i != j)
                def _off_diagonal():
                    acc[sub(r), sub(c)] += _dot_nt(
                        load(x_i, i, r, cols), load(x_j, j, c, cols))

            def diagonal():
                acc[sub(r), sub(c)] += _dot_nt(
                    load(x_i, i, r, cols), load(x_i, i, c, cols))

            if upper is None:
                diagonal()
            else:
                pl.when(upper)(diagonal)
            return carry

        loop(body, 0)

    for x_i, x_j, acc, n, rem in ((a_i, a_j, acc_a, nka, ra),
                                  (g_i, g_j, acc_g, nkg, rg)):
        if rem:  # mask the ragged last chunk, and only that one
            pl.when(k < n - 1)(functools.partial(gram, x_i, x_j, acc, None))
            pl.when(k == n - 1)(functools.partial(gram, x_i, x_j, acc, rem))
        else:
            pl.when(k < n)(functools.partial(gram, x_i, x_j, acc, None))

    @pl.when(k == nk - 1)
    def _emit():
        def body(q, total):
            r, c = q // nsub, q % nsub
            w = 1.0 if nsub == 1 else jnp.where(
                c > r, 2.0, jnp.where(c == r, 1.0, 0.0))
            if two:  # off-diagonal pairs stand in for (j, i) as well
                w = jnp.where(i == j, w, 2.0)
            blk = acc_a[sub(r), sub(c)] * acc_g[sub(r), sub(c)]
            return total + w * jnp.sum(blk)

        out_ref[...] += loop(body, jnp.float32(0.0))


def ghost_norm(a: jax.Array, g: jax.Array, *, bt: int | None = None,
               dk: int | None = None, interpret: bool = False) -> jax.Array:
    """(B,) squared per-example grad norms. a: (B,T,din); g: (B,T,dout).

    A tile left `None` comes from `tiles`. interpret=True executes the
    kernel body on the host (validation mode off-TPU); the default
    compiles it through Mosaic.
    """
    b, t, din = a.shape
    dout = g.shape[-1]
    dtype = jnp.promote_types(a.dtype, g.dtype)
    tl = tiles(t, din, dout, dtype, bt=bt, dk=dk)
    nt = pl.cdiv(t, tl.bt)
    two = nt > 1
    nka, nkg = pl.cdiv(din, tl.dka), pl.cdiv(dout, tl.dkg)
    nk = max(nka, nkg)
    tab = _pairs(nt)

    def side(width, n, j_side):
        if not j_side:
            def index(bb, p, k, tab):
                return bb, tab[0, p], jnp.minimum(k, n - 1)
        else:
            def index(bb, p, k, tab):
                diag = tab[0, p] == tab[1, p]
                return (jnp.maximum(bb - tab[3, p], 0), tab[2, p],
                        jnp.where(diag, n - 1, jnp.minimum(k, n - 1)))
        return pl.BlockSpec((1, tl.bt, width), index)

    if two:
        in_specs = [side(tl.dka, nka, False), side(tl.dka, nka, True),
                    side(tl.dkg, nkg, False), side(tl.dkg, nkg, True)]
        operands = (a, a, g, g)
    else:
        in_specs = [side(tl.dka, nka, False), side(tl.dkg, nkg, False)]
        operands = (a, g)
    need = _vmem(tl, t, jnp.dtype(dtype).itemsize)
    out = pl.pallas_call(
        functools.partial(_kernel, t=t, bt=tl.bt, bs=tl.bs, nk=nk, nka=nka,
                          nkg=nkg, ra=din % tl.dka, rg=dout % tl.dkg,
                          two=two, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, tab.shape[1], nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1,) + OUT_TILE,
                                   lambda bb, p, k, tab: (bb, 0, 0)),
            scratch_shapes=[pltpu.VMEM((tl.bt, tl.bt), jnp.float32),
                            pltpu.VMEM((tl.bt, tl.bt), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b,) + OUT_TILE, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, need * 5 // 4)),
        interpret=interpret,
        name="ghost_norm",
    )(jnp.asarray(tab), *operands)
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
                       dk: int | None = None, interpret: bool = False
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

    dk = DEFAULT_DK if dk is None else dk
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

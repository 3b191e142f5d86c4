"""Pallas paged-gather decode attention.

One-token GQA/MQA attention that reads K/V THROUGH a per-row page table
instead of a contiguous (B, S, ...) cache. The page table is a scalar-
prefetch operand (`pltpu.PrefetchScalarGridSpec`), so the physical page id
feeds the K/V BlockSpec index_map directly: grid step (b, p) DMAs physical
page `pt[b, p]` into VMEM — the gather happens in the pipeline's address
generation and the (B, S) gathered cache is never materialized in HBM.

Softmax is the standard online accumulation over page steps (running max
/ denominator / weighted-value scratch in VMEM, emitted at the last page),
identical in structure to a flash decode kernel with `page_len`-sized KV
blocks. Validity masking reuses the engine's kpos algebra: logical index
`p * page_len + i` attends iff `<= pos[b]` — partially filled last pages
and trash-mapped (unallocated) table entries mask out for free.

Shapes (decode only, T == 1):
  q:     (B, KV, G, dq)   post-RoPE, UNscaled query, G = heads per KV head
  kpool: (N, L, KV, dq)   physical page pool (N pages of L tokens)
  vpool: (N, L, KV, dvp)  value pool; may alias kpool (MLA latents) with
                          the value read truncated to `dv` (dv <= dvp)
  pt:    (B, P) int32     page table (any id in [0, N); invalid entries
                          must still be IN RANGE — point them at a trash
                          page, the pos mask discards their scores)
  pos:   (B,)   int32     index of the newest written token (all logical
                          indices <= pos are valid)
  out:   (B, KV, G, dv) float32

The pure-jnp oracle is `repro.kernels.ref.paged_attn_ref` (which is also
the production XLA backend path — see kernels/backend.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, page_len: int, dv: int,
                       scale: float, num_pt_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (KV, G, dq)
    k = k_ref[0].astype(jnp.float32)                  # (L, KV, dq)
    v = v_ref[0, :, :, :dv].astype(jnp.float32)       # (L, KV, dv)

    kt = jnp.transpose(k, (1, 0, 2))                  # (KV, L, dq)
    s = jax.lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))))  # (KV,G,L)

    # kpos validity: logical index of row i on this page is p*L + i
    idx = p * page_len + jax.lax.broadcasted_iota(jnp.int32, (1, page_len), 1)
    valid = (idx <= pos_ref[b])[0]                    # (L,)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[..., None])
    l_ref[...] = l_prev * alpha + jnp.sum(pexp, axis=-1)
    m_ref[...] = m_new
    vt = jnp.transpose(v, (1, 0, 2))                  # (KV, L, dv)
    pv = jax.lax.dot_general(pexp, vt, (((2,), (1,)), ((0,), (0,))))
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv

    @pl.when(p == num_pt_pages - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]


def paged_attn(q, kpool, vpool, pt, pos, *, scale: float,
               dv: int | None = None, interpret: bool = False):
    """Paged-gather decode attention (see module doc). Returns
    (B, KV, G, dv) float32."""
    b, kv, g, dq = q.shape
    n_pages, page_len = kpool.shape[0], kpool.shape[1]
    dvp = vpool.shape[-1]
    dv = dvp if dv is None else dv
    p_tab = pt.shape[1]

    kernel = functools.partial(
        _paged_attn_kernel, page_len=page_len, dv=dv, scale=float(scale),
        num_pt_pages=p_tab)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # pt, pos
        grid=(b, p_tab),
        in_specs=[
            pl.BlockSpec((1, kv, g, dq),
                         lambda bb, pp, pt_s, pos_s: (bb, 0, 0, 0)),
            pl.BlockSpec((1, page_len, kv, dq),
                         lambda bb, pp, pt_s, pos_s: (pt_s[bb, pp], 0, 0, 0)),
            pl.BlockSpec((1, page_len, kv, dvp),
                         lambda bb, pp, pt_s, pos_s: (pt_s[bb, pp], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kv, g, dv),
                               lambda bb, pp, pt_s, pos_s: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),       # running max
            pltpu.VMEM((kv, g), jnp.float32),       # running denominator
            pltpu.VMEM((kv, g, dv), jnp.float32),   # weighted-value acc
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), jnp.float32),
        interpret=interpret,
        name="paged_attn",
    )(pt.astype(jnp.int32), pos.astype(jnp.int32), q, kpool, vpool)

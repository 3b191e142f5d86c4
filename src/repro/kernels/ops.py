"""jit'd dispatch wrappers around the Pallas kernels.

Kernel routing is owned by the backend engine (`repro.kernels.backend`):
select it per training run with `DPConfig(backend="pallas" | "auto")` or
scope it manually with `backend.scoped(...)` — there is no module-global
switch. The wrappers here are thin jitted entry points for callers that
want to hit one kernel directly.

On TPU the kernels compile through Mosaic; on CPU (this container) they run
in interpret mode for correctness validation and the XLA reference paths
stay the production default. Dry-run lowering always uses the XLA paths (a
TPU custom-call cannot lower on the CPU backend). See the backend module
docstring for the full op x backend selection matrix.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.clip_reduce import clip_reduce, scale_contract
from repro.kernels.fused_clip import fused_norm_clip
from repro.kernels.ghost_norm import ghost_norm, ghost_norm_blocked
from repro.kernels.paged_attn import paged_attn


def _interpret() -> bool:
    """Interpret off-TPU. Asked at trace time, not import time: importing
    this module must not initialize a JAX backend (that would claim the
    chip in a parent process that only spawns workers)."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("bt", "dk"))
def ghost_norm_op(a, g, *, bt: int | None = None, dk: int | None = None):
    return ghost_norm(a, g, bt=bt, dk=dk, interpret=_interpret())


@partial(jax.jit, static_argnames=("num_blocks", "block_axis", "bt", "dk"))
def ghost_norm_blocked_op(a, g, num_blocks: int, *, block_axis: str = "out",
                          bt: int = 256, dk: int = 512):
    return ghost_norm_blocked(a, g, num_blocks, block_axis=block_axis,
                              bt=bt, dk=dk, interpret=_interpret())


@partial(jax.jit, static_argnames=("bi", "bj", "bt"))
def clip_reduce_op(a, g, factors, *, bi: int | None = None,
                   bj: int | None = None, bt: int | None = None):
    return clip_reduce(a, g, factors, bi=bi, bj=bj, bt=bt,
                       interpret=_interpret())


@partial(jax.jit, static_argnames=("bt",))
def fused_norm_clip_op(a, g, c, extra_norms_sq=None, *, bt: int = 256):
    return fused_norm_clip(a, g, c, extra_norms_sq, bt=bt,
                           interpret=_interpret())


@partial(jax.jit, static_argnames=("bi", "bj", "bt"))
def scale_contract_op(a, g, factors, *, bi: int | None = None,
                      bj: int | None = None, bt: int | None = None):
    return scale_contract(a, g, factors, bi=bi, bj=bj, bt=bt,
                          interpret=_interpret())


@partial(jax.jit, static_argnames=("scale", "dv"))
def paged_attn_op(q, kpool, vpool, pt, pos, *, scale: float,
                  dv: int | None = None):
    return paged_attn(q, kpool, vpool, pt, pos, scale=scale, dv=dv,
                      interpret=_interpret())

"""Ghost-op backend engine: pluggable dispatch for the fused-clipping ops.

Every `custom_vjp` backward rule in `repro.core.dp_layers` (and the LoRA
primitive in `repro.core.lora`) resolves its ghost ops through the engine
returned by `active()` instead of calling `repro.core.ghost` directly. Three
backends are registered:

  xla     the pure-jnp reference paths of `repro.core.ghost` (gram /
          gram_chunked / outer auto-dispatch). Always available; the
          semantics oracle for the others.
  pallas  real `pallas_call` kernels for the linear-layer hot paths
          (kernels/ghost_norm.py; kernels/clip_reduce.py, which also
          serves BK's scale_contract; kernels/fused_clip.py). On TPU they
          compile to Mosaic; on CPU they run in interpret mode
          (correctness validation — slow, tests only). Ops with no
          kernel fall back to the xla implementations.
  auto    per-op empirical choice between the two: when an autotune table
          (repro.kernels.autotune) is installed and has measured this
          (op, shape-bucket), the measured argmin wins — on ANY jax
          backend. Unmeasured buckets fall back to the static cost model
          (`gram_path_cost` / `outer_path_cost` plus a VMEM-footprint
          guard), where the non-TPU short-circuit to xla still applies
          (interpret-mode kernels are validation-only *until measured
          faster*).

Backend selection matrix (op x backend), CPU behavior in parens:

  op                        xla            pallas (CPU)          auto on TPU
  ------------------------- -------------- --------------------- -----------------
  linear_norms_sq           gram/outer     ghost_norm (interp)   cost model + VMEM
  linear_norms_sq_blocked   einsum         ghost_norm_blocked    cost model + VMEM
  clipped_sum_linear        einsum         clip_reduce (interp)  pallas if big T
  clipped_sum_linear_blk    einsum         scale + clip_reduce   like unblocked
  linear_clip (norm+clip)   composed       fused_norm_clip*      fused if VMEM fits
  bias/embed/scale/vector   einsum/scatter = xla (no kernel)     = xla
  clipped_sum_bias/embed/.. einsum/scatter = xla (no kernel)     = xla
  paged_attn (decode)       gather+attend  paged_attn (interp)   pallas on TPU

  (*) falls back to the two-kernel composition when 2·din·dout f32 exceeds
      `vmem_limit_bytes`, or when `prefer_fused=False`. The fused kernel
      emits norms AND the clipped sum from one pallas_call, which a
      norms-only pass could not dead-code-eliminate — so the two-pass
      drivers (ghost_flat/per_group pass 1, core/clipping.py) scope
      `prefer_fused=False` around their norms-only backward.

How `auto` chooses for a linear (B, T, din, dout):
  0. `config.autotune` and the installed autotune table has a measurement
     for this (op, shape bucket) -> the measured argmin backend;
  1. outer path allowed (din·dout <= outer_max_elems) and cheaper by flops
     -> xla outer path (one einsum, no kernel beats it);
  2. else gram regime: T >= bt and the kernel's working set
     (4·bt·dk + 2·bt²) f32 fits vmem_limit_bytes -> pallas gram kernel
     (the (B,T,T) gram never touches HBM);
  3. else -> xla gram/gram_chunked.

Engine config is SCOPED, not global: `with backend.scoped("pallas"): ...`
pushes an engine for the dynamic extent of the block, so jitted step
functions capture their backend statically at trace time (this replaces the
old `ghost.configure()` module-global mutation). Unspecified fields inherit
from the enclosing scope, so e.g. the dry-run can widen `outer_max_elems`
and a `make_dp_train_step(cfg)` inside still honors it.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import ghost
from repro.core.ghost import clip_factor
from repro.kernels import autotune
from repro.kernels.clip_reduce import clip_reduce
from repro.kernels.clip_reduce import scale_contract as scale_contract_kernel
from repro.kernels.fused_clip import fused_norm_clip
from repro.kernels.fused_clip import padded_dims as fused_clip_padded_dims
from repro.kernels.ghost_norm import ghost_norm, ghost_norm_blocked
from repro.kernels.paged_attn import paged_attn as paged_attn_kernel
from repro.kernels.ref import paged_attn_ref

__all__ = [
    "EngineConfig", "Backend", "XlaBackend", "PallasBackend", "AutoBackend",
    "register_backend", "backends", "make_engine", "active", "scoped",
    "clip_factor", "choose_linear_path", "choose_op", "recording_choices",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (trace-time) engine configuration.

    Jitted programs capture the active config at trace time via
    `backend.scoped(...)`; nothing here is a runtime value. Knobs:

    * `backend` (default `"xla"`; `--backend` on the CLIs, `auto` from
      `DPConfig`): `xla` einsum/gram reference paths, `pallas` TPU
      kernels (interpret-mode off TPU — correctness only), `auto`
      measured-table argmin then static cost model.
    * `outer_max_elems` / `gram_chunk`: xla path policy — max din·dout
      elements for the outer-product norms path, and the gram-matrix
      chunk size (elements along B·T). `None` inherits the
      `repro.core.ghost` module defaults.
    * `bt`, `dk`: pallas tile sizes of `ghost_norm` and the fused kernel
      (rows of the sequence and feature chunk; units = array elements).
      Defaults suit ~16 MB VMEM cores; the autotune sweep measures
      alternatives.
    * `bi`, `bj`: the din and dout tiles of `clip_reduce` and
      `scale_contract`, which share one kernel. `None` (the default)
      means derived: `clip_reduce.tiles` sizes both from the shape and
      dtype. An explicit value overrides both. Their row tile is always
      derived, since it has to divide the (padded) sequence.
    * `interpret` (default `None` = interpret off-TPU, compiled on
      TPU): force pallas interpret mode either way.
    * `vmem_limit_bytes` (default 12 MiB): kernel-selection guard —
      `auto` rejects a pallas candidate whose working set exceeds it.
    * `prefer_fused` (default True): allow the single-pallas_call fused
      norm+clip kernel; scoped off by the two-pass drivers so the
      norms-only pass can dead-code-eliminate the unused contraction.
    * `autotune` (default True; `--autotune off` to disable): let
      measured (op, shape-bucket) entries from the installed table
      override the static model, on any jax backend.
    * `capture_residuals` (default False): BK capture pass marker —
      scoped on by `bk.capture_clipped` ONLY; primitives refuse
      BkChannels outside it (a capture pass returns zero param
      cotangents and must never be mistaken for a gradient pass).
      Interacts with `--execution bk`: the norm backprop runs under
      this scope, the epilogue (`scale_contract`) outside it.
    """

    backend: str = "xla"
    # xla path policy; None -> fall through to the repro.core.ghost module
    # globals, so legacy ghost.configure() callers stay honored
    outer_max_elems: int | None = None
    gram_chunk: int | None = None
    # pallas tile sizes
    bt: int = 256   # sequence tile (ghost_norm / fused)
    dk: int = 512   # feature-chunk tile (ghost_norm)
    bi: int | None = None   # clip_reduce / scale_contract din tile
    bj: int | None = None   # clip_reduce / scale_contract dout tile
    # None -> interpret off TPU, compiled on TPU; bools force it
    interpret: bool | None = None
    # VMEM-footprint guard for kernel selection (bytes)
    vmem_limit_bytes: int = 12 << 20
    # False -> linear_clip composes norm + reduce ops instead of the fused
    # kernel. Two-pass drivers (ghost_flat/per_group pass 1) scope this off:
    # they only consume norms², and XLA can dead-code-eliminate the unused
    # dW einsum of the composed path but never half of one pallas_call.
    prefer_fused: bool = True
    # True -> the auto backend consults the installed autotune table
    # (repro.kernels.autotune.installed_table()) before the static cost
    # model; measured (op, shape-bucket) argmins then win on any jax
    # backend. False pins auto to the static model regardless of tables.
    autotune: bool = True
    # True -> the dp_* custom VJPs are in a book-keeping capture pass
    # (repro.core.bk): when a BkChannel threshold reaches a primitive, its
    # backward rule emits per-example norms² AND stashes the (a, g) ghost
    # residuals through the channel's sink cotangent instead of contracting
    # weight grads. Scoped on by bk.capture_clipped only; primitives refuse
    # BkChannels outside this scope (a capture pass returns ZERO param
    # cotangents, so it must never be mistaken for a gradient pass).
    capture_residuals: bool = False


_REGISTRY: dict[str, type["Backend"]] = {}


def register_backend(name: str):
    """Class decorator: register a Backend under `name`."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


class Backend:
    """The full ghost-op surface. Base implementations are the xla
    reference paths; subclasses override the ops they accelerate."""

    name = "base"

    def __init__(self, config: EngineConfig):
        self.config = config

    def _interpret(self) -> bool:
        if self.config.interpret is not None:
            return self.config.interpret
        return jax.default_backend() != "tpu"

    # -- norms² ------------------------------------------------------------
    def linear_norms_sq(self, a, g):
        return ghost.linear_norms_sq(
            a, g, outer_max_elems=self.config.outer_max_elems,
            gram_chunk=self.config.gram_chunk)

    def linear_norms_sq_blocked(self, a, g, num_blocks, *, block_axis="out"):
        return ghost.linear_norms_sq_blocked(a, g, num_blocks,
                                             block_axis=block_axis)

    def bias_norms_sq(self, g):
        return ghost.bias_norms_sq(g)

    def embed_norms_sq(self, ids, g):
        return ghost.embed_norms_sq(ids, g,
                                    gram_chunk=self.config.gram_chunk)

    def scale_norms_sq(self, xhat, g):
        return ghost.scale_norms_sq(xhat, g)

    def vector_norms_sq(self, per_example_grad):
        return ghost.vector_norms_sq(per_example_grad)

    # -- fused clipped sums ------------------------------------------------
    def clipped_sum_linear(self, a, g, factors):
        return ghost.clipped_sum_linear(a, g, factors)

    def clipped_sum_linear_blocked(self, a, g, factors, *, block_axis="out"):
        return ghost.clipped_sum_linear_blocked(a, g, factors,
                                                block_axis=block_axis)

    def clipped_sum_bias(self, g, factors):
        return ghost.clipped_sum_bias(g, factors)

    def clipped_sum_embed(self, ids, g, factors, vocab):
        return ghost.clipped_sum_embed(ids, g, factors, vocab)

    def clipped_sum_scale(self, xhat, g, factors):
        return ghost.clipped_sum_scale(xhat, g, factors)

    # -- BK epilogue: scaled contraction over cached residuals -------------
    def scale_contract(self, a, g, factors):
        """Σ_i f[s,i] A[s,i]ᵀ G[s,i] per stack slice (repro.core.bk).

        a: (S, B, T, din); g: (S, B, T, dout); factors: (S, B) ->
        (S, din, dout) f32. Accepts the unstacked 3-D form too.
        """
        if a.ndim == 3:
            return ghost.clipped_sum_linear(a, g, factors)
        a32 = a.astype(jnp.float32)
        gs = (g.astype(jnp.float32)
              * factors[:, :, None, None].astype(jnp.float32))
        return jnp.einsum("sbti,sbto->sio", a32, gs)

    # -- paged decode attention (launch.engine data plane) -----------------
    def paged_impl(self, *, t=None, din=None, dout=None) -> str:
        """Which implementation `paged_attn` resolves to: 'xla'|'pallas'.

        The serve paths branch on this statically at trace time: the xla
        gather path is the bitwise oracle (its math replicates the
        contiguous decode exactly), the pallas kernel is the TPU
        paged-gather path (allclose-level, different softmax association).
        The auto backend takes optional shape hints so its decision can
        come from the autotune table; fixed backends ignore them.
        """
        return "xla"

    def paged_attn(self, q, kpool, vpool, pt, pos, *, scale, dv=None):
        """One-token attention through a page table (kernels/paged_attn.py
        shapes). Base = the gather + attend-replica reference."""
        return paged_attn_ref(q, kpool, vpool, pt, pos, scale=scale, dv=dv)

    # -- fused norm + clip + reduce ---------------------------------------
    def linear_clip(self, a, g, c, extra_norms_sq=None):
        """One linear layer's whole backward clip:  (n_total, f, dW).

        n_total includes `extra_norms_sq` (co-grouped params, e.g. bias);
        f = clip_factor(c, n_total); dW = sum_i f_i A_iᵀ G_i. Backends may
        fuse all three into one kernel.
        """
        n = self.linear_norms_sq(a, g)
        if extra_norms_sq is not None:
            n = n + extra_norms_sq
        f = clip_factor(c, n)
        return n, f, self.clipped_sum_linear(a, g, f)


@register_backend("xla")
class XlaBackend(Backend):
    """Pure-jnp reference paths (repro.core.ghost) — the semantics oracle."""


@register_backend("pallas")
class PallasBackend(Backend):
    """pallas_call kernels for the linear hot paths; xla fallbacks for the
    cheap ops (bias/embed/scale/vector) that have no kernel."""

    def _fused_fits(self, din: int, dout: int) -> bool:
        dip, djp = fused_clip_padded_dims(din, dout)
        bt = self.config.bt
        need = 4 * (2 * dip * djp + 2 * bt * (dip + djp))
        return need <= self.config.vmem_limit_bytes

    def linear_norms_sq(self, a, g):
        a3, g3 = ghost._as3d(a), ghost._as3d(g)
        return ghost_norm(a3, g3, bt=self.config.bt, dk=self.config.dk,
                          interpret=self._interpret())

    def linear_norms_sq_blocked(self, a, g, num_blocks, *, block_axis="out"):
        a3, g3 = ghost._as3d(a), ghost._as3d(g)
        return ghost_norm_blocked(a3, g3, num_blocks, block_axis=block_axis,
                                  bt=self.config.bt, dk=self.config.dk,
                                  interpret=self._interpret())

    def _feature_tiles(self) -> dict:
        """The explicit din/dout tiles; absent ones are each kernel's own."""
        return {k: v for k, v in (("bi", self.config.bi),
                                  ("bj", self.config.bj)) if v is not None}

    def clipped_sum_linear(self, a, g, factors):
        a3, g3 = ghost._as3d(a), ghost._as3d(g)
        return clip_reduce(a3, g3, factors, **self._feature_tiles(),
                           interpret=self._interpret())

    def clipped_sum_linear_blocked(self, a, g, factors, *, block_axis="out"):
        # fold the per-block factors into the blocked operand (shared helper
        # with the jnp path), then run the big contraction through the
        # kernel with unit row factors
        a3, g3 = ghost.fold_block_factors(ghost._as3d(a), ghost._as3d(g),
                                          factors, block_axis)
        ones = jnp.ones((a3.shape[0],), jnp.float32)
        return clip_reduce(a3, g3, ones, **self._feature_tiles(),
                           interpret=self._interpret())

    def linear_clip(self, a, g, c, extra_norms_sq=None):
        a3, g3 = ghost._as3d(a), ghost._as3d(g)
        din, dout = a3.shape[-1], g3.shape[-1]
        if not self.config.prefer_fused or not self._fused_fits(din, dout):
            return super().linear_clip(a3, g3, c, extra_norms_sq)
        n_w, dw = fused_norm_clip(a3, g3, c, extra_norms_sq,
                                  bt=self.config.bt,
                                  interpret=self._interpret())
        n = n_w if extra_norms_sq is None else n_w + extra_norms_sq
        return n, clip_factor(c, n), dw

    def scale_contract(self, a, g, factors):
        return scale_contract_kernel(a, g, factors, **self._feature_tiles(),
                                     interpret=self._interpret())

    def paged_impl(self, *, t=None, din=None, dout=None) -> str:
        return "pallas"

    def paged_attn(self, q, kpool, vpool, pt, pos, *, scale, dv=None):
        return paged_attn_kernel(q, kpool, vpool, pt, pos, scale=scale,
                                 dv=dv, interpret=self._interpret())


def choose_linear_path(t: int, din: int, dout: int, config: EngineConfig,
                       *, on_tpu: bool | None = None) -> str:
    """The STATIC cost model's decision for one linear ghost op:
    'xla'|'pallas'. This is the fallback for shape buckets the autotune
    table has never measured (`choose_op` is the full decision); pure
    function of static shapes + config, exposed for tests and for the
    benchmark sweep to report what the model alone would pick.
    """
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and config.interpret is not True:
        # unmeasured + off-TPU: interpret-mode kernels are validation-only
        # (a MEASURED interpret-mode win is honored by choose_op above)
        return "xla"
    outer_cap = (ghost._OUTER_MAX_ELEMS if config.outer_max_elems is None
                 else config.outer_max_elems)
    outer_ok = din * dout <= outer_cap
    if outer_ok and (ghost.outer_path_cost(t, din, dout)
                     < ghost.gram_path_cost(t, din, dout)):
        return "xla"  # one einsum, transient fits: nothing to fuse
    if t < config.bt:
        return "xla"  # sub-tile sequence: kernel grid degenerates
    working_set = 4 * (4 * config.bt * config.dk + 2 * config.bt * config.bt)
    if working_set > config.vmem_limit_bytes:
        return "xla"
    return "pallas"


def choose_op(op: str, t: int, din: int, dout: int, config: EngineConfig,
              *, on_tpu: bool | None = None,
              table: "autotune.AutotuneTable | None" = None) -> str:
    """The auto backend's FULL decision for one engine op: measured argmin
    from the autotune table when this (op, shape bucket) has measurements
    — honored on any jax backend — else the static model.

    op is one of `autotune.OPS`; `table=None` consults the installed table
    (`autotune.installed_table()`), which entry points install under their
    --autotune knob and tests scope with `autotune.use_table`. Every
    decision lands in the enclosing `recording_choices()` log, if any.
    """
    choice = _decide(op, t, din, dout, config, on_tpu=on_tpu, table=table)
    log = _CHOICES.get()
    if log is not None:
        log[(op, int(t), int(din), int(dout))] = choice
    return choice


def _decide(op, t, din, dout, config, *, on_tpu, table) -> str:
    if config.autotune:
        tab = table if table is not None else autotune.installed_table()
        if tab is not None:
            measured = tab.best(op, t, din, dout)
            if measured is not None:
                return measured
    if op == "paged_attn":
        # static fallback: the paged-gather DMA only pays off on TPU;
        # off-TPU the xla gather path is the bitwise oracle
        if on_tpu is None:
            on_tpu = jax.default_backend() == "tpu"
        return "pallas" if (on_tpu or config.interpret is True) else "xla"
    return choose_linear_path(t, din, dout, config, on_tpu=on_tpu)


_CHOICES: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "ghost_op_choices", default=None)


@contextlib.contextmanager
def recording_choices():
    """Log the `auto` decisions traced inside the block.

    Yields a dict filled at TRACE time with {(op, t, din, dout): 'xla' |
    'pallas'}; a program traced in the block (a persistent-cache hit still
    traces) reports which implementation each ghost op resolved to.
    """
    log: dict = {}
    token = _CHOICES.set(log)
    try:
        yield log
    finally:
        _CHOICES.reset(token)


@register_backend("auto")
class AutoBackend(Backend):
    """Cost-model dispatch between the xla and pallas backends per op."""

    def __init__(self, config: EngineConfig):
        super().__init__(config)
        self._xla = XlaBackend(config)
        self._pallas = PallasBackend(config)

    def _pick(self, op: str, a, g) -> Backend:
        a3, g3 = ghost._as3d(a), ghost._as3d(g)
        t, din, dout = a3.shape[1], a3.shape[-1], g3.shape[-1]
        choice = choose_op(op, t, din, dout, self.config)
        return self._pallas if choice == "pallas" else self._xla

    # blocked variants run the same underlying kernels as their unblocked
    # ops, so they share the "norms"/"clip_sum" table buckets
    def linear_norms_sq(self, a, g):
        return self._pick("norms", a, g).linear_norms_sq(a, g)

    def linear_norms_sq_blocked(self, a, g, num_blocks, *, block_axis="out"):
        return self._pick("norms", a, g).linear_norms_sq_blocked(
            a, g, num_blocks, block_axis=block_axis)

    def clipped_sum_linear(self, a, g, factors):
        return self._pick("clip_sum", a, g).clipped_sum_linear(a, g, factors)

    def clipped_sum_linear_blocked(self, a, g, factors, *, block_axis="out"):
        return self._pick("clip_sum", a, g).clipped_sum_linear_blocked(
            a, g, factors, block_axis=block_axis)

    def linear_clip(self, a, g, c, extra_norms_sq=None):
        return self._pick("linear_clip", a, g).linear_clip(
            a, g, c, extra_norms_sq)

    def scale_contract(self, a, g, factors):
        if a.ndim == 3:
            return self._pick("scale_contract", a, g).scale_contract(
                a, g, factors)
        t, din, dout = a.shape[2], a.shape[-1], g.shape[-1]
        choice = choose_op("scale_contract", t, din, dout, self.config)
        eng = self._pallas if choice == "pallas" else self._xla
        return eng.scale_contract(a, g, factors)

    def paged_impl(self, *, t=None, din=None, dout=None) -> str:
        """With shape hints (logical context, query dim, value dim) this
        consults the autotune table like every other op; without hints —
        or unmeasured — the static rule applies: pallas only where the
        paged-gather DMA pays off (TPU), xla's bitwise-oracle gather path
        elsewhere (unless interpret is forced)."""
        if t is not None:
            return choose_op("paged_attn", t, din or 0, dout or 0,
                             self.config)
        if jax.default_backend() == "tpu" or self.config.interpret is True:
            return "pallas"
        return "xla"

    def paged_attn(self, q, kpool, vpool, pt, pos, *, scale, dv=None):
        t, din, dout = autotune.paged_attn_dims(
            q, pt, kpool.shape[1], dv if dv is not None else vpool.shape[-1])
        impl = self.paged_impl(t=t, din=din, dout=dout)
        eng = self._pallas if impl == "pallas" else self._xla
        return eng.paged_attn(q, kpool, vpool, pt, pos, scale=scale, dv=dv)


# ---------------------------------------------------------------------------
# Scoped engine resolution.
# ---------------------------------------------------------------------------

_DEFAULT: Backend | None = None
# context-local, not a process-global list: concurrent tracers (threads /
# async tasks) each see their own scope stack and cannot cross-contaminate
_STACK: contextvars.ContextVar[tuple[Backend, ...]] = contextvars.ContextVar(
    "ghost_backend_stack", default=())


def make_engine(backend: str | None = None, **overrides) -> Backend:
    """Build an engine; unspecified fields inherit from the active scope."""
    base = active().config
    cfg = dataclasses.replace(
        base, backend=base.backend if backend is None else backend,
        **overrides)
    try:
        cls = _REGISTRY[cfg.backend]
    except KeyError:
        raise ValueError(
            f"unknown ghost backend {cfg.backend!r}; "
            f"registered: {backends()}") from None
    return cls(cfg)


def active() -> Backend:
    """The engine in effect (innermost `scoped`, else the xla default)."""
    stack = _STACK.get()
    if stack:
        return stack[-1]
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = XlaBackend(EngineConfig())
    return _DEFAULT


@contextlib.contextmanager
def scoped(backend: str | None = None, **overrides):
    """Push an engine for the dynamic extent of the block.

    Trace jitted functions inside the block and they capture the engine
    statically; nesting composes (inner scopes inherit unspecified fields).
    """
    eng = make_engine(backend, **overrides)
    token = _STACK.set(_STACK.get() + (eng,))
    try:
        yield eng
    finally:
        _STACK.reset(token)

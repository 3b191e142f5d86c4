"""Plain reference for the MiniCPM family, and the weights the benchmark
feeds both it and the program.

Written from the published description (MiniCPM, arXiv:2404.06395, and
the `modeling_minicpm.py` of openbmb/MiniCPM-2B-sft-bf16): the dense GQA
decoder of `dense.py` (pre-norm RMSNorm blocks, RoPE, SwiGLU) with

  * x0 = E[ids] * scale_emb;
  * each residual branch times scale_depth / sqrt(published depth), so a
    depth cut keeps the published multiplier;
  * logits = (rms(x) / (hidden_size / dim_model_base)) @ Eᵀ: one matrix E
    is the input embedding and the LM head.

It imports `dense.py`'s helpers and nothing of the program. Arithmetic is
float32 at `highest` matmul precision; `precision="fp8"` is the control.

The DP step is flat clipping at a fixed threshold C, `global` noise (one
group: std sigma C) and Adam. The tied matrix's per-example gradient is
what `jax.grad` gives for the loss with E in both places; it is taken here
as the gradients of the two uses, E as the embedding and E as the head, at
the same value, summed: the norm of the sum carries the cross term of the
two uses without any formula for it. Their inner product is the cross term
the driver compares.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense as D

flatten, nest, as_stored = D.flatten, D.nest, D.as_stored
EMBED = "embed/w"


@dataclasses.dataclass(frozen=True)
class Dims(D.Dims):
    scale_emb: float = 1.0
    depth_mult: float = 1.0  # scale_depth / sqrt(published depth)
    logit_div: float = 1.0  # hidden_size / dim_model_base

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        base = dataclasses.asdict(D.Dims.of(cfg))
        return cls(**base, scale_emb=float(cfg["scale_emb"]),
                   depth_mult=float(cfg["scale_depth"]) / math.sqrt(
                       cfg["published_num_hidden_layers"]),
                   logit_div=cfg["hidden_size"] / cfg["dim_model_base"])


def param_shapes(m: Dims) -> dict:
    """Leaf path -> shape, in the program's checkpoint layout: the dense
    layout with no head of its own."""
    out = D.param_shapes(m)
    del out["head/w"]
    return out


def init_params(m: Dims, key, dtype=jnp.bfloat16) -> dict:
    """The benchmark's weights from one key, as `dense.init_params` makes
    them: norm scales 1, embedding N(0, 0.02²), every matrix
    N(0, 1/fan_in). Call it under one jit."""
    out = {}
    for i, (path, shape) in enumerate(param_shapes(m).items()):
        k = jax.random.fold_in(key, i)
        if path.endswith("/s"):
            out[path] = jnp.ones(shape, dtype)
        elif path == EMBED:
            out[path] = (0.02 * jax.random.normal(k, shape)).astype(dtype)
        else:
            std = 1.0 / math.sqrt(shape[-2])
            out[path] = (std * jax.random.normal(k, shape)).astype(dtype)
    return nest(out)


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------


def block(m: Dims, p, x, positions, precision):
    """One pre-norm decoder layer, each branch times the depth multiplier."""
    h = D.rmsnorm(x, p["attn_norm"]["s"], m.eps)
    x = x + m.depth_mult * D.attention(m, p["attn"], h, positions, precision)
    h = D.rmsnorm(x, p["mlp_norm"]["s"], m.eps)
    gu = D.matmul(h, p["mlp"]["gate_up"]["w"], precision)
    act = jax.nn.silu(gu[:, : m.f]) * gu[:, m.f:]
    return x + m.depth_mult * D.matmul(act, p["mlp"]["down"]["w"], precision)


def logits(m: Dims, params, tokens, precision="f32", head=None):
    """Logits of one sequence (T, V). `head` is the matrix the head reads,
    the embedding itself unless a caller separates the two uses."""
    table = params["embed"]["w"]
    head = table if head is None else head
    positions = jnp.arange(tokens.shape[0])
    x = table[tokens].astype(jnp.float32) * m.scale_emb

    # a gradient keeps each layer's input only and recomputes the rest; one
    # scanned layer keeps the compile short at any depth
    def layer(xx, p):
        return block(m, p, xx, positions, precision), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["dense_blocks"])
    h = D.rmsnorm(x, params["final_norm"]["s"], m.eps) / m.logit_div
    return D.matmul(h, head.astype(jnp.float32).T, precision)


def example_loss(m: Dims, params, tokens, targets, precision="f32",
                 head=None):
    """Mean next-token cross-entropy over the targets that are >= 0."""
    lg = logits(m, params, tokens, precision, head)
    valid = targets >= 0
    tok = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[:, None], 1)[:, 0]
    ce = (jax.nn.logsumexp(lg, axis=-1) - tok) * valid
    return jnp.sum(ce) / jnp.maximum(jnp.sum(valid), 1)


# ---------------------------------------------------------------------------
# The DP step.
# ---------------------------------------------------------------------------

# Leaves whose gradients one backward pass takes together in the clipped
# sums: at most one chunk's float32 gradient and sums are held at a time.
CHUNKS = (("dense_blocks",), ("embed", "final_norm"))
FAULTS = ("no_cross", "no_head", "half_batch", "half_sum", "norm_sq")


@dataclasses.dataclass(frozen=True)
class Job:
    """The private training job of a flat-clipping cell (its traffic)."""

    batch: int
    sigma: float
    threshold: float
    lr: float
    b1: float = 0.9
    b2: float = 0.999
    adam_eps: float = 1e-8

    @classmethod
    def of(cls, traffic: dict) -> "Job":
        dp, opt = traffic["dp"], traffic["optimizer"]
        if dp["clipping"] != "ghost_flat" or dp["adaptive"]:
            raise ValueError("the reference clips flat at a fixed threshold, "
                             f"not {dp['clipping']!r} (adaptive "
                             f"{dp['adaptive']})")
        return cls(batch=traffic["batch"], sigma=dp["sigma"],
                   threshold=dp["init_threshold"], lr=opt["lr"],
                   b1=opt["b1"], b2=opt["b2"], adam_eps=opt["eps"])


_JITTED: dict = {}


def _jit(key, make):
    """One compiled function per key, shared by every DPReference of a
    process: the calibration's variants and seeds differ in host-side
    faults only, and each chunk's gradient takes most of a minute to
    compile for the chip."""
    if key not in _JITTED:
        _JITTED[key] = make()
    return _JITTED[key]


def _chunk_grad(m: Dims, precision, chunk, params, tokens, targets):
    """One example: its loss, the gradient of one chunk's leaves, their
    squared norms per group, and with the tied matrix in the chunk (‖G_e‖²,
    ‖G_h‖², ‖G_e + G_h‖², <G_e, G_h>). Its two uses are differentiated
    apart at the same value; the tied gradient is their sum."""
    sub = {p: v.astype(jnp.float32) for p, v in params.items()
           if p.split("/")[0] in chunk}
    rest = {p: v for p, v in params.items() if p not in sub}
    head = sub.get(EMBED)

    def loss(s, h):
        return example_loss(m, nest({**rest, **s}), tokens, targets,
                            precision, h)

    val, (g, g_head) = jax.value_and_grad(loss, argnums=(0, 1))(sub, head)
    tied = jnp.zeros(4)
    if head is not None:
        g_emb = g[EMBED]
        g[EMBED] = g_emb + g_head
        tied = jnp.stack([jnp.sum(jnp.square(g_emb)),
                          jnp.sum(jnp.square(g_head)),
                          jnp.sum(jnp.square(g[EMBED])),
                          jnp.sum(g_emb * g_head)])
    rows = {p: D._row_norms(m, p, v) for p, v in g.items() if p != EMBED}
    return val, g, rows, tied


_acc = jax.jit(lambda acc, g, f: {p: acc[p] + g[p] * f for p in acc},
               donate_argnums=0)
_scale = jax.jit(lambda g, f: {p: g[p] * f for p in g})


def group_offsets(m: Dims) -> dict:
    """The program's clipping groups: each norm scale and each matrix, one
    group per layer of a stacked leaf; the tied matrix is one group.
    leaf -> (first group id, count)."""
    out, off = {}, 0
    for path in param_shapes(m):
        n = m.layers if path.startswith("dense_blocks/") else 1
        out[path] = (off, n)
        off += n
    return out


class DPReference(D.DPReference):
    """The reference's DP steps, one sequence at a time, with the dense
    reference's noise, Adam and batch loss. `precision="fp8"`
    is the control. A `fault` plants one for the calibration of the limits:
      no_cross    the tied matrix's squared norm as the two uses' own,
                  ‖G_e‖² + ‖G_h‖², without their cross term (which then
                  reads 0);
      no_head     the tied matrix's squared norm as the embedding's use
                  alone, ‖G_e‖²: the head's use left out of its group's
                  norm (the cross term reads 0);
      half_batch  the second half of the rows left out, the mean taken over
                  the rest;
      half_sum    the second half of the rows left out of the clipped sum,
                  the mean still taken over the whole batch;
      norm_sq     clip factors min(1, C / ||g||^2), from the squared norm.
    After a step, `readings` holds the first batch's per-example squared
    norms (B, K) in `offsets` order and the tied cross term (B,)."""

    def __init__(self, m: Dims, job: Job, precision: str = "f32",
                 fault: str | None = None):
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.m, self.job, self.precision, self.fault = m, job, precision, fault
        self.offsets = group_offsets(m)
        self.k = sum(n for _, n in self.offsets.values())
        self.readings = None
        prec = precision
        self._grad = {c: _jit(("grad", m, prec, c), lambda c=c: jax.jit(
            partial(_chunk_grad, m, prec, c))) for c in CHUNKS}
        self._leaf = _jit(("leaf", job), lambda: jax.jit(
            self._leaf_update, donate_argnums=(0, 2, 3)))
        self._apply = _jit(("apply", job), lambda: jax.jit(
            self._adam_apply, donate_argnums=0))

    def initial_state(self, params: dict) -> dict:
        return {"params": flatten(params), "mu": {}, "nu": {}, "t": 0}

    def batch_loss(self, params: dict, tokens: np.ndarray,
                   targets: np.ndarray) -> float:
        """Mean example loss of a batch at `params` (flat), no step: the
        loss the last chunk's gradient computes, which saves compiling a
        forward of its own."""
        fn = self._grad[CHUNKS[-1]]
        return float(np.mean([float(fn(params, tokens[i], targets[i])[0])
                              for i in range(tokens.shape[0])]))

    def _factors(self, params, tokens, targets) -> tuple:
        """Per row: the loss, the squared norms (B, K), the cross term and
        the flat clip factor."""
        b = tokens.shape[0]
        norms = np.zeros((b, self.k))
        losses, cross, factors = np.zeros(b), np.zeros(b), np.zeros(b)
        for i in range(b):
            for chunk, fn in self._grad.items():
                val, _, rows, tied = fn(params, tokens[i], targets[i])
                if "embed" in chunk:
                    e2, h2, t2, c = np.asarray(tied, np.float64)
                    rows[EMBED] = [{"no_cross": e2 + h2, "no_head": e2}.get(
                        self.fault, t2)]
                    cross[i] = (0.0 if self.fault in ("no_cross", "no_head")
                                else c)
                for p, sq in rows.items():
                    o, n = self.offsets[p]
                    norms[i, o: o + n] = np.asarray(sq, np.float64)
            losses[i] = float(val)
            total = float(np.sum(norms[i]))
            norm = total if self.fault == "norm_sq" else math.sqrt(
                total + 1e-12)
            factors[i] = min(1.0, self.job.threshold / max(norm, 1e-30))
        return losses, norms, cross, factors

    def _clipped_sum(self, chunk, params, tokens, targets, factors, live):
        acc = None
        for i in range(live):
            g = self._grad[chunk](params, tokens[i], targets[i])[1]
            f = jnp.float32(factors[i])
            acc = _scale(g, f) if acc is None else _acc(acc, g, f)
            del g
        return acc

    def step(self, state: dict, tokens: np.ndarray, targets: np.ndarray,
             key, other: tuple | None = None, read=None) -> tuple:
        """One DP step; updates `state` in place. Returns (the mean example
        loss, {leaf: norm of the gradient as Adam gets it}, the second
        batch's mean loss or None). `other` and `read` as in
        `dense.DPReference.step`."""
        job = self.job
        if other is not None and state["t"] != 0:
            raise ValueError("a second batch is stepped from the initial "
                             "state only")
        b = tokens.shape[0]
        live = b // 2 if self.fault in ("half_batch", "half_sum") else b
        denom = live if self.fault == "half_batch" else job.batch
        params = state["params"]
        losses, norms, cross, factors = self._factors(params, tokens,
                                                      targets)
        self.readings = {"norms": norms, "cross": cross}
        losses_o = factors_o = None
        if other is not None:
            losses_o, _, _, factors_o = self._factors(params, *other)
        std = jnp.float32(job.sigma * job.threshold)
        k_noise = jax.random.split(key)[0]
        names = list(param_shapes(self.m))
        grad_norms = {}
        for chunk in CHUNKS:
            acc = self._clipped_sum(chunk, params, tokens, targets, factors,
                                    live)
            acc_o = (None if other is None else self._clipped_sum(
                chunk, params, other[0], other[1], factors_o, live))
            for p in list(acc):
                leaf_key = jax.random.fold_in(k_noise, names.index(p))
                if state["t"] == 0:
                    state["mu"][p] = jnp.zeros_like(params[p])
                    state["nu"][p] = jnp.zeros_like(params[p])
                mu, nu, gn, exact, stored = self._leaf(
                    acc.pop(p), None if acc_o is None else acc_o.pop(p),
                    state["mu"][p], state["nu"][p], leaf_key, std,
                    jnp.float32(denom))
                state["mu"][p], state["nu"][p] = mu, nu
                grad_norms[p] = float(gn)
                if read is not None and exact is not None:
                    read(p, exact, stored)
                del exact, stored
            del acc, acc_o
        state["t"] += 1
        state["params"] = self._apply(params, state["mu"], state["nu"],
                                      jnp.int32(state["t"]))

        def mean(v):
            return float(np.sum(v[:live] if self.fault == "half_batch"
                                else v)) / denom

        return (mean(losses), grad_norms,
                None if losses_o is None else mean(losses_o))

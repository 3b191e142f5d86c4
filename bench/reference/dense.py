"""Plain reference for the dense GQA decoder family, and the weights the
benchmark feeds both it and the program.

Written from the published description (Qwen3 / MiniCPM: pre-norm RMSNorm
blocks, grouped-query attention with RoPE and optional per-head q/k RMSNorm,
SwiGLU MLP, untied LM head) with the departures its configuration file
lists. It imports nothing of the program. Weights use the program's
checkpoint layout: one fused `qkv` matrix (q heads, then k, then v), one
fused `gate_up` matrix (gate, then up), layers stacked on a leading axis.

Arithmetic is float32 at `highest` matmul precision. `precision="fp8"`
casts both operands of every matmul to float8_e4m3fn with one absmax scale
per tensor, as a lower-precision path would: that is the control.

The DP step follows Algorithm 1 of the paper (per-layer adaptive
clipping), with the privacy-budget split of its Proposition 3.1, `global`
noise allocation and Adam. State that the
configuration gives a dtype (weights, Adam moments) is stored in that dtype
(bf16); every step is computed in float32.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    layers: int
    heads: int
    kv: int
    hd: int
    vocab: int
    qk_norm: bool
    eps: float
    theta: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                   layers=cfg["num_hidden_layers"],
                   heads=cfg["num_attention_heads"],
                   kv=cfg["num_key_value_heads"],
                   hd=cfg.get("head_dim") or (cfg["hidden_size"]
                                              // cfg["num_attention_heads"]),
                   vocab=cfg["vocab_size"], qk_norm=bool(cfg.get("qk_norm")),
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]))


def param_shapes(m: Dims) -> dict:
    """Leaf path -> shape, in the program's checkpoint layout."""
    n = m.layers
    out = {
        "embed/w": (m.vocab, m.d),
        "final_norm/s": (m.d,),
        "head/w": (m.d, m.vocab),
        "dense_blocks/attn_norm/s": (n, m.d),
        "dense_blocks/attn/qkv/w": (n, m.d, (m.heads + 2 * m.kv) * m.hd),
        "dense_blocks/attn/o/w": (n, m.heads * m.hd, m.d),
        "dense_blocks/mlp_norm/s": (n, m.d),
        "dense_blocks/mlp/gate_up/w": (n, m.d, 2 * m.f),
        "dense_blocks/mlp/down/w": (n, m.f, m.d),
    }
    if m.qk_norm:
        out["dense_blocks/attn/q_norm/s"] = (n, m.hd)
        out["dense_blocks/attn/k_norm/s"] = (n, m.hd)
    return dict(sorted(out.items()))


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}/{k}" if prefix else k
        out.update(flatten(v, path) if isinstance(v, dict) else {path: v})
    return out


def init_params(m: Dims, key, dtype=jnp.bfloat16) -> dict:
    """The benchmark's weights from one key: norm scales 1, embedding
    N(0, 0.02²), every matrix N(0, 1/fan_in). Call it under one jit."""
    out = {}
    for i, (path, shape) in enumerate(param_shapes(m).items()):
        k = jax.random.fold_in(key, i)
        if path.endswith("/s"):
            out[path] = jnp.ones(shape, dtype)
        elif path == "embed/w":
            out[path] = (0.02 * jax.random.normal(k, shape)).astype(dtype)
        else:
            std = 1.0 / math.sqrt(shape[-2])
            out[path] = (std * jax.random.normal(k, shape)).astype(dtype)
    return nest(out)


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn), scale


@jax.custom_vjp
def fp8_matmul(x, w):
    return _fp8_fwd(x, w)[0]


def _fp8_fwd(x, w):
    xq, xs = _fp8(x)
    wq, ws = _fp8(w)
    y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y * (xs * ws), (xq, xs, wq, ws)


def _fp8_bwd(res, gy):
    # cotangents in float32 against the fp8-rounded operands
    xq, xs, wq, ws = res
    x = xq.astype(jnp.float32) * xs
    w = wq.astype(jnp.float32) * ws
    gx = jnp.matmul(gy, w.T, precision=HIGHEST)
    gw = jnp.matmul(x.reshape(-1, x.shape[-1]).T,
                    gy.reshape(-1, gy.shape[-1]), precision=HIGHEST)
    return gx, gw


fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(x, w, precision: str):
    """x (..., k) @ w (k, n) in float32, or through fp8 for the control."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "fp8":
        return fp8_matmul(x, w)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, s, eps):
    mu = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mu + eps) * s.astype(jnp.float32)


def rope(x, positions, theta):
    """x (T, H, hd): rotate interleaved pairs (x[2i], x[2i+1])."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


QUERY_BLOCK = 512  # queries per block of scores: (heads, 512, T) at a time


def attention(m: Dims, p, x, positions, precision):
    """Causal GQA over one sequence; x (T, d) -> (T, d). Scores are formed
    one block of queries at a time, against the keys up to the block's end."""
    t = x.shape[0]
    qkv = matmul(x, p["qkv"]["w"], precision)
    q = qkv[:, : m.heads * m.hd].reshape(t, m.heads, m.hd)
    k = qkv[:, m.heads * m.hd: (m.heads + m.kv) * m.hd].reshape(t, m.kv, m.hd)
    v = qkv[:, (m.heads + m.kv) * m.hd:].reshape(t, m.kv, m.hd)
    if m.qk_norm:
        q = rmsnorm(q, p["q_norm"]["s"], m.eps)
        k = rmsnorm(k, p["k_norm"]["s"], m.eps)
    q, k = rope(q, positions, m.theta), rope(k, positions, m.theta)
    g = m.heads // m.kv
    k = jnp.repeat(k, g, axis=1)  # head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        scores = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi],
                            precision=HIGHEST) / math.sqrt(m.hd)
        causal = positions[lo:hi, None] >= positions[None, :hi]
        w = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hts,shd->thd", w, v[:hi], precision=HIGHEST))
    out = jnp.concatenate(outs, axis=0)
    return matmul(out.reshape(t, m.heads * m.hd), p["o"]["w"], precision)


def block(m: Dims, p, x, positions, precision):
    """One pre-norm decoder layer."""
    h = rmsnorm(x, p["attn_norm"]["s"], m.eps)
    x = x + attention(m, p["attn"], h, positions, precision)
    h = rmsnorm(x, p["mlp_norm"]["s"], m.eps)
    gu = matmul(h, p["mlp"]["gate_up"]["w"], precision)
    act = jax.nn.silu(gu[:, : m.f]) * gu[:, m.f:]
    return x + matmul(act, p["mlp"]["down"]["w"], precision)


def hidden(m: Dims, params, tokens, precision="f32"):
    """Final-normed hidden states of one sequence; tokens (T,) -> (T, d)."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    blocks = params["dense_blocks"]
    # a gradient keeps each layer's input only and recomputes the rest
    layer = jax.checkpoint(lambda xx, p: block(m, p, xx, positions,
                                               precision))
    for i in range(m.layers):
        x = layer(x, jax.tree_util.tree_map(lambda a, i=i: a[i], blocks))
    return rmsnorm(x, params["final_norm"]["s"], m.eps)


def logits(m: Dims, params, tokens, precision="f32"):
    return matmul(hidden(m, params, tokens, precision), params["head"]["w"],
                  precision)


def example_loss(m: Dims, params, tokens, targets, precision="f32"):
    """Mean next-token cross-entropy over the targets that are >= 0."""
    lg = logits(m, params, tokens, precision)
    valid = targets >= 0
    tok = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[:, None], 1)[:, 0]
    ce = (jax.nn.logsumexp(lg, axis=-1) - tok) * valid
    return jnp.sum(ce) / jnp.maximum(jnp.sum(valid), 1)


# ---------------------------------------------------------------------------
# The DP step.
# ---------------------------------------------------------------------------

# Leaves whose gradients one backward pass takes together: at most one
# chunk's float32 gradient and clipped sums are held at a time.
CHUNKS = (("dense_blocks",), ("embed",), ("head", "final_norm"))
FAULTS = ("half_batch", "half_sum", "norm_sq")


@dataclasses.dataclass(frozen=True)
class Job:
    """The private training job of a cell (from its traffic file)."""

    batch: int
    sigma: float
    adaptive: bool
    init_threshold: float
    target_quantile: float
    quantile_lr: float
    quantile_budget: float
    lr: float
    b1: float = 0.9
    b2: float = 0.999
    adam_eps: float = 1e-8

    @classmethod
    def of(cls, traffic: dict) -> "Job":
        dp, opt = traffic["dp"], traffic["optimizer"]
        if dp["clipping"] != "per_layer":
            raise ValueError(f"the reference clips per layer, not "
                             f"{dp['clipping']!r}")
        return cls(batch=traffic["batch"], sigma=dp["sigma"],
                   adaptive=dp["adaptive"],
                   init_threshold=dp["init_threshold"],
                   target_quantile=dp["target_quantile"],
                   quantile_lr=dp["quantile_lr"],
                   quantile_budget=dp["quantile_budget"], lr=opt["lr"],
                   b1=opt["b1"], b2=opt["b2"], adam_eps=opt["eps"])

    def noise_multipliers(self, k: int) -> tuple:
        """(sigma_new, sigma_b): Proposition 3.1's split of sigma between
        the gradient and the K clip-count releases."""
        if not self.adaptive:
            return self.sigma, 0.0
        sigma_b = math.sqrt(k * self.sigma ** 2 / (4.0 * self.quantile_budget))
        sigma_new = (self.sigma ** -2 - k / (2.0 * sigma_b) ** 2) ** -0.5
        return sigma_new, sigma_b


def group_offsets(m: Dims) -> dict:
    """Per-layer clipping groups: each norm scale and each matrix, one group
    per layer of a stacked leaf. leaf -> (first group id, count)."""
    out, off = {}, 0
    for path in param_shapes(m):
        n = m.layers if path.startswith("dense_blocks/") else 1
        out[path] = (off, n)
        off += n
    return out


def as_stored(x, dtype):
    """x rounded to what `dtype` stores, in float32: reduce_precision, which
    XLA keeps where it may drop a pair of casts."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _row_norms(m: Dims, path: str, g):
    """Squared norm of a leaf's gradient per group: (layers,) or (1,)."""
    rows = m.layers if path.startswith("dense_blocks/") else 1
    return jnp.sum(jnp.square(g.reshape(rows, -1)), axis=1)


class DPReference:
    """The reference's DP steps, one sequence and one chunk of leaves at a
    time. `precision="fp8"` is the control. A `fault` plants one for the
    calibration of the limits:
      half_batch  the second half of the rows left out, the mean taken over
                  the rest;
      half_sum    the second half of the rows left out of the clipped sum,
                  the mean still taken over the whole batch;
      norm_sq     clip factors min(1, C / ||g||^2), from the squared norm."""

    def __init__(self, m: Dims, job: Job, precision: str = "f32",
                 fault: str | None = None):
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.m, self.job, self.precision, self.fault = m, job, precision, fault
        self.offsets = group_offsets(m)
        self.k = sum(n for _, n in self.offsets.values())
        self.sigma_new, self.sigma_b = job.noise_multipliers(self.k)
        self._grad = {c: self._make_grad(c) for c in CHUNKS}
        self._acc = jax.jit(
            lambda acc, g, f: {p: acc[p] + g[p] * f[p] for p in acc},
            donate_argnums=0)
        self._scale = jax.jit(lambda g, f: {p: g[p] * f[p] for p in g})
        self._leaf = jax.jit(self._leaf_update, donate_argnums=(0, 2, 3))
        self._apply = jax.jit(self._adam_apply, donate_argnums=0)
        self._loss = jax.jit(lambda params, tokens, targets: example_loss(
            m, params, tokens, targets, precision))

    def initial_state(self, params: dict) -> dict:
        flat = flatten(params)
        # Adam's moments start at zero; each leaf's are made as its first
        # step reaches it
        return {"params": flat, "mu": {}, "nu": {},
                "thresholds": np.full(self.k, self.job.init_threshold),
                "t": 0}

    def _make_grad(self, chunk):
        m, prec = self.m, self.precision

        def fn(params, tokens, targets):
            sub = {p: v.astype(jnp.float32) for p, v in params.items()
                   if p.split("/")[0] in chunk}
            rest = {p: v for p, v in params.items() if p not in sub}

            def loss(s):
                return example_loss(m, nest({**rest, **s}), tokens, targets,
                                    prec)

            val, g = jax.value_and_grad(loss)(sub)
            return val, g, {p: _row_norms(m, p, v) for p, v in g.items()}

        return jax.jit(fn)

    def _factors(self, sq_rows: dict, thr: np.ndarray) -> dict:
        """One example's clip factor per leaf, shaped to broadcast."""
        out = {}
        for p, sq in sq_rows.items():
            o, n = self.offsets[p]
            sq = np.asarray(sq, np.float64)
            norm = sq if self.fault == "norm_sq" else np.sqrt(sq + 1e-12)
            f = np.minimum(1.0, thr[o: o + n] / np.maximum(norm, 1e-30))
            shape = (n,) + (1,) * (len(param_shapes(self.m)[p]) - 1)
            out[p] = jnp.asarray(f, jnp.float32).reshape(
                shape if n > 1 else ())
        return out

    def _clipped_sum(self, chunk, params, tokens, targets, thr, live,
                     norms, losses):
        """Sum over the live rows of the clipped per-example gradients of
        one chunk's leaves; fills the rows' norms and losses."""
        acc = None
        for i in range(tokens.shape[0]):
            val, g, rows = self._grad[chunk](params, tokens[i], targets[i])
            losses[i] = float(val)
            for p, sq in rows.items():
                o, n = self.offsets[p]
                norms[i, o: o + n] = np.asarray(sq, np.float64)
            if i >= live:
                continue
            f = self._factors(rows, thr)
            acc = self._scale(g, f) if acc is None else self._acc(acc, g, f)
            del g
        return acc

    def _leaf_update(self, s, s_other, mu, nu, key, std, denom):
        """One leaf: the noised mean gradient and Adam's moments from it;
        with a second batch's clipped sum, the difference of the two
        batches' gradients before noise and of the first moments the two
        steps would store, both over the same noise."""
        b1, b2 = self.job.b1, self.job.b2
        z = jax.random.normal(key, s.shape, jnp.float32)
        g = (s + std * z) / denom
        mu_new = b1 * mu.astype(jnp.float32) + (1 - b1) * g
        nu_new = b2 * nu.astype(jnp.float32) + (1 - b2) * jnp.square(g)
        out = (mu_new.astype(mu.dtype), nu_new.astype(nu.dtype),
               jnp.sqrt(jnp.sum(jnp.square(g))))
        if s_other is None:
            return out + (None, None)
        g_o = (s_other + std * z) / denom
        mu_o = b1 * mu.astype(jnp.float32) + (1 - b1) * g_o
        stored = (as_stored(mu_new, mu.dtype)
                  - as_stored(mu_o, mu.dtype)) / (1 - b1)
        return out + ((s - s_other) / denom, stored)

    def _adam_apply(self, params, mu, nu, t):
        job = self.job
        bc1 = 1 - job.b1 ** t.astype(jnp.float32)
        bc2 = 1 - job.b2 ** t.astype(jnp.float32)
        out = {}
        for p in params:
            mhat = mu[p].astype(jnp.float32) / bc1
            vhat = nu[p].astype(jnp.float32) / bc2
            u = -job.lr * mhat / (jnp.sqrt(vhat) + job.adam_eps)
            out[p] = (params[p].astype(jnp.float32) + u).astype(params[p].dtype)
        return out

    def batch_loss(self, params: dict, tokens: np.ndarray,
                   targets: np.ndarray) -> float:
        """Mean example loss of a batch at `params` (flat), no step."""
        tree = nest(params)
        vals = [float(self._loss(tree, tokens[i], targets[i]))
                for i in range(tokens.shape[0])]
        return float(np.mean(vals))

    def step(self, state: dict, tokens: np.ndarray, targets: np.ndarray,
             key, other: tuple | None = None, read=None) -> tuple:
        """One DP step; updates `state` in place. Returns (the mean example
        loss, {leaf: norm of the gradient as Adam gets it}, the second
        batch's mean loss or None).

        `other` = (tokens, targets) of a second batch, stepped from the same
        (initial) state with the same noise: for each leaf,
        `read(leaf, exact, stored)` gets (S - S_other) / B, the difference
        of the two batches' clipped sums before noise, and the difference of
        the two steps' stored first moments over (1 - b1), where the noise
        cancels but the rounding to the moments' dtype does not."""
        job = self.job
        if other is not None and state["t"] != 0:
            raise ValueError("a second batch is stepped from the initial "
                             "state only")
        b = tokens.shape[0]
        live = b // 2 if self.fault in ("half_batch", "half_sum") else b
        denom = live if self.fault == "half_batch" else job.batch
        thr = np.asarray(state["thresholds"], np.float64)
        params = state["params"]
        norms = np.zeros((b, self.k))
        losses = np.zeros(b)
        losses_o = np.zeros(b)
        std = jnp.float32(self.sigma_new * math.sqrt(float(np.sum(thr ** 2))))
        k_noise, k_q = jax.random.split(key)
        names = list(param_shapes(self.m))
        grad_norms = {}
        for chunk in CHUNKS:
            acc = self._clipped_sum(chunk, params, tokens, targets, thr,
                                    live, norms, losses)
            acc_o = None
            if other is not None:
                acc_o = self._clipped_sum(chunk, params, other[0], other[1],
                                          thr, live, np.zeros_like(norms),
                                          losses_o)
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
        counts = np.sum(norms[:live] <= thr[None, :] ** 2, axis=0).astype(float)
        state["t"] += 1
        state["params"] = self._apply(params, state["mu"], state["nu"],
                                      jnp.int32(state["t"]))
        if job.adaptive:
            noise = self.sigma_b * np.asarray(jax.random.normal(
                k_q, (self.k,), jnp.float32), np.float64)
            frac = (counts + noise) / job.batch
            thr = np.clip(thr * np.exp(-job.quantile_lr
                                       * (frac - job.target_quantile)),
                          1e-10, 1e10)
        state["thresholds"] = thr
        loss = float(np.sum(losses[:live] if self.fault == "half_batch"
                            else losses)) / denom
        loss_o = None if other is None else float(
            np.sum(losses_o[:live] if self.fault == "half_batch"
                   else losses_o)) / denom
        return loss, grad_norms, loss_o

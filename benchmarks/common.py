"""Shared helpers of the paper-utility suites: CSV rows, a timer, and the
small DP MLP classifier they train."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp_layers as dpl
from repro.core.spec import GroupLayout, P


def timeit(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time per call in microseconds (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


# ---------------------------------------------------------------------------
# Small models used by the utility benchmarks (CIFAR/SST-2 analogues).
# ---------------------------------------------------------------------------


def mlp_classifier(dim: int, width: int, depth: int, classes: int,
                   feature_scales: tuple[float, ...] | None = None):
    """Spec + per-example-loss fn for a DP MLP classifier.

    feature_scales: optional per-layer input magnification — creates the
    strongly NON-uniform per-layer gradient norms of the paper's Figure 2
    (what makes hand-set uniform per-layer thresholds hurt)."""
    spec = {}
    sizes = [dim] + [width] * depth + [classes]
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        spec[f"l{i}"] = {"w": P((a, b)), "b": P((b,), init="zeros")}
    layout = GroupLayout(spec)

    n_layers = len(sizes) - 1
    scales = feature_scales or (1.0,) * n_layers

    def loss_fn(params, batch, th):
        x, y = batch
        h = x
        for i in range(n_layers):
            h = dpl.dp_linear(params[f"l{i}"]["w"], params[f"l{i}"]["b"],
                              (h * scales[i])[:, None, :] if h.ndim == 2
                              else h * scales[i], th[f"l{i}"])
            h = h[:, 0] if h.ndim == 3 else h
            if i < n_layers - 1:
                h = jnp.tanh(h)
        logp = jax.nn.log_softmax(h)
        return -logp[jnp.arange(y.shape[0]), y]

    def accuracy(params, x, y):
        th = layout.pack_value(jnp.inf, x.shape[0])
        h = x
        for i in range(n_layers):
            h = dpl.dp_linear(params[f"l{i}"]["w"], params[f"l{i}"]["b"],
                              (h * scales[i])[:, None, :],
                              th[f"l{i}"])[:, 0]
            if i < n_layers - 1:
                h = jnp.tanh(h)
        return float(jnp.mean((jnp.argmax(h, -1) == y).astype(jnp.float32)))

    return spec, layout, loss_fn, accuracy

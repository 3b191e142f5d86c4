"""Table 4/12 analogue: adaptive per-layer vs flat under fixed epochs.

Paper: under the SAME number of training epochs, adaptive per-layer
clipping matches flat clipping's utility — which, combined with the per-
update speed advantage, yields the wall-time win.
Testbed: tiny LM on the synthetic Markov corpus, loss after E epochs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line
from repro import optim
from repro.configs import get_config
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import init_params
from repro.data import PoissonSampler, SyntheticLM, make_lm_batch, pack_documents
from repro.models.transformer import build_model


def _train(mode, epochs, seed, *, quick):
    cfg = get_config("tiny")
    m = build_model(cfg)
    seq, batch = 32, 16
    src = SyntheticLM(vocab_size=cfg.vocab_size, num_docs=96, doc_len=64,
                      seed=7)
    rows = pack_documents(src.documents(), seq)
    n = rows.shape[0]
    steps = max(1, epochs * n // batch)
    params = init_params(m.spec, jax.random.PRNGKey(seed))
    dpc = DPConfig(mode=mode, sigma=0.7, sampling_rate=batch / n,
                   steps=steps, adaptive=(mode == "per_layer"),
                   init_threshold=1.0, target_quantile=0.5)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(2e-3), dpc, batch_size=batch)
    opt_state, dp_state = init_fn(params)
    step = jax.jit(step_fn)
    sampler = PoissonSampler(num_examples=n, rate=batch / n,
                             max_batch=batch, seed=seed)
    key = jax.random.PRNGKey(seed)
    loss = None
    for i in range(steps):
        idx = sampler.next_indices()
        b = make_lm_batch(rows, idx, batch)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt_state, dp_state, met = step(params, opt_state, dp_state,
                                                b, key)
    # eval: mean loss on all rows
    th = m.layout.pack_value(jnp.inf, n)
    ev = make_lm_batch(rows, np.arange(n), n)
    losses = m.loss_fn(params, {k: jnp.asarray(v) for k, v in ev.items()}, th)
    return float(jnp.mean(losses))


def run(quick: bool = True) -> list[str]:
    epoch_grid = (1, 3) if quick else (1, 3, 10)
    seeds = (0,) if quick else (0, 1, 2)
    lines = []
    for e in epoch_grid:
        for mode, label in (("per_layer", "adaptive_per_layer"),
                            ("ghost_flat", "flat")):
            ls = [_train(mode, e, s, quick=quick) for s in seeds]
            lines.append(csv_line(
                f"table4_epochs_E{e}_{label}", 0.0,
                f"eval_loss={np.mean(ls):.4f}"))
    return lines

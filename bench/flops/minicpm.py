"""Operations and bytes of the MiniCPM family: the dense decoder's
(`dense.py`), with one matrix serving as the embedding and the LM head.

The tied head is a matmul and counts once, in `linears`; the embedding is
a gather and counts no FLOPs, as in `dense.py`. The muP scalings are
elementwise and count nothing.
"""
from __future__ import annotations

from bench.flops import dense

BF16 = dense.BF16
dims = dense.dims
linears = dense.linears
matmul_params = dense.matmul_params
attention_flops_fwd = dense.attention_flops_fwd
train_step_flops = dense.train_step_flops
ghost_norm_cost = dense.ghost_norm_cost
least_time = dense.least_time


def params(cfg: dict) -> int:
    """Every parameter: the matrices (the tied one once) and the norm
    scales."""
    m = dims(cfg)
    norms = m["layers"] * 2 * m["d"] + m["d"]
    return matmul_params(cfg) + norms

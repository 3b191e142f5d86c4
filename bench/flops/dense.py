"""Operations and bytes of the dense GQA decoder family, from the shapes in
a configuration file (`bench/configs/<name>.json`).

Conventions:
  * model FLOPs of a training step are 6 x tokens x matmul parameters
    (forward, and backward to activations and weights) plus attention's
    score and value products over the full T x T square, forward and
    backward: 3 x 4 x B x T^2 x heads x head_dim per layer (the PaLM
    appendix-B convention). Recomputation and the clipping work are not
    model FLOPs;
  * the embedding is a gather and counts no FLOPs;
  * a ghost norm of one linear (activations (B, T, din), output gradients
    (B, T, dout)) needs the upper triangle of both T x T grams:
    B x T(T+1) x (din + dout) FLOPs plus the elementwise product, and reads
    each operand once in bf16.
"""
from __future__ import annotations

BF16 = 2


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "heads": h,
            "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h, "vocab": cfg["vocab_size"]}


def linears(cfg: dict) -> list:
    """[(name, din, dout, uses per forward)] of every matmul weight."""
    m = dims(cfg)
    d, hd, n = m["d"], m["hd"], m["layers"]
    return [("qkv", d, (m["heads"] + 2 * m["kv"]) * hd, n),
            ("o", m["heads"] * hd, d, n),
            ("gate_up", d, 2 * m["f"], n),
            ("down", m["f"], d, n),
            ("head", d, m["vocab"], 1)]


def matmul_params(cfg: dict) -> int:
    return sum(din * dout * uses for _, din, dout, uses in linears(cfg))


def params(cfg: dict) -> int:
    """Every parameter: the matrices, the embedding and the norm scales."""
    m = dims(cfg)
    norms = m["layers"] * (2 * m["d"] + (2 * m["hd"] if cfg.get("qk_norm")
                                         else 0)) + m["d"]
    return matmul_params(cfg) + m["vocab"] * m["d"] + norms


def attention_flops_fwd(cfg: dict, batch: int, seq: int) -> float:
    """Score and value products of one forward, full T x T square."""
    m = dims(cfg)
    return 4.0 * batch * seq * seq * m["heads"] * m["hd"] * m["layers"]


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (forward + backward)."""
    tokens = batch * seq
    return (6.0 * tokens * matmul_params(cfg)
            + 3.0 * attention_flops_fwd(cfg, batch, seq))


def ghost_norm_cost(batch: int, seq: int, din: int, dout: int) -> tuple:
    """(FLOPs, bytes) of one linear's per-example squared gradient norms."""
    flops = batch * seq * (seq + 1) * (din + dout + 1)
    nbytes = batch * seq * (din + dout) * BF16 + batch * 4
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of the compute and the memory bound."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


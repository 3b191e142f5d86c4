"""The one generator of the training mixes: corpora from the parameters in
`bench/traffic/<mix>.json` and `--seed`.

Training corpus: a copy of the repository's `SyntheticLM` source (a
Markov chain over a Zipf marginal with one planted successor per token,
followed with probability `order_mix`), drawn for all documents at once,
then packed into rows of `seq` tokens. Every seed gives rows of the same
shape; the seed draws the tokens.
"""
from __future__ import annotations

import numpy as np


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for jax.random.PRNGKey from any non-negative integer
    (PRNGKey silently drops the high bits of a large one)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def zipf_markov_docs(vocab: int, docs: int, doc_len: int, seed: int,
                     order_mix: float = 0.8) -> np.ndarray:
    """(docs, doc_len) int32 documents of the SyntheticLM chain."""
    r = rng(seed, 1)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    succ = r.integers(0, vocab, size=vocab)

    def marginal(size):
        return np.minimum(np.searchsorted(cdf, r.random(size)), vocab - 1)

    out = np.empty((docs, doc_len), np.int32)
    out[:, 0] = marginal(docs)
    follow = r.random((docs, doc_len)) < order_mix
    fresh = marginal((docs, doc_len))
    for t in range(1, doc_len):
        out[:, t] = np.where(follow[:, t], succ[out[:, t - 1]], fresh[:, t])
    return out


def train_rows(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """(rows, seq) packed training rows: documents of 2 x seq tokens."""
    seq, rows = traffic["seq"], traffic["rows"]
    docs = zipf_markov_docs(vocab, -(-rows // 2), 2 * seq, seed,
                            traffic["corpus"]["order_mix"])
    return docs.reshape(-1, seq)[:rows]


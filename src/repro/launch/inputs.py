"""Abstract input builders: ShapeDtypeStruct stand-ins for every workload.

This is the ONLY place the frontend stubs live (task-spec carve-out):
audio archs receive precomputed frame embeddings, VLMs receive precomputed
patch embeddings — weak-type-correct, shardable, no allocation."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import INPUT_SHAPES, InputShape, ModelConfig

VLM_PATCHES = 256  # stub vision-token count prepended to the text sequence


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    b, t = shape.global_batch, shape.seq_len
    batch = {
        "tokens": sds((b, t), jnp.int32),
        "targets": sds((b, t), jnp.int32),
    }
    if cfg.arch_type == "audio":
        batch["frames"] = sds((b, cfg.encoder_seq_len, cfg.d_model),
                              cfg.dtype)
    if cfg.arch_type == "vlm":
        batch["vision_embeds"] = sds((b, VLM_PATCHES, cfg.d_model), cfg.dtype)
        batch["positions3_full"] = sds((b, 3, t + VLM_PATCHES), jnp.int32)
    return batch


def serve_batch_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    return {"token": sds((shape.global_batch, 1), jnp.int32)}


# ---------------------------------------------------------------------------
# Ragged request batching (serving): real traffic never arrives as an
# equal-length batch, so the serving paths take right-padded prompts plus
# explicit true lengths (launch.serve) or raw per-request token arrays
# (launch.engine).
# ---------------------------------------------------------------------------


def pad_ragged_prompts(prompts) -> tuple[np.ndarray, np.ndarray]:
    """Right-pad a list of variable-length prompts into one batch.

    prompts: sequence of 1-D int token sequences (len >= 1 each).
    Returns (tokens (B, Pmax) int32, lengths (B,) int32). The pad value is
    0 — it never reaches the cache: the serving paths mask every position
    >= lengths[i] out of both the cache write and the logit gather.
    """
    rows = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    if not rows:
        raise ValueError("empty request set")
    if any(r.size == 0 for r in rows):
        raise ValueError("empty prompt in request set: seed with a BOS token")
    pmax = max(r.size for r in rows)
    toks = np.zeros((len(rows), pmax), np.int32)
    lengths = np.zeros((len(rows),), np.int32)
    for i, r in enumerate(rows):
        toks[i, : r.size] = r
        lengths[i] = r.size
    return toks, lengths


def synthetic_requests(vocab_size: int, n: int, *, min_len: int,
                       max_len: int, seed: int = 0) -> list[np.ndarray]:
    """n random prompts with lengths uniform in [min_len, max_len] — the
    ragged request sets used by the serve CLI, the engine smoke and the
    examples."""
    if not 1 <= min_len <= max_len:
        raise ValueError(f"need 1 <= min_len <= max_len, got "
                         f"[{min_len}, {max_len}]")
    rng = np.random.default_rng(seed)
    lens = rng.integers(min_len, max_len + 1, size=n)
    return [rng.integers(0, vocab_size, size=int(l)).astype(np.int32)
            for l in lens]


def concrete_train_batch(cfg: ModelConfig, b: int, t: int, key) -> dict:
    """Small concrete batch for smoke tests / examples."""
    toks = jax.random.randint(key, (b, t), 0, cfg.vocab_size)
    tgt = jnp.roll(toks, -1, axis=1).at[:, -1].set(-1)
    batch = {"tokens": toks, "targets": tgt}
    if cfg.arch_type == "audio":
        batch["frames"] = 0.1 * jax.random.normal(
            key, (b, cfg.encoder_seq_len, cfg.d_model), dtype=jnp.float32
        ).astype(cfg.dtype)
    if cfg.arch_type == "vlm":
        tv = 8
        batch["vision_embeds"] = 0.1 * jax.random.normal(
            key, (b, tv, cfg.d_model), dtype=jnp.float32).astype(cfg.dtype)
        pos = jnp.broadcast_to(jnp.arange(t + tv, dtype=jnp.int32)[None],
                               (b, t + tv))
        batch["positions3_full"] = jnp.broadcast_to(
            pos[:, None, :], (b, 3, t + tv))
    return batch

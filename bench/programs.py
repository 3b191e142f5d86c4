"""The program's side of a configuration file: the registry's ModelConfig
for the file's `arch`, cut to the file's depth, with the file's RMSNorm
epsilon, and checked against every number of the file that the program
reads, so that a change to the registry cannot move the benchmark unseen."""
from __future__ import annotations

import dataclasses

from bench import harness


def program_config(cfg: dict):
    """The registry's ModelConfig cut to the file's depth, with its RMSNorm
    epsilon, checked against
    every number of the file that the program reads."""
    import jax.numpy as jnp
    from repro.configs import get_config

    pc = dataclasses.replace(get_config(cfg["arch"]),
                             num_layers=cfg["num_hidden_layers"],
                             norm_eps=cfg["rms_norm_eps"])
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "resolved_head_dim": cfg["head_dim"],
            "qk_norm": cfg["qk_norm"], "norm_eps": cfg["rms_norm_eps"],
            "rope_theta": cfg["rope_theta"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": cfg["attention_bias"],
            "dtype": jnp.dtype(cfg["torch_dtype"])}
    got = {k: getattr(pc, k) for k in want}
    got["dtype"] = jnp.dtype(got["dtype"])
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad or pc.arch_type != "dense" or pc.sliding_window is not None:
        raise harness.CellError(f"program config {pc.name} departs from the "
                                f"benchmark's file: {bad}")
    return pc

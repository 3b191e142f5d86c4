"""minicpm-2b [dense]: 40L d_model=2304 36H (GQA kv=36) d_ff=5760
vocab=122753, RMSNorm eps 1e-5, rope_theta 10000, embedding tied to the LM
head, muP scalings scale_emb=12, scale_depth=1.4 (residual branches times
1.4/sqrt(40)), dim_model_base=256 (logits of h / 9) — llama-like otherwise;
trained with the WSD schedule (repro.optim.wsd).
[arXiv:2404.06395; huggingface.co/openbmb/MiniCPM-2B-sft-bf16 config.json]"""
import jax.numpy as jnp

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="minicpm-2b", arch_type="dense",
    num_layers=40, d_model=2304, d_ff=5760, vocab_size=122_753,
    num_heads=36, num_kv_heads=36, rope_theta=10_000.0, norm_eps=1e-5,
    tie_embeddings=True, scale_emb=12.0, scale_depth=1.4, mup_depth=40,
    dim_model_base=256,
    dtype=jnp.bfloat16,
)

# the same structure at CPU size: tied, muP (logit divisor 256 / 64 = 4)
REDUCED = ModelConfig(
    name="minicpm-2b-reduced", arch_type="dense",
    num_layers=2, d_model=256, d_ff=512, vocab_size=1_000,
    num_heads=4, num_kv_heads=4, rope_theta=10_000.0, norm_eps=1e-5,
    tie_embeddings=True, scale_emb=12.0, scale_depth=1.4, mup_depth=40,
    dim_model_base=64,
)

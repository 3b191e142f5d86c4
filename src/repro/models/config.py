"""Model configuration covering every assigned architecture family.

One dataclass drives the whole zoo: dense decoder LMs (llama/qwen style),
MoE (token-choice top-k, shared experts, MLA), SSM (Mamba2, RWKV6), hybrids
(Zamba2), encoder-decoder audio backbones (Whisper) and M-RoPE VLM decoders
(Qwen2-VL). `repro.configs.<id>` instantiates the exact assigned numbers.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    # ----- attention -----
    num_heads: int = 0  # 0 => attention-free (pure SSM)
    num_kv_heads: int = 0
    head_dim: int | None = None  # default d_model // num_heads
    qk_norm: bool = False  # per-head RMSNorm on q,k (Qwen3)
    qkv_bias: bool = False  # Qwen1.5
    rope_theta: float = 10_000.0
    sliding_window: int | None = None  # window size; None = full attention
    attention_kind: str = "gqa"  # gqa | mla | none
    # ----- MLA (DeepSeek-V3) -----
    q_lora_rank: int = 0  # 0 => direct q projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # ----- MoE -----
    num_experts: int = 0  # 0 => dense MLP
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim (d_ff used for dense layers)
    first_k_dense: int = 0  # leading dense layers (DeepSeek-V3 style)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_dispatch: str = "flat"  # flat (global-capacity scatter, exact
    #   masked-gram DP norms) | grouped (per-(example, expert) buffers:
    #   block-diagonal DP norms, ~B x cheaper — §Perf optimization)
    # ----- SSM: Mamba2 -----
    ssm_state: int = 0  # d_state (0 => no mamba layers)
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 256
    # ----- SSM: RWKV6 -----
    rwkv_head_dim: int = 64
    # ----- hybrid layout -----
    layer_pattern: str | None = None  # e.g. "mmmmma": m=mamba2, a=attn, r=rwkv
    shared_attention: bool = False  # Zamba2: ONE attn block shared across sites
    shared_every: int = 6  # apply the shared block before every k-th layer
    # ----- encoder-decoder (Whisper) -----
    encoder_layers: int = 0
    encoder_seq_len: int = 1500  # stub frame-embedding length (whisper-medium)
    # ----- VLM (Qwen2-VL) -----
    m_rope: bool = False
    m_rope_sections: tuple[int, ...] = (16, 24, 24)  # (t, h, w) of head_dim/2
    # ----- MTP (DeepSeek-V3 multi-token prediction) -----
    mtp_depth: int = 0
    # ----- muP (MiniCPM, arXiv:2404.06395) -----
    # x0 = scale_emb * E[ids]; each residual branch is multiplied by
    # scale_depth / sqrt(mup_depth); the LM head reads h / logit_divisor,
    # logit_divisor = d_model / dim_model_base. mup_depth is the PUBLISHED
    # depth, so a depth cut (a pipeline stage) keeps the multiplier.
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    mup_depth: int = 0  # 0 => residual multiplier 1
    dim_model_base: int = 0  # 0 => logit divisor 1
    # ----- misc -----
    max_seq_len: int = 131_072
    # one (vocab, d) matrix is the input embedding AND the LM head (head
    # logits = h @ Eᵀ); its clipping group's exact per-example norm needs
    # the cross term of the two uses (core.bk), so only BK execution of the
    # flat / group modes trains it privately
    tie_embeddings: bool = False
    dtype: Any = jnp.float32
    norm_eps: float = 1e-5
    # per-shard clipping layout (per-device analogue): M column blocks
    dp_blocks: int = 1
    # DP LoRA (the paper's large-model recipe): 0 = full fine-tune
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # rematerialize layer-scan bodies (activation checkpointing): without it
    # the L-layer scan saves every block's residuals and peak memory is
    # O(L x activations); with it, O(1 block) at ~1.33x flops.
    remat: bool = True

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def residual_multiplier(self) -> float:
        if not self.mup_depth:
            return 1.0
        return self.scale_depth / math.sqrt(self.mup_depth)

    @property
    def logit_divisor(self) -> float:
        if not self.dim_model_base:
            return 1.0
        return self.d_model / self.dim_model_base

    @property
    def has_attention(self) -> bool:
        return self.attention_kind != "none" and self.num_heads > 0

    def pattern(self) -> str:
        """Per-layer block kinds, length num_layers."""
        if self.layer_pattern is None:
            base = "a" if self.has_attention else ("r" if self.ssm_state == 0 else "m")
            return base * self.num_layers
        pat = (self.layer_pattern * (self.num_layers // len(self.layer_pattern) + 1))
        return pat[: self.num_layers]

    def validate(self) -> None:
        if self.has_attention:
            assert self.num_kv_heads > 0 and self.num_heads % self.num_kv_heads == 0
        if self.num_experts:
            assert self.num_experts_per_tok > 0
            assert self.moe_d_ff > 0
        if self.arch_type == "audio":
            assert self.encoder_layers > 0
        mup = (self.scale_emb != 1.0 or self.residual_multiplier != 1.0
               or self.logit_divisor != 1.0)
        if (self.tie_embeddings or mup) and (
                self.arch_type != "dense" or self.mtp_depth
                or self.lora_rank or self.num_experts):
            raise ValueError(
                f"{self.name}: tied embeddings and the muP scalars are "
                "implemented for the dense decoder without MTP or DP-LoRA")

    def param_count(self) -> int:
        """Exact dense-equivalent parameter count from the spec (filled in by
        models.transformer at build time); here: rough analytic estimate."""
        d, f, v, l = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        per_layer = 4 * d * d + 3 * d * f
        return l * per_layer + (1 if self.tie_embeddings else 2) * v * d


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One assigned workload shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

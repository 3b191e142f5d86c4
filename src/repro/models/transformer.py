"""Model assembly: every assigned architecture as one configurable LM.

Compile-time scalability: homogeneous layer stacks are `jax.lax.scan`s over
stacked parameters, so HLO size is O(1) in depth (95-layer DeepSeek-67B and
81-layer Zamba2 lower as fast as 2-layer smoke variants). Mixed-depth models
(DeepSeek-V3's first-k-dense) use one scan per homogeneous run. Zamba2's
SHARED attention block is applied inside the backbone scan under lax.cond
at its sites, with a sensitivity multiplier equal to the number of sites
(see DESIGN.md on parameter sharing).

The public surface per architecture:
    m = build_model(cfg)
    m.spec / m.layout                      # params + clipping groups
    m.loss_fn(params, batch, thresholds)   # (B,) per-example losses
    m.serve_step(params, cache, batch)     # one-token decode
    m.init_cache(batch_size, cache_len)    # decode cache pytree
    (launch.dryrun builds abstract ShapeDtypeStruct inputs from these)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp_layers as dpl
from repro.core.spec import GroupLayout, P, subth
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import rwkv6 as R6
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# Block specs.
# ---------------------------------------------------------------------------


def _attn_block_spec(cfg: ModelConfig, n: int, *, moe_layer: bool,
                     cross: bool = False, sens: float = 1.0) -> dict:
    stack = (n,) if n else ()
    spec = {
        "attn_norm": L.rmsnorm_spec(cfg.d_model, stack=stack, dtype=cfg.dtype),
        "attn": (A.mla_spec(cfg, stack=stack)
                 if cfg.attention_kind == "mla"
                 else A.gqa_spec(cfg, stack=stack, sensitivity_mult=sens)),
        "mlp_norm": L.rmsnorm_spec(cfg.d_model, stack=stack, dtype=cfg.dtype),
    }
    if cross:
        spec["cross_norm"] = L.rmsnorm_spec(cfg.d_model, stack=stack,
                                            dtype=cfg.dtype)
        spec["cross"] = A.gqa_spec(cfg, stack=stack, cross=True)
    if moe_layer:
        spec["moe"] = MOE.moe_spec(cfg, stack=stack)
    else:
        if sens > 1.0:
            spec["mlp"] = {
                "gate_up": L.linear_spec(cfg.d_model, 2 * cfg.d_ff,
                                         stack=stack, dtype=cfg.dtype,
                                         sensitivity_mult=sens),
                "down": L.linear_spec(cfg.d_ff, cfg.d_model, stack=stack,
                                      dtype=cfg.dtype, sensitivity_mult=sens),
            }
        else:
            spec["mlp"] = L.swiglu_spec(cfg.d_model, cfg.d_ff, stack=stack,
                                        dtype=cfg.dtype)
    return spec


def _mamba_block_spec(cfg: ModelConfig, n: int) -> dict:
    stack = (n,) if n else ()
    return M2.mamba2_spec(cfg, stack=stack)


def _rwkv_block_spec(cfg: ModelConfig, n: int) -> dict:
    stack = (n,) if n else ()
    return R6.rwkv6_spec(cfg, stack=stack)


# ---------------------------------------------------------------------------
# Block applies (one layer; thresholds pre-sliced by the scan).
# ---------------------------------------------------------------------------


def _branch(cfg, y):
    """A residual branch's output times muP's depth multiplier, where the
    config sets one (MiniCPM: scale_depth / sqrt(published depth))."""
    s = cfg.residual_multiplier
    return y if s == 1.0 else y * s


def _embed(cfg, params, tokens, th):
    """Input embeddings, times muP's scale_emb where the config sets one."""
    x = dpl.dp_embed(params["embed"]["w"], tokens, th["embed"])
    return x if cfg.scale_emb == 1.0 else x * cfg.scale_emb


def _lm_head(cfg, params, x, th, ids):
    """Logits from final-normed hidden states x (B, T, d), divided first by
    muP's logit divisor where the config sets one. A tied config reads the
    embedding table as the head (dp_tied_head, in the embedding's clipping
    group); `ids` (B, T) are the tokens the same rows embedded."""
    if cfg.logit_divisor != 1.0:
        x = x / cfg.logit_divisor
    if cfg.tie_embeddings:
        return dpl.dp_tied_head(params["embed"]["w"], x, ids, th["embed"])
    return dpl.dp_linear(params["head"]["w"], None, x, th["head"])


def _apply_attn_block(cfg, params, x, th, positions, *, causal=True,
                      window=None, enc_out=None, moe_layer=False,
                      lora=None, lora_th=None):
    h = L.rmsnorm(params["attn_norm"], x, th["attn_norm"], eps=cfg.norm_eps)
    if cfg.attention_kind == "mla":
        att = A.mla_attention(cfg, params["attn"], h, subth(th, "attn"),
                              positions, causal=causal, lora=lora,
                              lora_th=lora_th)
    else:
        att = A.gqa_attention(cfg, params["attn"], h, subth(th, "attn"),
                              positions, causal=causal, window=window,
                              lora=lora, lora_th=lora_th)
    x = x + _branch(cfg, att)
    aux = jnp.zeros((x.shape[0],), jnp.float32)
    if enc_out is not None:
        h = L.rmsnorm(params["cross_norm"], x, th["cross_norm"],
                      eps=cfg.norm_eps)
        ca = _cross_attention(cfg, params["cross"], h, subth(th, "cross"),
                              enc_out)
        x = x + ca
    h = L.rmsnorm(params["mlp_norm"], x, th["mlp_norm"], eps=cfg.norm_eps)
    if moe_layer:
        moe_fn = (MOE.moe_block_grouped if cfg.moe_dispatch == "grouped"
                  else MOE.moe_block)
        y, aux = moe_fn(cfg, params["moe"], h, subth(th, "moe"))
    else:
        y = L.swiglu(params["mlp"], h, subth(th, "mlp"), f=cfg.d_ff)
    return x + _branch(cfg, y), aux


def _cross_attention(cfg, params, x, th, enc_out):
    b, t = x.shape[0], x.shape[1]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = L.linear(params["qkv"], x, th["qkv"]).reshape(b, t, h, hd)
    kv = L.linear(params["kv"], enc_out, th["kv"])
    s = enc_out.shape[1]
    k = kv[..., : kvh * hd].reshape(b, s, kvh, hd)
    v = kv[..., kvh * hd:].reshape(b, s, kvh, hd)
    qpos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    out = A.attend(q, k, v, qpos, kpos, causal=False)
    return L.linear(params["o"], out.reshape(b, t, h * hd), th["o"])


def _apply_mamba_block(cfg, params, x, th):
    h = M2.mamba2_block(cfg, params["m"], L.rmsnorm(
        params["norm"], x, th["norm"], eps=cfg.norm_eps), subth(th, "m"))
    return x + h


def _apply_rwkv_block(cfg, params, x, th, *, tm_prev, cm_prev, state,
                      formulation="scan"):
    h = L.rmsnorm(params["norm1"], x, th["norm1"], eps=cfg.norm_eps)
    att, tm_new, s_new = R6.time_mix(cfg, params["tm"], h, subth(th, "tm"),
                                     x_prev=tm_prev, state=state,
                                     formulation=formulation)
    x = x + att
    h = L.rmsnorm(params["norm2"], x, th["norm2"], eps=cfg.norm_eps)
    ff, cm_new = R6.channel_mix(cfg, params["cm"], h, subth(th, "cm"),
                                x_prev=cm_prev)
    return x + ff, tm_new, cm_new, s_new


def _maybe_remat(fn, cfg):
    """Activation-checkpoint a per-layer apply (saves only block inputs)."""
    return jax.checkpoint(fn) if cfg.remat else fn


# ---------------------------------------------------------------------------
# The Model container.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    spec: dict
    layout: GroupLayout
    loss_fn: Callable  # (params, batch, thresholds) -> (B,) losses
    serve_step: Callable  # (params, cache, batch) -> (logits, cache)
    init_cache: Callable  # (batch_size, cache_len) -> cache pytree
    num_params: int

    def abstract_cache(self, batch_size: int, cache_len: int):
        shapes = jax.eval_shape(lambda: self.init_cache(batch_size, cache_len))
        return shapes


def _count(spec) -> int:
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, P):
            total += int(np.prod(node.shape, dtype=np.int64))
        else:
            for v in node.values():
                walk(v)

    walk(spec)
    return total


def build_model(cfg: ModelConfig, *, rwkv_formulation: str = "scan") -> Model:
    cfg.validate()
    if cfg.arch_type == "audio":
        return _build_encdec(cfg)
    return _build_decoder(cfg, rwkv_formulation)


# ---------------------------------------------------------------------------
# Decoder-only family (dense / moe / ssm / hybrid / vlm).
# ---------------------------------------------------------------------------


def _build_decoder(cfg: ModelConfig, rwkv_formulation: str) -> Model:
    pat = cfg.pattern()
    d, v = cfg.d_model, cfg.vocab_size

    spec: dict = {"embed": {"w": P((v, d), init="embed", dtype=cfg.dtype,
                                   tied=cfg.tie_embeddings)},
                  "final_norm": L.rmsnorm_spec(d, dtype=cfg.dtype)}
    if not cfg.tie_embeddings:
        spec["head"] = {"w": P((d, v), dtype=cfg.dtype)}

    kinds = sorted(set(pat))
    if cfg.shared_attention:
        # Zamba2: pure-mamba backbone + ONE shared attention block applied
        # before every `shared_every`-th layer inside the scan.
        n_backbone = cfg.num_layers
        n_sites = -(-n_backbone // cfg.shared_every)
        spec["backbone"] = {"norm": L.rmsnorm_spec(d, stack=(n_backbone,),
                                                   dtype=cfg.dtype),
                            "m": M2.mamba2_spec(cfg, stack=(n_backbone,))}
        spec["shared"] = _attn_block_spec(cfg, 0, moe_layer=False,
                                          sens=float(n_sites))
    else:
        if len(kinds) == 1:
            k = kinds[0]
            n = cfg.num_layers
            if k == "a":
                n_moe = n - cfg.first_k_dense if cfg.num_experts else 0
                n_dense = n - n_moe
                if n_dense:
                    spec["dense_blocks"] = _attn_block_spec(
                        cfg, n_dense, moe_layer=False)
                if n_moe:
                    spec["moe_blocks"] = _attn_block_spec(
                        cfg, n_moe, moe_layer=True)
            elif k == "m":
                spec["blocks"] = {"norm": L.rmsnorm_spec(
                    d, stack=(n,), dtype=cfg.dtype),
                    "m": M2.mamba2_spec(cfg, stack=(n,))}
            elif k == "r":
                spec["blocks"] = {"norm1": L.rmsnorm_spec(d, stack=(n,),
                                                          dtype=cfg.dtype),
                                  "norm2": L.rmsnorm_spec(d, stack=(n,),
                                                          dtype=cfg.dtype),
                                  **_rwkv_block_spec(cfg, n)}
            else:
                raise ValueError(k)
        else:
            raise NotImplementedError(
                "mixed patterns without shared_attention: use shared_attention"
                " or homogeneous patterns")

    if cfg.mtp_depth:
        spec["mtp"] = {"proj": L.linear_spec(2 * d, d, dtype=cfg.dtype),
                       "block": _attn_block_spec(cfg, 0, moe_layer=False),
                       "norm": L.rmsnorm_spec(d, dtype=cfg.dtype)}

    # ----- DP LoRA (the paper's large-model recipe): adapters on the
    # attention projections; everything else frozen. -----
    lora_on = cfg.lora_rank > 0
    lora_tree: dict = {}
    if lora_on:
        from repro.core.lora import lora_spec as _lspec
        h_, kv_, hd_ = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        for name in ("dense_blocks", "moe_blocks"):
            if name not in spec:
                continue
            n = spec[name]["attn_norm"]["s"].shape[0]
            if cfg.attention_kind == "mla":
                lora_tree[name] = {
                    "kv_b": _lspec(cfg.kv_lora_rank,
                                   h_ * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                                   cfg.lora_rank, stack=(n,), dtype=cfg.dtype),
                    "o": _lspec(h_ * cfg.v_head_dim, d, cfg.lora_rank,
                                stack=(n,), dtype=cfg.dtype),
                }
            else:
                lora_tree[name] = {
                    "qkv": _lspec(d, (h_ + 2 * kv_) * hd_, cfg.lora_rank,
                                  stack=(n,), dtype=cfg.dtype),
                    "o": _lspec(h_ * hd_, d, cfg.lora_rank, stack=(n,),
                                dtype=cfg.dtype),
                }
        spec["lora"] = lora_tree

    base_spec = {k: v for k, v in spec.items() if k != "lora"}
    base_layout = GroupLayout(base_spec)
    layout = GroupLayout({"lora": lora_tree}) if lora_on else base_layout

    # ---------------- shared helpers ----------------

    def positions_of(batch, bsz, t):
        if "positions" in batch:
            return batch["positions"]
        return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (bsz, t))

    window = cfg.sliding_window

    # ---------------- forward over blocks (training / prefill) -------------

    def backbone_fwd(params, x, th, positions, batch):
        bsz = x.shape[0]
        aux = jnp.zeros((bsz,), jnp.float32)

        if cfg.shared_attention:
            n = cfg.num_layers
            shared_every = cfg.shared_every
            bb_th = subth(th, "backbone")
            sh_th = subth(th, "shared")

            def body(carry, xs):
                h, i = carry
                bp, bt = xs

                def with_shared(hh):
                    out, _ = _apply_attn_block(
                        cfg, params["shared"], hh, sh_th, positions,
                        causal=True, window=window, moe_layer=False)
                    return out

                h = jax.lax.cond(i % shared_every == 0,
                                 _maybe_remat(with_shared, cfg),
                                 lambda hh: hh, h)
                h = _maybe_remat(
                    lambda hh, bp_, bt_: _apply_mamba_block(cfg, bp_, hh, bt_),
                    cfg)(h, bp, bt)
                return (h, i + 1), None

            (x, _), _ = jax.lax.scan(
                body, (x, jnp.int32(0)), (params["backbone"], bb_th))
            return x, aux

        if "blocks" in spec and "m" in spec["blocks"]:
            bb_th = subth(th, "blocks")

            def body(h, xs):
                bp, bt = xs
                f = _maybe_remat(
                    lambda hh, bp_, bt_: _apply_mamba_block(cfg, bp_, hh, bt_),
                    cfg)
                return f(h, bp, bt), None

            x, _ = jax.lax.scan(body, x, (params["blocks"], bb_th))
            return x, aux

        if "blocks" in spec and "tm" in spec["blocks"]:
            bb_th = subth(th, "blocks")
            nh = d // cfg.rwkv_head_dim
            hd = cfg.rwkv_head_dim

            def body(h, xs):
                bp, bt = xs

                def blk(hh, bp_, bt_):
                    tm_prev = jnp.zeros((bsz, 1, d), hh.dtype)
                    cm_prev = jnp.zeros((bsz, 1, d), hh.dtype)
                    s0 = jnp.zeros((bsz, nh, hd, hd), jnp.float32)
                    out, _, _, _ = _apply_rwkv_block(
                        cfg, bp_, hh, bt_, tm_prev=tm_prev, cm_prev=cm_prev,
                        state=s0, formulation=rwkv_formulation)
                    return out

                return _maybe_remat(blk, cfg)(h, bp, bt), None

            x, _ = jax.lax.scan(body, x, (params["blocks"], bb_th))
            return x, aux

        # attention stacks (dense and/or moe runs)
        for name, moe_layer in (("dense_blocks", False), ("moe_blocks", True)):
            if name not in spec or name == "lora":
                continue
            run_th = subth(th, name)
            if lora_on:
                lora_run_th = subth(th, "lora/" + name)

                def body(carry, xs, moe_layer=moe_layer):
                    h, aux_c = carry
                    bp, bt, lp, lt = xs

                    def blk(hh, bp_, bt_, lp_, lt_):
                        return _apply_attn_block(
                            cfg, bp_, hh, bt_, positions, causal=True,
                            window=window, moe_layer=moe_layer,
                            lora=lp_, lora_th=lt_)

                    h, aux_l = _maybe_remat(blk, cfg)(h, bp, bt, lp, lt)
                    return (h, aux_c + aux_l), None

                (x, aux), _ = jax.lax.scan(
                    body, (x, aux),
                    (params[name], run_th, params["lora"][name],
                     lora_run_th))
            else:
                def body(carry, xs, moe_layer=moe_layer):
                    h, aux_c = carry
                    bp, bt = xs

                    def blk(hh, bp_, bt_):
                        return _apply_attn_block(
                            cfg, bp_, hh, bt_, positions, causal=True,
                            window=window, moe_layer=moe_layer)

                    h, aux_l = _maybe_remat(blk, cfg)(h, bp, bt)
                    return (h, aux_c + aux_l), None

                (x, aux), _ = jax.lax.scan(body, (x, aux),
                                           (params[name], run_th))
        return x, aux

    # ---------------- loss ----------------

    def loss_fn(params, batch, th):
        tokens = batch["tokens"]  # (B, T)
        bsz, t = tokens.shape
        if lora_on:
            # base groups get +inf (frozen, unused grads DCE'd); real
            # thresholds arrive only for the lora/... groups
            th = {**base_layout.pack_value(jnp.inf, bsz), **th}
        x = _embed(cfg, params, tokens, th)
        tv = 0
        if "vision_embeds" in batch:  # VLM: prepend stub patch embeddings
            ve = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
            tv = ve.shape[1]
        if cfg.m_rope:
            if "positions3_full" in batch:
                # batch-major (B, 3, Tv+T) -> (3, B, Tv+T)
                positions = jnp.moveaxis(batch["positions3_full"], 1, 0)
            elif "positions3" in batch:
                positions = jnp.moveaxis(batch["positions3"], 1, 0)
            else:
                p1 = positions_of(batch, bsz, t + tv)
                positions = jnp.broadcast_to(p1[None], (3,) + p1.shape)
        else:
            positions = positions_of(batch, bsz, t + tv)

        if cfg.m_rope:
            x, aux = _mrope_backbone(cfg, spec, params, x, th, positions,
                                     backbone_fwd)
        else:
            x, aux = backbone_fwd(params, x, th, positions, batch)

        if tv:
            x = x[:, tv:]
        x = L.rmsnorm(params["final_norm"], x, th["final_norm"],
                      eps=cfg.norm_eps)
        logits = _lm_head(cfg, params, x, th, tokens)  # (B, T, V)
        targets = batch["targets"]  # (B, T) with -1 = ignore
        ce = _per_example_ce(logits, targets)
        if cfg.mtp_depth:
            ce = ce + 0.3 * _mtp_loss(cfg, params, x, th, batch, positions
                                      if not cfg.m_rope else None)
        return ce + aux

    def _mtp_loss(cfg, params, x, th, batch, positions):
        # DeepSeek-V3 MTP: combine h_t with embed(token_{t+1}) to predict
        # token_{t+2} through one extra block sharing the main head.
        tokens = batch["tokens"]
        bsz, t = tokens.shape
        nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
        e = _embed(cfg, params, nxt, th)
        h = L.linear(params["mtp"]["proj"],
                     jnp.concatenate([x, e], axis=-1),
                     th["mtp/proj"])
        pos = positions if positions is not None else jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None], (bsz, t))
        h, _ = _apply_attn_block(cfg, params["mtp"]["block"], h,
                                 subth(th, "mtp/block"), pos, causal=True,
                                 moe_layer=False)
        h = L.rmsnorm(params["mtp"]["norm"], h, th["mtp/norm"],
                      eps=cfg.norm_eps)
        logits = _lm_head(cfg, params, h, th, tokens)
        tgt = batch["targets"]
        tgt2 = jnp.concatenate(
            [tgt[:, 2:], jnp.full((bsz, 2), -1, tgt.dtype)], axis=1)
        return _per_example_ce(logits, tgt2)

    # ---------------- decode ----------------

    serve_step, init_cache, init_paged_cache = _make_decoder_serve(
        cfg, base_spec, base_layout)

    def prefill_step(params, batch):
        """Full-sequence forward -> last-position logits (B, V): the
        inference-prefill workload (prefill_32k)."""
        tokens = batch["tokens"]
        bsz, t = tokens.shape
        th = base_layout.pack_value(jnp.inf, bsz)
        if lora_on:
            th = {**th, **layout.pack_value(jnp.inf, bsz)}
        x = _embed(cfg, params, tokens, th)
        tv = 0
        if "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
            tv = ve.shape[1]
        if cfg.m_rope:
            if "positions3_full" in batch:
                positions = jnp.moveaxis(batch["positions3_full"], 1, 0)
            else:
                p1 = positions_of(batch, bsz, t + tv)
                positions = jnp.broadcast_to(p1[None], (3,) + p1.shape)
            x, _ = _mrope_backbone(cfg, spec, params, x, th, positions,
                                   backbone_fwd)
        else:
            positions = positions_of(batch, bsz, t + tv)
            x, _ = backbone_fwd(params, x, th, positions, batch)
        x = x[:, -1:]
        x = L.rmsnorm(params["final_norm"], x, th["final_norm"],
                      eps=cfg.norm_eps)
        logits = _lm_head(cfg, params, x, th, tokens[:, -1:])
        return logits[:, 0]

    m = Model(cfg=cfg, spec=spec, layout=layout, loss_fn=loss_fn,
              serve_step=serve_step, init_cache=init_cache,
              num_params=_count(spec))
    m.prefill_step = prefill_step  # type: ignore[attr-defined]
    m.init_paged_cache = init_paged_cache  # type: ignore[attr-defined]
    m.cache_slot_axes = cache_slot_axes  # type: ignore[attr-defined]
    m.base_layout = base_layout  # type: ignore[attr-defined]
    m.trainable_key = "lora" if lora_on else None  # type: ignore
    m.dp_spec = {"lora": lora_tree} if lora_on else spec  # type: ignore
    return m


def _mrope_backbone(cfg, spec, params, x, th, positions3, backbone_fwd):
    """Qwen2-VL: swap plain rope for M-RoPE by monkey-free config plumbing:
    attention reads (B, T) positions normally; for M-RoPE we pass the 3-D
    streams through a closure-level override."""
    # We implement M-RoPE by rotating q/k inside gqa via positions packed as
    # complex trick: simplest correct route — temporarily replace apply_rope.
    # Instead we run the standard stack but with positions = temporal stream,
    # then add the (h, w) rotations via the sections: implemented directly in
    # layers.apply_m_rope by calling the stack with a wrapped config.
    return _backbone_mrope_impl(cfg, spec, params, x, th, positions3)


def _backbone_mrope_impl(cfg, spec, params, x, th, positions3):
    bsz = x.shape[0]
    aux = jnp.zeros((bsz,), jnp.float32)
    run_th = subth(th, "dense_blocks")
    sections = cfg.m_rope_sections
    lora_on = "lora" in params

    if lora_on:
        lora_run_th = subth(th, "lora/dense_blocks")

        def body(carry, xs):
            h, aux_c = carry
            bp, bt, lp, lt = xs

            def blk(hh, bp_, bt_, lp_, lt_):
                hn = L.rmsnorm(bp_["attn_norm"], hh, bt_["attn_norm"],
                               eps=cfg.norm_eps)
                att = _mrope_attention(cfg, bp_["attn"], hn,
                                       subth(bt_, "attn"), positions3,
                                       sections, lora=lp_, lora_th=lt_)
                hh = hh + att
                hn = L.rmsnorm(bp_["mlp_norm"], hh, bt_["mlp_norm"],
                               eps=cfg.norm_eps)
                y = L.swiglu(bp_["mlp"], hn, subth(bt_, "mlp"), f=cfg.d_ff)
                return hh + y

            h = _maybe_remat(blk, cfg)(h, bp, bt, lp, lt)
            return (h, aux_c), None

        (x, aux), _ = jax.lax.scan(
            body, (x, aux), (params["dense_blocks"], run_th,
                             params["lora"]["dense_blocks"], lora_run_th))
        return x, aux

    def body(carry, xs):
        h, aux_c = carry
        bp, bt = xs

        def blk(hh, bp_, bt_):
            hn = L.rmsnorm(bp_["attn_norm"], hh, bt_["attn_norm"],
                           eps=cfg.norm_eps)
            att = _mrope_attention(cfg, bp_["attn"], hn, subth(bt_, "attn"),
                                   positions3, sections)
            hh = hh + att
            hn = L.rmsnorm(bp_["mlp_norm"], hh, bt_["mlp_norm"],
                           eps=cfg.norm_eps)
            y = L.swiglu(bp_["mlp"], hn, subth(bt_, "mlp"), f=cfg.d_ff)
            return hh + y

        h = _maybe_remat(blk, cfg)(h, bp, bt)
        return (h, aux_c), None

    (x, aux), _ = jax.lax.scan(body, (x, aux),
                               (params["dense_blocks"], run_th))
    return x, aux


def _mrope_attention(cfg, params, x, th, positions3, sections, *,
                     lora=None, lora_th=None):
    qkv = A._proj(cfg, params["qkv"], x, th.get("qkv"),
                  lora=lora and lora.get("qkv"),
                  lora_th=lora_th and lora_th.get("qkv"),
                  alpha=cfg.lora_alpha)
    q, k, v = A._split_qkv(cfg, qkv)
    q = L.apply_m_rope(q, positions3, cfg.rope_theta, sections)
    k = L.apply_m_rope(k, positions3, cfg.rope_theta, sections)
    b, t = x.shape[0], x.shape[1]
    pos = positions3[0]  # temporal stream drives causal masking
    out = A.attend(q, k, v, pos, pos, causal=True,
                   window=cfg.sliding_window)
    out = out.reshape(b, t, -1)
    return A._proj(cfg, params["o"], out, th.get("o"),
                   lora=lora and lora.get("o"),
                   lora_th=lora_th and lora_th.get("o"),
                   alpha=cfg.lora_alpha)


def _per_example_ce(logits, targets):
    """(B,) mean CE over valid (target >= 0) positions."""
    valid = targets >= 0
    tsafe = jnp.maximum(targets, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tok_ll = jnp.take_along_axis(
        logits.astype(jnp.float32), tsafe[..., None], axis=-1)[..., 0]
    ce = (lse - tok_ll) * valid
    return jnp.sum(ce, axis=-1) / jnp.maximum(jnp.sum(valid, axis=-1), 1)


# ---------------------------------------------------------------------------
# Decode (serve_step) for the decoder family.
# ---------------------------------------------------------------------------

# Which axis of each decode-cache tensor indexes the slot (the engine's
# batch row). Explicit, per cache family — the old engine hardcoded
# `0 if k == "pos" else 1`, which happened to hold for every family but
# silently relied on it; paged pools break the pattern (they are SHARED by
# all slots, axis None) and a wrong axis in the recycle program would
# cross-contaminate slots without any test tripping locally.
_SLOT_AXIS_BY_KEY = {
    "pos": 0, "pt": 0,
    "conv": 1, "ssm": 1,                       # mamba2 recurrent state
    "tm_prev": 1, "cm_prev": 1, "wkv": 1,      # rwkv6 recurrent state
    "shared_k": 1, "shared_v": 1,              # zamba2 shared-attention KV
    "dec_k": 1, "dec_v": 1, "cross_k": 1, "cross_v": 1,  # enc-dec
}


def cache_slot_axes(cache) -> dict:
    """Map every decode-cache key to its slot axis (None = slot-free).

    Slot-free tensors (physical page pools) must pass through a slot
    recycle untouched: zeroing them would destroy other slots' pages.
    Unknown keys raise — a new cache family must declare its layout here
    before the engine will recycle it."""
    out = {}
    for k in cache:
        if k in _SLOT_AXIS_BY_KEY:
            out[k] = _SLOT_AXIS_BY_KEY[k]
        elif k.endswith(("_kpool", "_vpool", "_latpool")):
            out[k] = None
        elif k.endswith(("_k", "_v", "_ckv", "_krope")):
            out[k] = 1  # per-stack attention caches: (n, B, S, ...)
        else:
            raise KeyError(
                f"decode-cache key {k!r} has no slot-axis entry; add it to "
                "transformer._SLOT_AXIS_BY_KEY (or a suffix rule) so the "
                "engine's recycle program knows which axis to mask")
    return out


def _make_decoder_serve(cfg: ModelConfig, spec, layout):
    d = cfg.d_model
    window = cfg.sliding_window
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads

    def init_cache(batch_size: int, cache_len: int):
        b = batch_size
        cap = min(window, cache_len) if window else cache_len
        cache = {"pos": jnp.zeros((b,), jnp.int32)}
        if cfg.shared_attention:
            n = cfg.num_layers
            n_sites = -(-n // cfg.shared_every)
            d_in, nh, nst, p = M2.dims(cfg)
            cache["conv"] = jnp.zeros(
                (n, b, cfg.ssm_conv_kernel - 1, d_in + 2 * nst), cfg.dtype)
            cache["ssm"] = jnp.zeros((n, b, nh, p, nst), jnp.float32)
            cache["shared_k"] = jnp.zeros((n_sites, b, cap, kvh, hd), cfg.dtype)
            cache["shared_v"] = jnp.zeros((n_sites, b, cap, kvh, hd), cfg.dtype)
            return cache
        if "blocks" in spec and "m" in spec["blocks"]:
            n = cfg.num_layers
            d_in, nh, nst, p = M2.dims(cfg)
            cache["conv"] = jnp.zeros(
                (n, b, cfg.ssm_conv_kernel - 1, d_in + 2 * nst), cfg.dtype)
            cache["ssm"] = jnp.zeros((n, b, nh, p, nst), jnp.float32)
            return cache
        if "blocks" in spec and "tm" in spec["blocks"]:
            n = cfg.num_layers
            nh = d // cfg.rwkv_head_dim
            rhd = cfg.rwkv_head_dim
            cache["tm_prev"] = jnp.zeros((n, b, 1, d), cfg.dtype)
            cache["cm_prev"] = jnp.zeros((n, b, 1, d), cfg.dtype)
            cache["wkv"] = jnp.zeros((n, b, nh, rhd, rhd), jnp.float32)
            return cache
        # attention stacks
        for name in ("dense_blocks", "moe_blocks"):
            if name not in spec:
                continue
            n = spec[name]["attn_norm"]["s"].shape[0]
            if cfg.attention_kind == "mla":
                cache[f"{name}_ckv"] = jnp.zeros(
                    (n, b, cache_len, cfg.kv_lora_rank), cfg.dtype)
                cache[f"{name}_krope"] = jnp.zeros(
                    (n, b, cache_len, cfg.qk_rope_head_dim), cfg.dtype)
            else:
                cache[f"{name}_k"] = jnp.zeros((n, b, cap, kvh, hd), cfg.dtype)
                cache[f"{name}_v"] = jnp.zeros((n, b, cap, kvh, hd), cfg.dtype)
        return cache

    # paging applies to full (position-bounded) attention caches only:
    # ring windows and recurrent state are O(W)/O(1) per slot and have
    # nothing to fragment, so those families keep the contiguous plane
    paged_ok = (not cfg.shared_attention
                and not ("blocks" in spec and ("m" in spec["blocks"]
                                               or "tm" in spec["blocks"]))
                and (cfg.attention_kind == "mla" or window is None))

    def init_paged_cache(batch_size: int, cache_len: int, *, num_pages: int,
                         page_len: int):
        """Paged decode cache: per-slot int32 page tables plus physical
        page pools shared by every slot. Pools carry `num_pages + 1`
        pages — the extra LAST page is the trash page absorbing writes
        from inactive rows (attention._paged_write). Tables start fully
        trash-mapped; the engine overwrites them at admission."""
        if not paged_ok:
            raise ValueError(
                "paged cache is only supported for full-attention decoder "
                "stacks (ring-window / recurrent families bypass paging)")
        b = batch_size
        p_tab = -(-cache_len // page_len)
        cache = {"pos": jnp.zeros((b,), jnp.int32),
                 "pt": jnp.full((b, p_tab), num_pages, jnp.int32)}
        for name in ("dense_blocks", "moe_blocks"):
            if name not in spec:
                continue
            n = spec[name]["attn_norm"]["s"].shape[0]
            if cfg.attention_kind == "mla":
                cache[f"{name}_latpool"] = jnp.zeros(
                    (n, num_pages + 1, page_len,
                     cfg.kv_lora_rank + cfg.qk_rope_head_dim), cfg.dtype)
            else:
                cache[f"{name}_kpool"] = jnp.zeros(
                    (n, num_pages + 1, page_len, kvh, hd), cfg.dtype)
                cache[f"{name}_vpool"] = jnp.zeros(
                    (n, num_pages + 1, page_len, kvh, hd), cfg.dtype)
        return cache

    def serve_step(params, cache, batch):
        """batch: {'token': (B, 1) int32, optional 'active': (B,) bool,
        optional 'tenant': (B,) int32}; returns (logits (B, V), cache).

        `active` is the slot-pool write/retire hook (launch.engine): rows
        with `active=False` come back with a bit-identical cache slot and
        an unchanged position — their logits are garbage and must be
        ignored by the caller. Omitting the key advances every row (the
        historical single-batch path, no masking cost).

        Multi-tenant serving: when `params` carries a 'lora_stack' subtree
        (tenant-stacked adapters, leaves (n, T, ...) — see
        core.lora.stacked_adapter_zeros) AND the batch carries 'tenant'
        (per-row int32 adapter-slot ids), every attention projection adds
        its row's tenant adapter delta (attention.gqa_decode /
        mla_decode and their paged variants). Both are data: admitting a
        tenant or hot-swapping an adapter never retraces this program."""
        token = batch["token"]
        active = batch.get("active")
        lstack = params.get("lora_stack")
        tenant = batch.get("tenant")
        if (lstack is None) != (tenant is None):
            raise ValueError(
                "multi-tenant serve_step needs BOTH params['lora_stack'] "
                "and batch['tenant'] (or neither)")
        b = token.shape[0]
        pos = cache["pos"]
        th = layout.pack_value(jnp.inf, b)
        x = _embed(cfg, params, token, th)
        new_cache = dict(cache)

        if cfg.shared_attention:
            shared_every = cfg.shared_every
            inf_b = jnp.full((b,), jnp.inf)

            def subth_bb(prefix):
                names = [k for k in layout._by_name
                         if k.startswith(f"backbone/{prefix}/")]
                return {k[len(f"backbone/{prefix}/"):]: inf_b for k in names}

            def mk_shared(sub):
                names = [k for k in layout._by_name
                         if k.startswith(f"shared/{sub}/")]
                return {k[len(f"shared/{sub}/"):]: inf_b for k in names}

            def body(carry, xs):
                h, i, sk_all, sv_all = carry
                bp, conv_s, ssm_s = xs
                site = i // shared_every

                def with_shared(args):
                    hh, sk_all, sv_all = args
                    hn = L.rmsnorm(params["shared"]["attn_norm"], hh,
                                   inf_b, eps=cfg.norm_eps)
                    ck = jax.lax.dynamic_index_in_dim(sk_all, site,
                                                      keepdims=False)
                    cv = jax.lax.dynamic_index_in_dim(sv_all, site,
                                                      keepdims=False)
                    att, ck, cv = A.gqa_decode(
                        cfg, params["shared"]["attn"], hn,
                        mk_shared("attn"), ck, cv, pos, window=window,
                        active=active)
                    sk_all = jax.lax.dynamic_update_index_in_dim(
                        sk_all, ck, site, axis=0)
                    sv_all = jax.lax.dynamic_update_index_in_dim(
                        sv_all, cv, site, axis=0)
                    hh = hh + att
                    hn = L.rmsnorm(params["shared"]["mlp_norm"], hh,
                                   inf_b, eps=cfg.norm_eps)
                    y = L.swiglu(params["shared"]["mlp"], hn,
                                 mk_shared("mlp"), f=cfg.d_ff)
                    return hh + y, sk_all, sv_all

                h, sk_all, sv_all = jax.lax.cond(
                    i % shared_every == 0, with_shared,
                    lambda a: a, (h, sk_all, sv_all))
                hn = L.rmsnorm(bp["norm"], h, inf_b, eps=cfg.norm_eps)
                out, conv_n, ssm_n = M2.mamba2_decode(
                    cfg, bp["m"], hn, subth_bb("m"), conv_s, ssm_s)
                conv_n = A.masked_state(active, conv_n, conv_s)
                ssm_n = A.masked_state(active, ssm_n, ssm_s)
                return (h + out, i + 1, sk_all, sv_all), (conv_n, ssm_n)

            (x, _, sk_all, sv_all), (conv_n, ssm_n) = jax.lax.scan(
                body, (x, jnp.int32(0), cache["shared_k"], cache["shared_v"]),
                (params["backbone"], cache["conv"], cache["ssm"]))
            new_cache["conv"], new_cache["ssm"] = conv_n, ssm_n
            new_cache["shared_k"], new_cache["shared_v"] = sk_all, sv_all
        elif "conv" in cache:  # pure mamba
            inf_b = jnp.full((b,), jnp.inf)

            def body(h, xs):
                bp, conv_s, ssm_s = xs
                names = [k for k in layout._by_name
                         if k.startswith("blocks/m/")]
                tm = {k[len("blocks/m/"):]: inf_b for k in names}
                hn = L.rmsnorm(bp["norm"], h, inf_b, eps=cfg.norm_eps)
                out, conv_n, ssm_n = M2.mamba2_decode(cfg, bp["m"], hn, tm,
                                                      conv_s, ssm_s)
                conv_n = A.masked_state(active, conv_n, conv_s)
                ssm_n = A.masked_state(active, ssm_n, ssm_s)
                return h + out, (conv_n, ssm_n)

            x, (conv_n, ssm_n) = jax.lax.scan(
                body, x, (params["blocks"], cache["conv"], cache["ssm"]))
            new_cache["conv"], new_cache["ssm"] = conv_n, ssm_n
        elif "wkv" in cache:  # rwkv
            inf_b = jnp.full((b,), jnp.inf)

            def mk(prefix):
                names = [k for k in layout._by_name
                         if k.startswith(prefix + "/")]
                return {k[len(prefix) + 1:]: inf_b for k in names}

            def body(h, xs):
                bp, tm_p, cm_p, st = xs
                hn = L.rmsnorm(bp["norm1"], h, inf_b, eps=cfg.norm_eps)
                att, tm_n, st_n = R6.time_mix_decode(
                    cfg, bp["tm"], hn, mk("blocks/tm"), x_prev=tm_p, state=st)
                h = h + att
                hn = L.rmsnorm(bp["norm2"], h, inf_b, eps=cfg.norm_eps)
                ff, cm_n = R6.channel_mix_decode(cfg, bp["cm"], hn,
                                                 mk("blocks/cm"), x_prev=cm_p)
                tm_n = A.masked_state(active, tm_n, tm_p)
                cm_n = A.masked_state(active, cm_n, cm_p)
                st_n = A.masked_state(active, st_n, st)
                return h + ff, (tm_n, cm_n, st_n)

            x, (tm_n, cm_n, st_n) = jax.lax.scan(
                body, x, (params["blocks"], cache["tm_prev"],
                          cache["cm_prev"], cache["wkv"]))
            new_cache["tm_prev"], new_cache["cm_prev"] = tm_n, cm_n
            new_cache["wkv"] = st_n
        else:  # attention stacks
            for name in ("dense_blocks", "moe_blocks"):
                if name not in spec:
                    continue
                moe_layer = name == "moe_blocks"
                run_prefix = name
                inf_b = jnp.full((b,), jnp.inf)

                def mk(sub):
                    names = [k for k in layout._by_name
                             if k.startswith(f"{run_prefix}/{sub}/")]
                    return {k[len(f"{run_prefix}/{sub}/"):]: inf_b
                            for k in names}

                # tenant-stacked adapters ride the layer scan as one more
                # xs leaf (None when single-tenant: an empty pytree)
                ls = lstack[name] if lstack is not None else None

                if cfg.attention_kind == "mla" and "pt" in cache:
                    def body(h, xs, mk=mk, moe_layer=moe_layer):
                        bp, lp, latpool = xs
                        hn = L.rmsnorm(bp["attn_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        att, lat_n = A.mla_decode_paged(
                            cfg, bp["attn"], hn, mk("attn"), latpool,
                            cache["pt"], pos, active=active, lora=lp,
                            tenant=tenant)
                        h = h + _branch(cfg, att)
                        hn = L.rmsnorm(bp["mlp_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        if moe_layer:
                            moe_fn = (MOE.moe_block_grouped
                                      if cfg.moe_dispatch == "grouped"
                                      else MOE.moe_block)
                            y, _ = moe_fn(cfg, bp["moe"], hn, mk("moe"))
                        else:
                            y = L.swiglu(bp["mlp"], hn, mk("mlp"),
                                         f=cfg.d_ff)
                        return h + _branch(cfg, y), lat_n

                    x, lat_n = jax.lax.scan(
                        body, x, (params[name], ls,
                                  cache[f"{name}_latpool"]))
                    new_cache[f"{name}_latpool"] = lat_n
                elif cfg.attention_kind == "mla":
                    def body(h, xs, mk=mk, moe_layer=moe_layer):
                        bp, lp, ckv, krope = xs
                        hn = L.rmsnorm(bp["attn_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        att, ckv_n, krope_n = A.mla_decode(
                            cfg, bp["attn"], hn, mk("attn"), ckv, krope, pos,
                            active=active, lora=lp, tenant=tenant)
                        h = h + _branch(cfg, att)
                        hn = L.rmsnorm(bp["mlp_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        if moe_layer:
                            moe_fn = (MOE.moe_block_grouped
                                      if cfg.moe_dispatch == "grouped"
                                      else MOE.moe_block)
                            y, _ = moe_fn(cfg, bp["moe"], hn, mk("moe"))
                        else:
                            y = L.swiglu(bp["mlp"], hn, mk("mlp"),
                                         f=cfg.d_ff)
                        return h + _branch(cfg, y), (ckv_n, krope_n)

                    x, (ckv_n, kr_n) = jax.lax.scan(
                        body, x, (params[name], ls, cache[f"{name}_ckv"],
                                  cache[f"{name}_krope"]))
                    new_cache[f"{name}_ckv"] = ckv_n
                    new_cache[f"{name}_krope"] = kr_n
                elif "pt" in cache:
                    def body(h, xs, mk=mk, moe_layer=moe_layer):
                        bp, lp, kpool, vpool = xs
                        hn = L.rmsnorm(bp["attn_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        att, kp_n, vp_n = A.gqa_decode_paged(
                            cfg, bp["attn"], hn, mk("attn"), kpool, vpool,
                            cache["pt"], pos, active=active, lora=lp,
                            tenant=tenant)
                        h = h + _branch(cfg, att)
                        hn = L.rmsnorm(bp["mlp_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        if moe_layer:
                            moe_fn = (MOE.moe_block_grouped
                                      if cfg.moe_dispatch == "grouped"
                                      else MOE.moe_block)
                            y, _ = moe_fn(cfg, bp["moe"], hn, mk("moe"))
                        else:
                            y = L.swiglu(bp["mlp"], hn, mk("mlp"),
                                         f=cfg.d_ff)
                        return h + _branch(cfg, y), (kp_n, vp_n)

                    x, (kp_n, vp_n) = jax.lax.scan(
                        body, x, (params[name], ls, cache[f"{name}_kpool"],
                                  cache[f"{name}_vpool"]))
                    new_cache[f"{name}_kpool"] = kp_n
                    new_cache[f"{name}_vpool"] = vp_n
                else:
                    def body(h, xs, mk=mk, moe_layer=moe_layer):
                        bp, lp, ck, cv = xs
                        hn = L.rmsnorm(bp["attn_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        att, ck_n, cv_n = A.gqa_decode(
                            cfg, bp["attn"], hn, mk("attn"), ck, cv, pos,
                            window=window, active=active, lora=lp,
                            tenant=tenant)
                        h = h + _branch(cfg, att)
                        hn = L.rmsnorm(bp["mlp_norm"], h, inf_b,
                                       eps=cfg.norm_eps)
                        if moe_layer:
                            moe_fn = (MOE.moe_block_grouped
                                      if cfg.moe_dispatch == "grouped"
                                      else MOE.moe_block)
                            y, _ = moe_fn(cfg, bp["moe"], hn, mk("moe"))
                        else:
                            y = L.swiglu(bp["mlp"], hn, mk("mlp"),
                                         f=cfg.d_ff)
                        return h + _branch(cfg, y), (ck_n, cv_n)

                    x, (ck_n, cv_n) = jax.lax.scan(
                        body, x, (params[name], ls, cache[f"{name}_k"],
                                  cache[f"{name}_v"]))
                    new_cache[f"{name}_k"] = ck_n
                    new_cache[f"{name}_v"] = cv_n

        x = L.rmsnorm(params["final_norm"], x, th["final_norm"],
                      eps=cfg.norm_eps)
        logits = _lm_head(cfg, params, x, th, token)
        new_cache["pos"] = (pos + 1 if active is None
                            else pos + active.astype(jnp.int32))
        return logits[:, 0], new_cache

    return serve_step, init_cache, (init_paged_cache if paged_ok else None)


# ---------------------------------------------------------------------------
# Encoder-decoder (Whisper backbone; conv/mel frontend stubbed per task spec:
# `frames` are precomputed frame embeddings of shape (B, S_enc, D)).
# ---------------------------------------------------------------------------


def _build_encdec(cfg: ModelConfig) -> Model:
    d, v = cfg.d_model, cfg.vocab_size
    n_enc, n_dec = cfg.encoder_layers, cfg.num_layers

    spec = {
        "embed": {"w": P((v, d), init="embed", dtype=cfg.dtype)},
        "enc_blocks": _attn_block_spec(cfg, n_enc, moe_layer=False),
        "enc_norm": L.rmsnorm_spec(d, dtype=cfg.dtype),
        "dec_blocks": _attn_block_spec(cfg, n_dec, moe_layer=False,
                                       cross=True),
        "final_norm": L.rmsnorm_spec(d, dtype=cfg.dtype),
        "head": {"w": P((d, v), dtype=cfg.dtype)},
    }
    layout = GroupLayout(spec)
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def encode(params, frames, th):
        s = frames.shape[1]
        x = frames.astype(cfg.dtype) + L.sinusoidal_positions(s, d).astype(
            cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (frames.shape[0], s))
        run_th = subth(th, "enc_blocks")

        def body(h, xs):
            bp, bt = xs

            def blk(hh, bp_, bt_):
                out, _ = _apply_attn_block(cfg, bp_, hh, bt_, positions,
                                           causal=False, moe_layer=False)
                return out

            return _maybe_remat(blk, cfg)(h, bp, bt), None

        x, _ = jax.lax.scan(body, x, (params["enc_blocks"], run_th))
        return L.rmsnorm(params["enc_norm"], x, th["enc_norm"],
                         eps=cfg.norm_eps)

    def loss_fn(params, batch, th):
        frames, tokens = batch["frames"], batch["tokens"]
        bsz, t = tokens.shape
        enc_out = encode(params, frames, th)
        x = dpl.dp_embed(params["embed"]["w"], tokens, th["embed"])
        x = x + L.sinusoidal_positions(t, d).astype(x.dtype)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                                     (bsz, t))
        run_th = subth(th, "dec_blocks")

        def body(h, xs):
            bp, bt = xs

            def blk(hh, bp_, bt_, enc_):
                out, _ = _apply_attn_block(cfg, bp_, hh, bt_, positions,
                                           causal=True, enc_out=enc_,
                                           moe_layer=False)
                return out

            return _maybe_remat(blk, cfg)(h, bp, bt, enc_out), None

        x, _ = jax.lax.scan(body, x, (params["dec_blocks"], run_th))
        x = L.rmsnorm(params["final_norm"], x, th["final_norm"],
                      eps=cfg.norm_eps)
        logits = dpl.dp_linear(params["head"]["w"], None, x, th["head"])
        return _per_example_ce(logits, batch["targets"])

    def init_cache(batch_size: int, cache_len: int):
        b = batch_size
        return {
            "pos": jnp.zeros((b,), jnp.int32),
            "dec_k": jnp.zeros((n_dec, b, cache_len, kvh, hd), cfg.dtype),
            "dec_v": jnp.zeros((n_dec, b, cache_len, kvh, hd), cfg.dtype),
            "cross_k": jnp.zeros((n_dec, b, cfg.encoder_seq_len, kvh, hd),
                                 cfg.dtype),
            "cross_v": jnp.zeros((n_dec, b, cfg.encoder_seq_len, kvh, hd),
                                 cfg.dtype),
        }

    def prefill_cross(params, frames, batch_size: int, cache_len: int):
        """Run the encoder and fill the cross-attention KV cache."""
        th = layout.pack_value(jnp.inf, batch_size)
        enc_out = encode(params, frames, th)
        cache = init_cache(batch_size, cache_len)
        inf_b = jnp.full((batch_size,), jnp.inf)
        s = enc_out.shape[1]

        def body(carry, bp):
            kv = L.linear(bp["cross"]["kv"], enc_out, inf_b)
            k = kv[..., : kvh * hd].reshape(batch_size, s, kvh, hd)
            vv = kv[..., kvh * hd:].reshape(batch_size, s, kvh, hd)
            return carry, (k, vv)

        _, (ck, cv) = jax.lax.scan(body, 0, params["dec_blocks"])
        cache["cross_k"], cache["cross_v"] = ck, cv
        return cache

    def serve_step(params, cache, batch):
        token = batch["token"]
        active = batch.get("active")  # (B,) slot write/retire mask
        b = token.shape[0]
        pos = cache["pos"]
        inf_b = jnp.full((b,), jnp.inf)
        th = layout.pack_value(jnp.inf, b)
        x = dpl.dp_embed(params["embed"]["w"], token, th["embed"])
        postab = L.sinusoidal_positions(cfg.max_seq_len, d).astype(x.dtype)
        x = x + postab[jnp.minimum(pos, cfg.max_seq_len - 1)][:, None, :]

        def mk(sub):
            names = [k for k in layout._by_name
                     if k.startswith(f"dec_blocks/{sub}/")]
            return {k[len(f"dec_blocks/{sub}/"):]: inf_b for k in names}

        def body(h, xs):
            bp, ck, cv, xk, xv = xs
            hn = L.rmsnorm(bp["attn_norm"], h, inf_b, eps=cfg.norm_eps)
            att, ck_n, cv_n = A.gqa_decode(cfg, bp["attn"], hn, mk("attn"),
                                           ck, cv, pos, active=active)
            h = h + att
            # cross attention over the precomputed encoder KV
            hn = L.rmsnorm(bp["cross_norm"], h, inf_b, eps=cfg.norm_eps)
            q = L.linear(bp["cross"]["qkv"], hn, inf_b).reshape(
                b, 1, cfg.num_heads, hd)
            qpos = pos[:, None]
            kpos = jnp.broadcast_to(
                jnp.arange(xk.shape[1], dtype=jnp.int32)[None],
                (b, xk.shape[1]))
            ca = A.attend(q, xk, xv, qpos, kpos, causal=False)
            ca = L.linear(bp["cross"]["o"],
                          ca.reshape(b, 1, cfg.num_heads * hd), inf_b)
            h = h + ca
            hn = L.rmsnorm(bp["mlp_norm"], h, inf_b, eps=cfg.norm_eps)
            y = L.swiglu(bp["mlp"], hn, mk("mlp"), f=cfg.d_ff)
            return h + y, (ck_n, cv_n)

        x, (ck_n, cv_n) = jax.lax.scan(
            body, x, (params["dec_blocks"], cache["dec_k"], cache["dec_v"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache)
        new_cache["dec_k"], new_cache["dec_v"] = ck_n, cv_n
        new_cache["pos"] = (pos + 1 if active is None
                            else pos + active.astype(jnp.int32))
        x = L.rmsnorm(params["final_norm"], x, th["final_norm"],
                      eps=cfg.norm_eps)
        logits = dpl.dp_linear(params["head"]["w"], None, x, th["head"])
        return logits[:, 0], new_cache

    def prefill_step(params, batch):
        frames, tokens = batch["frames"], batch["tokens"]
        bsz, t = tokens.shape
        th = layout.pack_value(jnp.inf, bsz)
        enc_out = encode(params, frames, th)
        x = dpl.dp_embed(params["embed"]["w"], tokens, th["embed"])
        x = x + L.sinusoidal_positions(t, d).astype(x.dtype)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                                     (bsz, t))
        run_th = subth(th, "dec_blocks")

        def body(h, xs):
            bp, bt = xs

            def blk(hh, bp_, bt_, enc_):
                out, _ = _apply_attn_block(cfg, bp_, hh, bt_, positions,
                                           causal=True, enc_out=enc_,
                                           moe_layer=False)
                return out

            return _maybe_remat(blk, cfg)(h, bp, bt, enc_out), None

        x, _ = jax.lax.scan(body, x, (params["dec_blocks"], run_th))
        x = L.rmsnorm(params["final_norm"], x[:, -1:], th["final_norm"],
                      eps=cfg.norm_eps)
        return dpl.dp_linear(params["head"]["w"], None, x, th["head"])[:, 0]

    model = Model(cfg=cfg, spec=spec, layout=layout, loss_fn=loss_fn,
                  serve_step=serve_step, init_cache=init_cache,
                  num_params=_count(spec))
    model.prefill_cross = prefill_cross  # type: ignore[attr-defined]
    model.encode = encode  # type: ignore[attr-defined]
    model.prefill_step = prefill_step  # type: ignore[attr-defined]
    model.init_paged_cache = None  # type: ignore[attr-defined]
    model.cache_slot_axes = cache_slot_axes  # type: ignore[attr-defined]
    return model

"""Algorithm 2: private pipeline-parallel training with per-device clipping.

This is the paper's Sec-4 mechanism expressed in JAX-native terms:

  * the model is partitioned into S stages of consecutive blocks; stage s's
    parameters live ONLY on mesh axis 'stage' coordinate s (shard_map —
    manual SPMD, not GSPMD inference);
  * microbatches stream through the pipeline: at each of
    (n_micro + S - 1) ticks every stage processes the microbatch it holds
    and `ppermute`s activations to the next stage (LocalForward's
    activation sends, Algorithm 3 line 5). Reverse-mode AD through the
    loop yields the mirrored backward ppermutes (Algorithm 4 line 7) —
    the backward schedule is derived, not hand-written;
  * PER-DEVICE CLIPPING: each stage's parameters form one clipping group.
    The dp_* primitives inside the stage body compute stage-LOCAL
    per-example norms — by construction no norm ever crosses the stage
    axis (the paper's "no extra communication" property, now checkable in
    the HLO: zero collectives touch the per-example norm values);
  * noise: equal-budget allocation (gamma_k = C_k) drawn stage-locally —
    each stage's noise std depends only on its own threshold (paper
    Appendix C, Algorithm 2 line 6).

The reference model here is a stage-stacked MLP tower (the mechanism is
architecture-agnostic; transformer stages plug in the same way — each
stage body is any pure block function). `tests/test_pipeline.py` checks
the pipelined loss/grads against a single-device reference and the
per-stage clipping against the per_group driver oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.core import dp_layers as dpl
from repro.core.spec import P, GroupLayout, init_params


# ---------------------------------------------------------------------------
# A stage-stacked MLP tower (each stage: L_per_stage [linear+tanh] blocks).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    layers_per_stage: int
    d_model: int
    d_in: int
    n_classes: int

    @property
    def total_layers(self) -> int:
        return self.n_stages * self.layers_per_stage


def pipeline_spec(cfg: PipelineConfig) -> dict:
    lps, d = cfg.layers_per_stage, cfg.d_model
    return {
        # stage-stacked: leading dim = stage (sharded over 'stage');
        # ONE clipping group per stage (per-DEVICE clipping): explicit
        # group names collapse the per-layer params of a stage together.
        "blocks": {
            "w": P((cfg.n_stages, lps, d, d), stack=1, group="stage"),
            "b": P((cfg.n_stages, lps, d), init="zeros", stack=1,
                   group="stage"),
        },
        "head": {"w": P((d, cfg.n_classes))},
        "embed": {"w": P((cfg.d_in, d))},
    }


def _stage_body(stage_params, x, c):
    """One stage: layers_per_stage DP blocks. x: (B, d). c: (B,) encoded
    thresholds for THIS stage's group."""

    def layer(h, wb):
        w, b = wb
        h = dpl.dp_linear(w, b, h[:, None, :], c)[:, 0]
        return jnp.tanh(h), None

    x, _ = jax.lax.scan(layer, x, (stage_params["w"], stage_params["b"]))
    return x


def make_pipeline_loss(cfg: PipelineConfig, mesh, *, stage_axis: str = "pod"):
    """Returns loss_fn(params, (x, y), thresholds) -> (B,) per-example
    losses, computed through the shard_map pipeline.

    thresholds: dict {'stage': (S, B) encoded}, plus 'embed', 'head' (B,)
    (embed/head live on stage 0 / S-1 conceptually; here replicated for
    simplicity — their groups clip as usual)."""
    s_count = cfg.n_stages

    def pipelined(blocks_w, blocks_b, x0, c_stage):
        """Manual-SPMD pipeline over the stage axis.

        blocks_w/b: LOCAL stage params (1, lps, d, d) per device;
        x0: (n_micro, mb, d) microbatched embedded inputs (replicated);
        c_stage: (1, B) local encoded thresholds.
        Returns (n_micro, mb, d) final activations (valid on the LAST
        stage; other stages hold garbage, masked by the caller)."""
        idx = jax.lax.axis_index(stage_axis)
        n_micro, mb, d = x0.shape
        sp = {"w": blocks_w[0], "b": blocks_b[0]}
        c = c_stage[0]
        ticks = n_micro + s_count - 1
        buf = jnp.zeros((mb, d), x0.dtype)
        outs = jnp.zeros((n_micro, mb, d), x0.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            take = jnp.clip(t, 0, n_micro - 1)
            fresh = x0[take]
            inp = jnp.where(idx == 0, fresh, buf)
            # stage s works on microbatch m = t - s; zero invalid ticks so
            # their (garbage) activations contribute nothing to gradients
            # OR to the per-example norm side channel
            m = t - idx
            valid = (m >= 0) & (m < n_micro)
            inp = jnp.where(valid, inp, jnp.zeros_like(inp))
            # threshold columns of THIS microbatch's examples
            mclip = jnp.clip(m, 0, n_micro - 1)
            c_mb = jax.lax.dynamic_slice_in_dim(c, mclip * mb, mb)
            out = _stage_body(sp, inp, c_mb)
            # last stage records its result at slot t - (S-1)
            slot = jnp.clip(t - (s_count - 1), 0, n_micro - 1)
            valid_out = (t - (s_count - 1) >= 0) & (t - (s_count - 1) < n_micro)
            outs = jax.lax.cond(
                valid_out,
                lambda o: o.at[slot].set(out),
                lambda o: o,
                outs)
            # send activations to the next stage (ring; last->first unused)
            perm = [(i, (i + 1) % s_count) for i in range(s_count)]
            buf = jax.lax.ppermute(out, stage_axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
        # broadcast the last stage's outs to all stages (psum of masked)
        mine = jnp.where(idx == s_count - 1, 1.0, 0.0)
        outs = jax.lax.psum(outs * mine.astype(outs.dtype), stage_axis)
        return outs

    # shard_map: blocks sharded on stage, inputs/outputs replicated
    _in_specs = (PS(stage_axis), PS(stage_axis), PS(), PS(stage_axis))
    smapped = jax.shard_map(pipelined, mesh=mesh, in_specs=_in_specs,
                            out_specs=PS(), check_vma=False)

    def loss_fn(params, batch, th, *, n_micro: int = 2):
        x, y = batch  # (B, d_in), (B,)
        b = x.shape[0]
        mb = b // n_micro
        h = dpl.dp_linear(params["embed"]["w"], None, x[:, None, :],
                          th["embed"])[:, 0]
        hm = h.reshape(n_micro, mb, -1)
        # per-microbatch threshold layout: the stage group's (S, B) encoded
        # thresholds; inside the pipeline each example keeps its own column
        out = smapped(params["blocks"]["w"], params["blocks"]["b"], hm,
                      th["stage"])
        out = out.reshape(b, -1)
        logits = dpl.dp_linear(params["head"]["w"], None, out[:, None, :],
                               th["head"])[:, 0]
        logp = jax.nn.log_softmax(logits)
        return -logp[jnp.arange(b), y]

    return loss_fn


def reference_loss(cfg: PipelineConfig, params, batch, th):
    """Single-device reference: same math, no pipeline."""
    x, y = batch
    h = dpl.dp_linear(params["embed"]["w"], None, x[:, None, :],
                      th["embed"])[:, 0]
    for s in range(cfg.n_stages):
        sp = {"w": params["blocks"]["w"][s], "b": params["blocks"]["b"][s]}
        h = _stage_body(sp, h, th["stage"][s])
    logits = dpl.dp_linear(params["head"]["w"], None, h[:, None, :],
                           th["head"])[:, 0]
    logp = jax.nn.log_softmax(logits)
    return -logp[jnp.arange(y.shape[0]), y]

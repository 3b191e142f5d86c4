"""MiniCPM-2B as published, at CPU size: the embedding tied to the LM head,
the muP scalings, and the exact per-example norm of the tied leaf.

The tied leaf's per-example gradient is the embedding's scatter plus the
head's transposed gradient; its squared norm carries the cross term of the
two, which the BK capture adds (core.bk). Every check here is against an
independent plain-jnp forward of the published equations, differentiated
per example by jax.grad."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.core import bk
from repro.core.clipping import dp_clipped_gradients
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import init_params
from repro.launch.engine import DecodeEngine
from repro.launch.serve import greedy_decode
from repro.models.transformer import build_model

B, T = 3, 12
C = 0.05  # every example clipped, so a wrong norm shows in the sums


@pytest.fixture(scope="module")
def tied():
    cfg = get_config("minicpm-2b", reduced=True)
    model = build_model(cfg)
    params = init_params(model.spec, jax.random.PRNGKey(0))
    # few distinct tokens: repeats within an example, and the cross term's
    # gather hits the same rows many times; the last targets are ignored
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 9)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -3:].set(-1)
    targets = targets.at[1, :5].set(-1)
    return cfg, model, params, {"tokens": tokens, "targets": targets}


@pytest.fixture(scope="module")
def grads(tied):
    """[{leaf: grad}] of each example's plain loss, by jax.grad."""
    cfg, _, params, batch = tied

    def one(i):
        return jax.grad(lambda q: plain_losses(
            cfg, q, batch["tokens"][i:i + 1],
            batch["targets"][i:i + 1])[0])(params)
    return [one(i) for i in range(B)]


def plain_logits(cfg, p, tokens):
    """The published equations in plain jnp: x0 = 12 E[ids]; each branch
    times 1.4 / sqrt(40); logits = rms(x) / (d / dim_model_base) @ Eᵀ."""
    e = p["embed"]["w"]
    x = e[tokens] * cfg.scale_emb
    mult = cfg.scale_depth / math.sqrt(cfg.mup_depth)
    h, hd, f = cfg.num_heads, cfg.resolved_head_dim, cfg.d_ff
    b, t = tokens.shape

    def rms(v, s):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + cfg.norm_eps) * s

    ang = jnp.arange(t)[:, None] * (
        1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2) / hd))
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(q):  # interleaved pairs, as the program rotates them
        q1, q2 = q[..., ::2], q[..., 1::2]
        return jnp.stack([q1 * cos - q2 * sin, q1 * sin + q2 * cos],
                         -1).reshape(q.shape)

    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], p["dense_blocks"])
        qkv = rms(x, lp["attn_norm"]["s"]) @ lp["attn"]["qkv"]["w"]
        q = rope(qkv[..., :h * hd].reshape(b, t, h, hd))
        k = rope(qkv[..., h * hd:2 * h * hd].reshape(b, t, h, hd))
        v = qkv[..., 2 * h * hd:].reshape(b, t, h, hd)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("bhts,bshd->bthd", w, v).reshape(b, t, h * hd)
        x = x + mult * (o @ lp["attn"]["o"]["w"])
        gu = rms(x, lp["mlp_norm"]["s"]) @ lp["mlp"]["gate_up"]["w"]
        x = x + mult * ((jax.nn.silu(gu[..., :f]) * gu[..., f:])
                        @ lp["mlp"]["down"]["w"])
    hn = rms(x, p["final_norm"]["s"]) / (cfg.d_model / cfg.dim_model_base)
    return hn @ e.T


def plain_losses(cfg, p, tokens, targets):
    lg = plain_logits(cfg, p, tokens)
    valid = targets >= 0
    tok = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[..., None],
                              -1)[..., 0]
    ce = (jax.nn.logsumexp(lg, -1) - tok) * valid
    return jnp.sum(ce, -1) / jnp.maximum(jnp.sum(valid, -1), 1)


def sq(tree):
    return sum(float(jnp.sum(jnp.square(l)))
               for l in jax.tree_util.tree_leaves(tree))


def test_config_is_the_published_model():
    cfg = get_config("minicpm-2b")
    assert (cfg.tie_embeddings, cfg.norm_eps, cfg.scale_emb) == (
        True, 1e-5, 12.0)
    assert cfg.logit_divisor == 9.0
    assert cfg.residual_multiplier == pytest.approx(1.4 / math.sqrt(40))
    # a depth cut is one stage of the 40-layer model: same multiplier
    import dataclasses
    cut = dataclasses.replace(cfg, num_layers=6)
    assert cut.residual_multiplier == pytest.approx(0.22135943621178655)
    spec = build_model(get_config("minicpm-2b", reduced=True)).spec
    assert "head" not in spec


def test_loss_matches_plain_forward(tied):
    cfg, model, params, batch = tied
    th = model.layout.pack_value(jnp.inf, B)
    got = model.loss_fn(params, batch, th)
    want = plain_losses(cfg, params, batch["tokens"], batch["targets"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def _clip(model, params, batch, mode):
    kw = dict(flat_threshold=C)
    if mode == "per_group":
        emb = model.layout.group("embed").offset
        assign = np.ones(model.layout.num_groups, np.int32)
        assign[emb] = 0
        kw = dict(group_assignment=jnp.asarray(assign),
                  group_thresholds=jnp.asarray([C, 2 * C]))
    return dp_clipped_gradients(model.loss_fn, params, batch, model.layout,
                                mode=mode, batch_size=B, execution="bk",
                                **kw)


@pytest.mark.parametrize("mode", ["ghost_flat", "per_group"])
def test_tied_norm_is_the_norm_of_the_whole_gradient(tied, grads, mode):
    cfg, model, params, batch = tied
    res = _clip(model, params, batch, mode)
    emb = model.layout.group("embed").offset
    want = [float(jnp.sum(jnp.square(g["embed"]["w"]))) for g in grads]
    np.testing.assert_allclose(res.norms_sq[emb], want, rtol=1e-4)
    # every other group too, and the cross term is a real share of the norm
    np.testing.assert_allclose(jnp.sum(res.norms_sq, 0),
                               [sq(g) for g in grads], rtol=1e-4)
    assert np.all(np.abs(2 * np.asarray(res.tied_cross))
                  > 1e-3 * np.asarray(want))


@pytest.mark.parametrize("mode", ["ghost_flat", "per_group"])
def test_clipped_sums_match_per_example_grads(tied, grads, mode):
    cfg, model, params, batch = tied
    res = _clip(model, params, batch, mode)
    want = None
    for g in grads:
        if mode == "ghost_flat":
            f = min(1.0, C / math.sqrt(sq(g)))
            fe = fo = f
        else:  # the embedding alone at C, everything else at 2C
            e = sq(g["embed"])
            fe = min(1.0, C / math.sqrt(e))
            fo = min(1.0, 2 * C / math.sqrt(sq(g) - e))
        scaled = {k: jax.tree_util.tree_map(
            lambda l, k=k: l * (fe if k == "embed" else fo), v)
            for k, v in g.items()}
        want = scaled if want is None else jax.tree_util.tree_map(
            jnp.add, want, scaled)
    for a, b in zip(jax.tree_util.tree_leaves(res.grads),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-7)


@pytest.mark.parametrize("check", ["norm", "sum"])
def test_dropping_the_cross_term_fails(tied, monkeypatch, check):
    cfg, model, params, batch = tied
    exact = _clip(model, params, batch, "ghost_flat")
    monkeypatch.setattr(bk, "tied_cross",
                        lambda g, a, kst: jnp.zeros((g.shape[0],)))
    dropped = _clip(model, params, batch, "ghost_flat")
    emb = model.layout.group("embed").offset
    if check == "norm":
        assert not np.allclose(dropped.norms_sq[emb], exact.norms_sq[emb],
                               rtol=1e-3)
    else:
        a = dropped.grads["embed"]["w"]
        b = exact.grads["embed"]["w"]
        assert not np.allclose(a, b, rtol=1e-3, atol=1e-7)


def test_naive_flat_norm_is_exact(tied):
    """naive_flat differentiates each example's loss by the leaf itself:
    its tied norm needs no cross term of its own, and is allowed."""
    cfg, model, params, batch = tied
    naive = dp_clipped_gradients(model.loss_fn, params, batch, model.layout,
                                 mode="naive_flat", batch_size=B,
                                 flat_threshold=C)
    bk_ = _clip(model, params, batch, "ghost_flat")
    np.testing.assert_allclose(naive.norms_sq, bk_.norms_sq, rtol=1e-4,
                               atol=1e-9)


@pytest.mark.parametrize("mode,execution", [
    ("per_layer", "bk"), ("ghost_flat", "twopass"),
    ("per_group", "twopass"), ("ghost_flat_twopass", "bk"),
    ("per_group_twopass", "bk")])
def test_modes_without_the_exact_norm_refuse_at_build(tied, mode,
                                                      execution):
    cfg, model, params, batch = tied
    dpc = DPConfig(mode=mode, execution=execution, sigma=1.0,
                   adaptive=False, group_assignment=(0,) * (
                       model.layout.num_groups))
    with pytest.raises(ValueError, match="tied group"):
        make_dp_train_step(model.loss_fn, model.spec, model.layout,
                           optim.adam(1e-3), dpc, batch_size=B)


@pytest.mark.parametrize("mode", ["ghost_flat", "per_group"])
def test_the_sharded_step_refuses_a_tied_table(tied, mode):
    """The mesh step has no tested tied path: it refuses at build, and so
    does its clipping entry point."""
    from repro.core.clipping import sharded_clipped_gradients
    from repro.launch.mesh import make_mesh
    cfg, model, params, batch = tied
    dpc = DPConfig(mode=mode, sigma=1.0, adaptive=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="sharded step"):
        make_dp_train_step(model.loss_fn, model.spec, model.layout,
                           optim.adam(1e-3), dpc, batch_size=B, mesh=mesh)
    with pytest.raises(ValueError, match="sharded step"):
        sharded_clipped_gradients(
            model.loss_fn, params, batch, model.layout, mode=mode,
            batch_size=B, data_size=1, data_axes=("data",),
            model_axis="model")


def test_emit_zero_fills_only_a_tied_channel():
    """A tied channel's use writes its own sinks and zeros the other's; any
    other channel must be given every sink."""
    from repro.kernels import backend
    sds = {"a": jnp.ones((2, 3, 4)), "g": jnp.ones((2, 3, 5))}
    tied_sinks = {"g": jnp.ones((2, 3, 4)), "ids": jnp.ones((2, 3)),
                  "a": jnp.ones((2, 3, 4)), "gh": jnp.ones((2, 3, 7)),
                  "kst": jnp.ones((2, 3, 3))}
    n = jnp.ones((2,))
    with backend.scoped(capture_residuals=True):
        out = bk.emit(bk.BkChannel(n, tied_sinks, "embed"), n,
                      g=2 * jnp.ones((2, 3, 4)), ids=jnp.ones((2, 3)))
        assert float(jnp.sum(out.sink["gh"])) == 0.0
        assert float(jnp.sum(out.sink["g"])) == 48.0
        with pytest.raises(ValueError):
            bk.emit(bk.BkChannel(n, sds, "layer"), n, a=sds["a"])


@pytest.mark.parametrize("mode", ["ghost_flat", "non_private"])
def test_train_step_reports_what_it_clipped_with(tied, mode):
    cfg, model, params, batch = tied
    dpc = DPConfig(mode=mode, sigma=1.0, adaptive=False, init_threshold=C,
                   sampling_rate=0.1, steps=10)
    init_fn, step_fn, _ = make_dp_train_step(
        model.loss_fn, model.spec, model.layout, optim.adam(1e-3), dpc,
        batch_size=B)
    opt, dps = init_fn(params)
    p1, _, _, met = jax.jit(step_fn)(params, opt, dps, batch,
                                     jax.random.PRNGKey(5))
    assert met.norms_sq.shape == (model.layout.num_groups, B)
    assert met.tied_cross.shape == (B,)
    if mode == "ghost_flat":
        ref = _clip(model, params, batch, "ghost_flat")
        np.testing.assert_allclose(met.norms_sq, ref.norms_sq, rtol=1e-5)
        np.testing.assert_allclose(met.tied_cross, ref.tied_cross,
                                   rtol=1e-5)
    assert not np.allclose(p1["embed"]["w"], params["embed"]["w"])


@pytest.mark.parametrize("paging", ["on", "off"])
def test_decode_logits_match_the_full_forward(tied, paging):
    """Prefill then one token at a time through the serve step (contiguous
    or paged cache): the logits at each position are the full forward's."""
    cfg, model, params, batch = tied
    tokens = batch["tokens"]
    full = plain_logits(cfg, params, tokens)
    if paging == "on":
        cache = model.init_paged_cache(B, 16, num_pages=B * 2, page_len=8)
        cache["pt"] = jnp.arange(B * 2, dtype=jnp.int32).reshape(B, 2)
    else:
        cache = model.init_cache(B, 16)
    step = jax.jit(model.serve_step)
    for t in range(T):
        logits, cache = step(params, cache, {"token": tokens[:, t:t + 1]})
        np.testing.assert_allclose(logits, full[:, t], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        model.prefill_step(params, {"tokens": tokens}), full[:, -1],
        rtol=2e-4, atol=2e-5)


def test_decode_engine_serves_the_tied_model(tied):
    cfg, model, params, batch = tied
    eng = DecodeEngine(model, params, num_slots=2, cache_len=32, page_len=8)
    assert eng.paged
    prompts = [np.asarray(batch["tokens"][i, :5 + i]) for i in range(B)]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        want = np.asarray(greedy_decode(model, params, jnp.asarray(p)[None],
                                        6, 32, prefill="loop"))[0].tolist()
        assert done[rid].tokens == want

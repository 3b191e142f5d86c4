"""Book-keeping (BK) engine: single-backprop flat/group clipping.

Contract under test (repro.core.bk + kernels/clip_reduce.scale_contract):
  * bk ≡ twopass — clipped grads AND per-group norms² identical for
    ghost_flat and per_group, including microbatch accumulation and the
    DP-LoRA trainable_key path;
  * the scale_contract Pallas kernel matches its jnp oracle;
  * the compiled HLO really contains ONE backward pass under execution=bk
    and TWO under twopass (analysis.hlo.backward_passes);
  * unsupported layouts (shared-site params) fall back to twopass;
  * naive_flat reports real per-layout-group norms².
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.core import bk
from repro.core.clipping import dp_clipped_gradients
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import abstract_params, init_params
from repro.launch.inputs import concrete_train_batch
from repro.models.transformer import build_model

B, T = 8, 16


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny")
    m = build_model(cfg)
    params = init_params(m.spec, jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, B, T, jax.random.PRNGKey(1))
    return cfg, m, params, batch


def _assert_trees_close(a, b, rtol=2e-4, atol=2e-6):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol)


# ---------------------------------------------------------------------------
# bk ≡ twopass on the tiny transformer (scanned stacks, embed, head, norms).
# ---------------------------------------------------------------------------


def test_probe_captures_tiny_layout(tiny):
    cfg, m, params, batch = tiny
    rec = bk.probe_recipes(m.loss_fn, params, batch, m.layout, B)
    assert rec is not None
    kinds = {r.kind for r in rec.values()}
    assert {"linear", "embed", "scale"} <= kinds
    assert all(r.count == 1 for r in rec.values())


def test_ghost_flat_bk_equals_twopass(tiny):
    cfg, m, params, batch = tiny
    r_bk = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                mode="ghost_flat", batch_size=B,
                                flat_threshold=0.5, execution="bk")
    r_tp = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                mode="ghost_flat_twopass", batch_size=B,
                                flat_threshold=0.5)
    np.testing.assert_allclose(np.asarray(r_bk.norms_sq),
                               np.asarray(r_tp.norms_sq), rtol=1e-5,
                               atol=1e-8)
    _assert_trees_close(r_bk.grads, r_tp.grads)


def test_per_group_bk_equals_twopass(tiny):
    cfg, m, params, batch = tiny
    assign = jnp.array([i % 2 for i in range(m.layout.num_groups)])
    cg = jnp.array([0.3, 0.4])
    kw = dict(mode="per_group", batch_size=B, group_assignment=assign,
              group_thresholds=cg)
    r_bk = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                execution="bk", **kw)
    r_tp = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                execution="twopass", **kw)
    np.testing.assert_allclose(np.asarray(r_bk.norms_sq),
                               np.asarray(r_tp.norms_sq), rtol=1e-5,
                               atol=1e-8)
    _assert_trees_close(r_bk.grads, r_tp.grads)


def test_bk_microbatched_step_equals_twopass(tiny):
    """Full jitted train step, microbatches > 1: same key -> same noise, so
    any parameter difference comes from the clipped grads."""
    cfg, m, params, batch = tiny
    outs = []
    for execution in ("bk", "twopass"):
        dpc = DPConfig(mode="ghost_flat", sigma=1.0, sampling_rate=0.1,
                       steps=10, adaptive=True, microbatches=4,
                       execution=execution)
        init_fn, step_fn, _ = make_dp_train_step(
            m.loss_fn, m.spec, m.layout, optim.sgd(0.1), dpc, batch_size=B)
        opt_state, dp_state = init_fn(params)
        p2, _, _, met = jax.jit(step_fn)(params, opt_state, dp_state, batch,
                                         jax.random.PRNGKey(5))
        assert np.isfinite(float(met.loss))
        outs.append(p2)
    _assert_trees_close(outs[0], outs[1], rtol=2e-4, atol=2e-6)


def test_bk_lora_trainable_key_equals_twopass():
    cfg = dataclasses.replace(get_config("qwen3-4b", reduced=True),
                              lora_rank=4)
    m = build_model(cfg)
    params = init_params(m.spec, jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 4, T, jax.random.PRNGKey(1))
    kw = dict(mode="ghost_flat", batch_size=4, flat_threshold=0.5,
              trainable_key="lora")
    r_bk = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                execution="bk", **kw)
    r_tp = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                execution="twopass", **kw)
    np.testing.assert_allclose(np.asarray(r_bk.norms_sq),
                               np.asarray(r_tp.norms_sq), rtol=1e-5,
                               atol=1e-8)
    assert set(r_bk.grads) == {"lora"}
    _assert_trees_close(r_bk.grads, r_tp.grads)


def test_probe_failure_raises_instead_of_falling_back(tiny):
    """Only the documented layouts fall back to twopass. A loss that cannot
    trace with channel leaves is a bug; a silent fallback would double the
    backward passes with nothing to tell the two apart."""
    cfg, m, params, batch = tiny

    def broken_loss(p, b, th):
        if any(isinstance(v, bk.BkChannel) for v in th.values()):
            raise RuntimeError("recorder bug")
        return m.loss_fn(p, b, th)

    with pytest.raises(RuntimeError, match="recorder bug"):
        bk.probe_recipes(broken_loss, params, batch, m.layout, B)
    with pytest.raises(RuntimeError, match="recorder bug"):
        dp_clipped_gradients(broken_loss, params, batch, m.layout,
                             mode="ghost_flat", batch_size=B,
                             flat_threshold=0.5, execution="bk")


def test_bk_falls_back_on_shared_site_params():
    """Zamba2's shared attention block (sensitivity_mult > 1) cannot be
    captured — one threshold leaf, many runtime sites — so the probe must
    refuse and the driver must still produce twopass-correct results."""
    cfg = get_config("zamba2-7b", reduced=True)
    m = build_model(cfg)
    params = init_params(m.spec, jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 2, 8, jax.random.PRNGKey(1))
    assert bk.probe_recipes(m.loss_fn, params, batch, m.layout, 2) is None
    r_bk = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                mode="ghost_flat", batch_size=2,
                                flat_threshold=0.5, execution="bk")
    r_tp = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                mode="ghost_flat_twopass", batch_size=2,
                                flat_threshold=0.5)
    _assert_trees_close(r_bk.grads, r_tp.grads)


# ---------------------------------------------------------------------------
# The epilogue kernel.
# ---------------------------------------------------------------------------


_T128 = dict(bi=128, bj=128, bt=16)


# Past the first three (f32, derived tiles, uniform factors): bf16 operands
# against the f32 oracle with explicit 128 tiles and the derived ones; din
# 300 and 2700 (ragged last blocks, as the tied head's V = 122,753 has),
# dout 200; T = 40 and 20, no multiple of the row tile (16 explicit, 48 and
# 32 derived); S up to 3; factors spanning 0 to 1, the first example at 0.
@pytest.mark.parametrize("shape,dtype,tiles,span", [
    pytest.param((2, 4, 300, 65, 130), jnp.float32, {}, False, id="shape0"),
    pytest.param((1, 3, 17, 8, 5), jnp.float32, {}, False, id="shape1"),
    pytest.param((3, 2, 256, 130, 64), jnp.float32, {}, False, id="shape2"),
    pytest.param((3, 2, 40, 300, 200), jnp.bfloat16, _T128, True,
                 id="bf16-ragged-t128"),
    pytest.param((3, 2, 40, 300, 200), jnp.bfloat16, {}, True,
                 id="bf16-ragged-derived"),
    pytest.param((2, 2, 20, 2700, 130), jnp.bfloat16, {}, True,
                 id="bf16-wide-derived"),
    pytest.param((2, 3, 40, 300, 200), jnp.float32, _T128, True,
                 id="f32-ragged-t128"),
])
def test_scale_contract_kernel_matches_ref(shape, dtype, tiles, span):
    from repro.kernels.clip_reduce import scale_contract
    from repro.kernels.ref import scale_contract_ref
    s, b, t, di, do = shape
    k = jax.random.PRNGKey(0)
    a = jax.random.normal(jax.random.fold_in(k, 1), (s, b, t, di)
                          ).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(k, 2), (s, b, t, do)
                          ).astype(dtype)
    f = jax.random.uniform(jax.random.fold_in(k, 3), (s, b))
    if span:
        f = jnp.linspace(0.0, 1.0, s * b).reshape(s, b)
    got = scale_contract(a, g, f, **tiles, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(scale_contract_ref(a, g, f)),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("bt", [None, 16])
def test_scale_contract_sensitivity_bound(bt):
    """With f[s, b] = C / ‖A[s,b]ᵀG[s,b]‖, each example's clipped sum from
    bf16 operands has Frobenius norm at most C·(1 + 1e-5) in every stack
    slice: the factor stays an unquantized f32 that scales the f32
    partial sums."""
    from repro.kernels.clip_reduce import scale_contract
    from repro.kernels.ref import ghost_norm_ref
    s, b, t, din, dout, clip = 2, 3, 48, 200, 300, 0.7
    k = jax.random.PRNGKey(11)
    a = jax.random.normal(k, (s, b, t, din)).astype(jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(k, 1), (s, b, t, dout)
                          ).astype(jnp.bfloat16)
    f = clip / jnp.sqrt(jax.vmap(ghost_norm_ref)(a, g))
    for i in range(b):
        got = scale_contract(a[:, i:i + 1], g[:, i:i + 1], f[:, i:i + 1],
                             bt=bt, interpret=True)
        norms = jnp.linalg.norm(got.reshape(s, -1), axis=-1)
        assert float(jnp.max(norms)) <= clip * (1 + 1e-5), (i, norms)


def test_scale_contract_backend_op_parity():
    from repro.kernels import backend
    k = jax.random.PRNGKey(7)
    a = jax.random.normal(jax.random.fold_in(k, 1), (2, 3, 40, 20))
    g = jax.random.normal(jax.random.fold_in(k, 2), (2, 3, 40, 9))
    f = jax.random.uniform(jax.random.fold_in(k, 3), (2, 3))
    with backend.scoped("pallas", interpret=True):
        got = backend.active().scale_contract(a, g, f)
    with backend.scoped("xla"):
        want = backend.active().scale_contract(a, g, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=1e-4)
    # unstacked 3-D form routes through clipped_sum_linear semantics
    with backend.scoped("xla"):
        got3 = backend.active().scale_contract(a[0], g[0], f[0])
    np.testing.assert_allclose(np.asarray(got3), np.asarray(want[0]),
                               rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# The win is asserted from the compiled HLO, not assumed.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hlo_reports_single_backward_pass_under_bk():
    from repro.analysis.hlo import backward_passes
    cfg = dataclasses.replace(get_config("tiny"), num_layers=4)
    m = build_model(cfg)
    params = abstract_params(m.spec)
    batch = jax.eval_shape(
        lambda k: concrete_train_batch(cfg, B, T, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    counts = {}
    for execution in ("bk", "twopass"):
        dpc = DPConfig(mode="ghost_flat", sigma=1.0, sampling_rate=0.1,
                       steps=10, execution=execution, backend="xla")
        init_fn, step_fn, _ = make_dp_train_step(
            m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc,
            batch_size=B)
        opt_abs, dp_abs = jax.eval_shape(init_fn, params)
        hlo = jax.jit(step_fn).lower(params, opt_abs, dp_abs, batch,
                                     key).compile().as_text()
        counts[execution] = backward_passes(hlo, 4)
    assert counts == {"bk": 1, "twopass": 2}


# ---------------------------------------------------------------------------
# naive_flat now reports real per-layout-group norms².
# ---------------------------------------------------------------------------


def test_naive_flat_reports_per_group_norms(tiny):
    cfg, m, params, batch = tiny
    r_naive = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                   mode="naive_flat", batch_size=B,
                                   flat_threshold=0.5)
    r_ghost = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                   mode="ghost_flat", batch_size=B,
                                   flat_threshold=0.5)
    assert r_naive.norms_sq.shape == (m.layout.num_groups, B)
    np.testing.assert_allclose(np.asarray(r_naive.norms_sq),
                               np.asarray(r_ghost.norms_sq), rtol=2e-3,
                               atol=1e-6)

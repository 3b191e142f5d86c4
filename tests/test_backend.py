"""Ghost-op backend engine: registry mechanics + pallas ≡ xla parity for
every registered op, including ragged shapes that exercise the kernels'
padding paths, and an end-to-end DP train step on the tiny config."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend

# ragged on purpose: T not a multiple of bt=32, din < dk, dout < bj,
# plus one tile-aligned case
SHAPES = [
    (2, 8, 16, 24),
    (3, 70, 48, 40),
    (1, 33, 7, 130),
    (4, 64, 32, 32),
]


def _data(shape, seed=0):
    b, t, din, dout = shape
    key = jax.random.PRNGKey(seed + (hash(shape) & 0xFFFF))
    a = jax.random.normal(key, (b, t, din))
    g = jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)) * 0.1
    f = jax.random.uniform(jax.random.fold_in(key, 2), (b,))
    return a, g, f


def _engines():
    xla_eng = backend.make_engine("xla", bt=32, dk=32, bi=32, bj=32)
    pal_eng = backend.make_engine("pallas", bt=32, dk=32, bi=32, bj=32)
    return xla_eng, pal_eng


# ---------------------------------------------------------------------------
# Registry / scoping mechanics.
# ---------------------------------------------------------------------------


def test_registry_contents():
    assert set(backend.backends()) >= {"xla", "pallas", "auto"}


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown ghost backend"):
        backend.make_engine("tensorcore9000")


def test_scoped_nesting_and_inheritance():
    assert backend.active().name == "xla"  # default
    with backend.scoped("pallas", bt=64):
        assert backend.active().name == "pallas"
        assert backend.active().config.bt == 64
        # inner scope inherits unspecified fields from the enclosing one
        with backend.scoped(outer_max_elems=123):
            cfg = backend.active().config
            assert cfg.backend == "pallas"
            assert cfg.bt == 64
            assert cfg.outer_max_elems == 123
        assert backend.active().config.outer_max_elems != 123
    assert backend.active().name == "xla"


def test_scoped_restores_on_error():
    with pytest.raises(RuntimeError):
        with backend.scoped("pallas"):
            raise RuntimeError("boom")
    assert backend.active().name == "xla"


def test_choose_linear_path_cost_model():
    cfg = backend.EngineConfig(bt=256, dk=512)
    # off-TPU without forced interpret: always xla
    assert backend.choose_linear_path(4096, 1024, 1024, cfg,
                                      on_tpu=False) == "xla"
    # small weight, outer path cheaper -> xla even on TPU
    assert backend.choose_linear_path(4096, 16, 16, cfg, on_tpu=True) == "xla"
    # gram regime on TPU (outer transient over the cap) -> pallas
    assert backend.choose_linear_path(4096, 4096, 4096, cfg,
                                      on_tpu=True) == "pallas"
    # sub-tile sequence -> xla
    assert backend.choose_linear_path(64, 4096, 4096, cfg,
                                      on_tpu=True) == "xla"
    # interpret forced on CPU (tests): kernels selectable
    cfg_i = backend.EngineConfig(bt=256, dk=512, interpret=True)
    assert backend.choose_linear_path(4096, 4096, 4096, cfg_i,
                                      on_tpu=False) == "pallas"


def _cell_linears():
    """(T, din, dout) of every matmul of every benchmark cell, from its
    configuration's `bench/flops/dense.py` linears, named cell.linear."""
    import json
    import pathlib

    from bench.flops import dense
    root = pathlib.Path(__file__).resolve().parents[1]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in spec["configs"]}
    for cell in spec["workloads"]:
        cfg = json.loads((root / files[cell["config"]]).read_text())
        traffic = root / "bench" / "traffic" / f"{cell['traffic']}.json"
        t = json.loads(traffic.read_text())["seq"]
        for name, din, dout, _ in dense.linears(cfg):
            yield pytest.param(t, din, dout, id=f"{cell['name']}.{name}")


@pytest.mark.parametrize("t,din,dout", list(_cell_linears()))
def test_auto_routes_cell_linears_to_pallas(t, din, dout):
    """On the chip, with no measured table, `auto` sends every linear of
    the benchmark's cells to the Pallas kernels, the norm kernel's derived
    tiles checked against its VMEM: `ghost_norm_roofline.train` counts
    exactly these linears."""
    cfg = backend.EngineConfig(backend="auto", autotune=False)
    assert backend.choose_linear_path(t, din, dout, cfg,
                                      on_tpu=True) == "pallas"
    for op in ("norms", "linear_clip"):
        assert backend.choose_op(op, t, din, dout, cfg,
                                 on_tpu=True) == "pallas"


# ---------------------------------------------------------------------------
# Op-level parity: pallas (interpret) ≡ xla for the full op surface.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
def test_parity_linear_norms_sq(shape):
    xla_eng, pal_eng = _engines()
    a, g, _ = _data(shape)
    np.testing.assert_allclose(pal_eng.linear_norms_sq(a, g),
                               xla_eng.linear_norms_sq(a, g), rtol=1e-4)


@pytest.mark.parametrize("shape", [(2, 8, 16, 24), (3, 70, 48, 40)])
@pytest.mark.parametrize("axis,m", [("out", 4), ("in", 8)])
def test_parity_linear_norms_sq_blocked(shape, axis, m):
    xla_eng, pal_eng = _engines()
    a, g, _ = _data(shape)
    got = pal_eng.linear_norms_sq_blocked(a, g, m, block_axis=axis)
    want = xla_eng.linear_norms_sq_blocked(a, g, m, block_axis=axis)
    np.testing.assert_allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("shape", SHAPES)
def test_parity_clipped_sum_linear(shape):
    xla_eng, pal_eng = _engines()
    a, g, f = _data(shape)
    np.testing.assert_allclose(pal_eng.clipped_sum_linear(a, g, f),
                               xla_eng.clipped_sum_linear(a, g, f),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("axis,m", [("out", 4), ("in", 8)])
def test_parity_clipped_sum_linear_blocked(axis, m):
    xla_eng, pal_eng = _engines()
    a, g, _ = _data((3, 70, 48, 40))
    fb = jax.random.uniform(jax.random.PRNGKey(7), (3, m))
    got = pal_eng.clipped_sum_linear_blocked(a, g, fb, block_axis=axis)
    want = xla_eng.clipped_sum_linear_blocked(a, g, fb, block_axis=axis)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("with_extra", [False, True])
def test_parity_linear_clip(shape, with_extra):
    """The fused norm+clip+reduce op — inc. the composed fallback path."""
    xla_eng, pal_eng = _engines()
    a, g, _ = _data(shape)
    b = shape[0]
    c = jnp.array(([0.2, jnp.inf, -0.5, 0.01] * b)[:b])
    extra = (jax.random.uniform(jax.random.PRNGKey(3), (b,))
             if with_extra else None)
    n_x, f_x, dw_x = xla_eng.linear_clip(a, g, c, extra)
    n_p, f_p, dw_p = pal_eng.linear_clip(a, g, c, extra)
    np.testing.assert_allclose(n_p, n_x, rtol=1e-4)
    np.testing.assert_allclose(f_p, f_x, rtol=1e-4)
    np.testing.assert_allclose(dw_p, dw_x, rtol=1e-4, atol=1e-5)


def test_parity_linear_clip_vmem_fallback():
    """Over the VMEM guard the pallas engine composes two kernels — same
    answer as the fused kernel / xla."""
    xla_eng, _ = _engines()
    small = backend.make_engine("pallas", bt=32, dk=32, bi=32, bj=32,
                                vmem_limit_bytes=1024)
    assert not small._fused_fits(48, 40)
    a, g, _ = _data((3, 70, 48, 40))
    c = jnp.array([0.2, jnp.inf, 0.05])
    n_x, _, dw_x = xla_eng.linear_clip(a, g, c)
    n_p, _, dw_p = small.linear_clip(a, g, c)
    np.testing.assert_allclose(n_p, n_x, rtol=1e-4)
    np.testing.assert_allclose(dw_p, dw_x, rtol=1e-4, atol=1e-5)


def test_parity_linear_clip_prefer_fused_off():
    """prefer_fused=False (the two-pass drivers' norms-only scope) composes
    norm + reduce kernels — same answer as the fused kernel."""
    xla_eng, _ = _engines()
    composed = backend.make_engine("pallas", bt=32, dk=32, bi=32, bj=32,
                                   prefer_fused=False)
    a, g, _ = _data((3, 70, 48, 40))
    c = jnp.array([0.2, jnp.inf, 0.05])
    n_x, _, dw_x = xla_eng.linear_clip(a, g, c)
    n_p, _, dw_p = composed.linear_clip(a, g, c)
    np.testing.assert_allclose(n_p, n_x, rtol=1e-4)
    np.testing.assert_allclose(dw_p, dw_x, rtol=1e-4, atol=1e-5)


def test_parity_fallback_ops():
    """Ops with no kernel fall back to the xla implementations — identical
    answers by construction, but the dispatch must still resolve."""
    xla_eng, pal_eng = _engines()
    key = jax.random.PRNGKey(11)
    g = jax.random.normal(key, (4, 9, 7))
    ids = jax.random.randint(jax.random.fold_in(key, 1), (4, 9), 0, 30)
    xhat = jax.random.normal(jax.random.fold_in(key, 2), (4, 9, 7))
    f = jax.random.uniform(jax.random.fold_in(key, 3), (4,))
    for op, args in [
        ("bias_norms_sq", (g,)),
        ("embed_norms_sq", (ids, g)),
        ("scale_norms_sq", (xhat, g)),
        ("vector_norms_sq", (g,)),
        ("clipped_sum_bias", (g, f)),
        ("clipped_sum_embed", (ids, g, f, 30)),
        ("clipped_sum_scale", (xhat, g, f)),
    ]:
        np.testing.assert_allclose(getattr(pal_eng, op)(*args),
                                   getattr(xla_eng, op)(*args), rtol=1e-5)


def test_auto_backend_dispatch_runs():
    """auto resolves (to xla off-TPU) and matches the reference."""
    with backend.scoped("auto") as auto_eng:
        a, g, f = _data((2, 8, 16, 24))
        xla_eng, _ = _engines()
        np.testing.assert_allclose(auto_eng.linear_norms_sq(a, g),
                                   xla_eng.linear_norms_sq(a, g), rtol=1e-5)
        n, fac, dw = auto_eng.linear_clip(a, g, jnp.full((2,), 0.3))
        assert n.shape == (2,) and dw.shape == (16, 24)


# ---------------------------------------------------------------------------
# End-to-end: DP train step on configs/tiny under backend="pallas".
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_config
    from repro.core.spec import init_params
    from repro.launch.inputs import concrete_train_batch
    from repro.models.transformer import build_model
    cfg = get_config("tiny")
    m = build_model(cfg)
    params = init_params(m.spec, jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 4, 16, jax.random.PRNGKey(1))
    return m, params, batch


def test_e2e_norms_parity_tiny(tiny_model):
    """Acceptance: per-group norms² under pallas match xla to <=1e-4 rel."""
    from repro.core.clipping import dp_clipped_gradients
    m, params, batch = tiny_model
    th = jnp.full((m.layout.num_groups,), 0.05)

    def run():
        return dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                    mode="per_layer", batch_size=4,
                                    thresholds=th)

    with backend.scoped("xla"):
        res_x = jax.jit(run)()
    with backend.scoped("pallas"):
        res_p = jax.jit(run)()
    np.testing.assert_allclose(np.asarray(res_p.norms_sq),
                               np.asarray(res_x.norms_sq), rtol=1e-4)
    for gx, gp in zip(jax.tree_util.tree_leaves(res_x.grads),
                      jax.tree_util.tree_leaves(res_p.grads)):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                   rtol=2e-3, atol=1e-6)


def test_e2e_train_step_backends_match(tiny_model):
    """make_dp_train_step(backend='pallas') runs a full DP step on tiny and
    lands on the same state as backend='xla' (same noise key)."""
    from repro import optim
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    m, params, batch = tiny_model
    outs = []
    for be in ("xla", "pallas"):
        dpc = DPConfig(mode="per_layer", sigma=1.0, sampling_rate=0.1,
                       steps=10, adaptive=True, backend=be)
        init_fn, step_fn, _ = make_dp_train_step(
            m.loss_fn, m.spec, m.layout, optim.sgd(0.1), dpc, batch_size=4)
        opt_state, dp_state = init_fn(params)
        p2, _, dp2, met = jax.jit(step_fn)(params, opt_state, dp_state,
                                           batch, jax.random.PRNGKey(5))
        assert np.isfinite(float(met.loss))
        outs.append((p2, dp2, met))
    (p_x, dp_x, met_x), (p_p, dp_p, met_p) = outs
    # norms² drive clip_fraction and the threshold update — must agree
    np.testing.assert_allclose(float(met_p.clip_fraction),
                               float(met_x.clip_fraction), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dp_p.qstate.thresholds),
                               np.asarray(dp_x.qstate.thresholds), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_x),
                    jax.tree_util.tree_leaves(p_p)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-3, atol=1e-6)

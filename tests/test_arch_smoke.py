"""Per-architecture smoke: REDUCED variant forward/train/decode on CPU.

One test per assigned architecture (task requirement): instantiate the
reduced config, run one forward + one DP train step, assert output shapes
and finiteness; plus a two-token decode against the cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import ARCH_IDS, get_config
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import init_params
from repro.launch.inputs import concrete_train_batch
from repro.models.transformer import build_model

B, T = 2, 16


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch, reduced=True)
            m = build_model(cfg)
            params = init_params(m.spec, jax.random.PRNGKey(0))
            cache[arch] = (cfg, m, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch, built):
    cfg, m, params = built(arch)
    batch = concrete_train_batch(cfg, B, T, jax.random.PRNGKey(1))
    th = m.layout.pack_value(jnp.inf, B)
    losses = m.loss_fn(params, batch, th)
    assert losses.shape == (B,)
    assert np.isfinite(np.asarray(losses)).all()
    assert m.layout.num_groups > 0
    assert m.num_params > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_dp_train_step(arch, built):
    cfg, m, params = built(arch)
    batch = concrete_train_batch(cfg, 4, T, jax.random.PRNGKey(2))
    # a tied embedding / LM head clips exactly only through BK's flat or
    # group modes (clipping.check_tied_mode)
    mode = "ghost_flat" if cfg.tie_embeddings else "per_layer"
    dpc = DPConfig(mode=mode, sigma=0.8, sampling_rate=0.1, steps=10,
                   adaptive=True, init_threshold=1.0)
    init_fn, step_fn, plan = make_dp_train_step(
        m.loss_fn, getattr(m, "dp_spec", m.spec), m.layout,
        optim.adam(1e-3), dpc, batch_size=4,
        trainable_key=getattr(m, "trainable_key", None))
    opt_state, dp_state = init_fn(params)
    p2, _, dp2, met = jax.jit(step_fn)(params, opt_state, dp_state, batch,
                                       jax.random.PRNGKey(3))
    assert np.isfinite(float(met.loss))
    for leaf in jax.tree_util.tree_leaves(p2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2)))
    assert moved
    assert int(dp2.step) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_two_steps(arch, built):
    cfg, m, params = built(arch)
    cache = m.init_cache(B, 64)
    tok = jax.random.randint(jax.random.PRNGKey(4), (B, 1), 0,
                             cfg.vocab_size)
    step = jax.jit(m.serve_step)
    logits, cache = step(params, cache, {"token": tok})
    logits2, cache = step(params, cache, {"token": tok})
    assert logits.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2)).all()
    assert int(cache["pos"][0]) == 2

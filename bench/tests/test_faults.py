"""A whole run at the tiny size on the CPU, the chip check skipped, with the
program's training step broken underneath: `correct` must come out false
for each fault a training cell can have (on one chip there is no exchange
between chips to leave out), and for a clipped sum or clip factor gone
wrong while the loss stays right."""
import json

import pytest

from bench import run
from bench.tests import tiny


def _stale(orig):
    """The step returns its state unchanged."""
    def make(*a, **kw):
        init_fn, step_fn, plan = orig(*a, **kw)

        def step(p, o, d, batch, key):
            return p, o, d, step_fn(p, o, d, batch, key)[3]
        return init_fn, step, plan
    return make


def _half_batch(orig):
    """Half of the batch left out, the mean taken over the rest."""
    def make(loss_fn, spec, layout, opt, cfg, *, batch_size, **kw):
        half = batch_size // 2
        init_fn, step_fn, plan = orig(loss_fn, spec, layout, opt, cfg,
                                      batch_size=half, **kw)

        def step(p, o, d, batch, key):
            return step_fn(p, o, d, {k: v[:half] for k, v in batch.items()},
                           key)
        return init_fn, step, plan
    return make


def _half_sum(monkeypatch):
    """Half of the rows left out of the clipped sum, the mean still taken
    over the whole batch; the loss and the norms still see every row."""
    from repro.core import dp_sgd
    orig = dp_sgd.dp_clipped_gradients

    def clipped(loss_fn, params, batch, layout, *, batch_size, **kw):
        full = orig(loss_fn, params, batch, layout, batch_size=batch_size,
                    **kw)
        half = batch_size // 2
        part = orig(loss_fn, params, {k: v[:half] for k, v in batch.items()},
                    layout, batch_size=half, **kw)
        return full._replace(grads=part.grads)

    monkeypatch.setattr(dp_sgd, "dp_clipped_gradients", clipped)


def _norm_sq_factor(monkeypatch):
    """Clip factors min(1, C / ||g||^2), from the squared norm."""
    import jax.numpy as jnp
    from repro.core import dp_layers, ghost
    from repro.kernels import backend

    def clip_factor(c, norms_sq):
        c = c.astype(jnp.float32)
        n = norms_sq.astype(jnp.float32)
        f = jnp.where(jnp.isinf(c), 1.0, jnp.minimum(1.0, c / (n + 1e-12)))
        return jnp.where(c < 0, -c, f)

    for mod in (dp_layers, ghost, backend):
        monkeypatch.setattr(mod, "clip_factor", clip_factor)


def _patch_step(fault):
    """A fault of the step's factory, planted in `make_dp_train_step`."""
    def plant(monkeypatch):
        from repro.core import dp_sgd
        monkeypatch.setattr(dp_sgd, "make_dp_train_step",
                            fault(dp_sgd.make_dp_train_step))
    return plant


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("bench")))


def _run(root, capsys, seed):
    rc = run.main(["--workload", tiny.CELL, "--seed", str(seed),
                   "--seconds", "0.3"], root=root, allow_cpu=True,
                  cache=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,caught_by", [
    (None, None),
    (_patch_step(_stale), {"clip_gap", "grad_gap", "change_gap"}),
    (_patch_step(_half_batch), {"loss_gap", "grad_gap"}),
    (_half_sum, {"clip_gap"}),
    (_norm_sq_factor, {"clip_gap"}),
])
def test_correct_fails_on_each_fault(root, capsys, monkeypatch, fault,
                                     caught_by):
    if fault is not None:
        fault(monkeypatch)
    line = _run(root, capsys, 2 ** 35 + 11)
    failed = {k for k, c in line["compared"].items()
              if not c["value"] <= c["limit"]}
    if fault is None:
        assert line["correct"] is True and not failed
    else:
        assert line["correct"] is False and caught_by <= failed

"""The reference's step with a second batch, and the calibration's readings
at the tiny size on the CPU: the control and every planted fault come out
not correct under the cell's limits, the program does not."""
import numpy as np
import pytest

from bench import calibrate, harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("bench")))


def _reference(root, **kw):
    cell = harness.find_cell(root, tiny.CELL)
    ref = harness.load_module(root, "reference", cell.cfg["family"])
    m = ref.Dims.of(cell.cfg)
    import jax
    params = ref.init_params(m, jax.random.PRNGKey(3), "float32")
    dp = ref.DPReference(m, ref.Job.of(cell.traffic), **kw)
    rows = np.random.default_rng(0).integers(0, m.vocab, (8, 33))
    batches = [(rows[i:i + 4, :-1], rows[i:i + 4, 1:]) for i in (0, 4)]
    return ref, dp, dp.initial_state(params), batches


def test_the_noise_cancels_in_the_stored_difference(root):
    """In float32 state, the difference of the two steps' stored moments is
    the difference of the clipped sums, whatever the noise."""
    import jax
    ref, dp, state, (a, b) = _reference(root)
    seen = {}

    def read(p, exact, stored):
        seen[p] = (np.asarray(exact), np.asarray(stored))

    dp.step(state, *a, jax.random.PRNGKey(1), other=b, read=read)
    assert set(seen) == set(ref.param_shapes(dp.m))
    for p, (exact, stored) in seen.items():
        scale = max(np.abs(exact).max(), 1e-12)
        assert np.abs(stored - exact).max() <= 1e-3 * scale, p


def test_half_of_the_sum_halves_the_difference_of_the_sums(root):
    import jax
    sums = {}
    for fault in (None, "half_sum"):
        _, dp, state, (a, b) = _reference(root, fault=fault)
        out = {}
        dp.step(state, *a, jax.random.PRNGKey(1), other=b,
                read=lambda p, e, s: out.__setitem__(p, np.asarray(e)))
        sums[fault] = out
    whole = sum(np.sum(v * v) for v in sums[None].values())
    half = sum(np.sum(v * sums[None][p]) for p, v in sums["half_sum"].items())
    assert 0.2 < half / whole < 0.8


def test_the_control_and_every_fault_come_out_not_correct(root, capsys):
    rows = calibrate.calibrate(root, tiny.CELL, [2 ** 36 + 5], allow_cpu=True)
    limits = {k: v["limit"] for k, v in tiny.LIMITS.items()}

    def failed(reading):
        return {k for k, lim in limits.items() if not reading[k] <= lim}

    row = rows[0]
    assert not failed(row["program"])
    for name in ("control", "half_sum", "norm_sq", "half_batch"):
        assert failed(row[name]), name
    assert "clip_gap" in failed(row["half_sum"]) & failed(row["norm_sq"])

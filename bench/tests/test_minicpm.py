"""The MiniCPM cell's files at the tiny size on the CPU: the reference's flat
step against per-example jax.grad, its planted faults, the hand counts of
the FLOP model, the traffic file's rows, the driver's muP check, and whole
runs of a tiny copy of the cell, sound and with the cross term dropped."""
import dataclasses
import json
import math
import os

import numpy as np
import pytest

from bench import calibrate, generate, harness, run
from bench.flops import minicpm as flops
from bench.tests import tiny

REPO = tiny.REPO
CELL = "train.minicpm-tiny.ghost_flat"
FILE = os.path.join(REPO, "bench", "configs", "minicpm-2b-6l.json")
TRAFFIC = os.path.join(REPO, "bench", "traffic",
                       "dp_ghost_flat_b2_t2048.json")
CONFIG = {"source": "test", "arch": "minicpm-2b", "family": "minicpm",
          "reduced": [], "hidden_size": 64, "intermediate_size": 128,
          "num_hidden_layers": 2, "published_num_hidden_layers": 40,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "head_dim": 16, "vocab_size": 257, "attention_bias": False,
          "qk_norm": False, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
          "tie_word_embeddings": True, "scale_emb": 12, "scale_depth": 1.4,
          "dim_model_base": 16, "torch_dtype": "float32"}
# CPU float32 against the float32 reference: these limits only part a
# sound run from a broken one
LIMITS = {"loss_gap": {"limit": 1e-3}, "clip_gap": {"limit": 0.05},
          "grad_gap": {"limit": 0.2}, "change_gap": {"limit": 0.05},
          "norm_gap": {"limit": 1e-3}, "cross_gap": {"limit": 1e-2}}


def read(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture
def tiny_registry(monkeypatch):
    """`minicpm-2b` in the registry at the tiny config's widths, muP and
    tied as published."""
    import jax.numpy as jnp
    import repro.configs as configs
    orig = configs.get_config
    small = dataclasses.replace(
        configs.get_config("minicpm-2b", reduced=True), d_model=64,
        d_ff=128, vocab_size=257, num_heads=4, num_kv_heads=4,
        dim_model_base=16, dtype=jnp.float32)

    def get_config(arch, **kw):
        return small if arch == "minicpm-2b" else orig(arch, **kw)

    monkeypatch.setattr(configs, "get_config", get_config)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark copied with one tiny MiniCPM flat-clipping cell."""
    r = tiny.make(str(tmp_path_factory.mktemp("bench")))
    bench = read(os.path.join(r, "BENCHMARK.json"))
    bench["configs"] = [{"name": "minicpm-tiny", "source": "test",
                         "file": "bench/configs/minicpm-tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "minicpm-tiny",
                           "traffic": "tiny_flat", "chips": 1,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    tr = read(TRAFFIC)
    tr.update(seq=32, rows=64, backend="xla", clip_leaf_min=0)
    # every example clipped, so that a wrong norm shows in the sums
    tr["dp"] = dict(tr["dp"], init_threshold=0.01)
    files = {"BENCHMARK.json": bench,
             "bench/configs/minicpm-tiny.json": CONFIG,
             "bench/traffic/tiny_flat.json": tr,
             f"bench/limits/{CELL}.json": LIMITS}
    for path, doc in files.items():
        with open(os.path.join(r, path), "w") as fh:
            json.dump(doc, fh)
    return r


# --- the reference ----------------------------------------------------------


def _reference(root, **kw):
    import jax
    cell = harness.find_cell(root, CELL)
    ref = harness.load_module(root, "reference", cell.cfg["family"])
    m = ref.Dims.of(cell.cfg)
    params = ref.init_params(m, jax.random.PRNGKey(3), "float32")
    dp = ref.DPReference(m, ref.Job.of(cell.traffic), **kw)
    rows = np.random.default_rng(0).integers(0, 12, (4, 33))  # repeats
    targets = rows[:, 1:].copy()
    targets[:, -4:] = -1
    batches = [(rows[i:i + 2, :-1], targets[i:i + 2]) for i in (0, 2)]
    return ref, m, dp, params, batches


def test_the_reference_step_is_per_example_jax_grad_clipped_flat(root):
    """Norms, cross term and the clipped sums of the reference's flat step
    against each example's jax.grad of the tied loss, clipped by hand."""
    import jax
    import jax.numpy as jnp
    ref, m, dp, params, (a, b) = _reference(root)
    flat = ref.flatten(params)
    c = dp.job.threshold

    def clipped(tokens, targets):
        out, norms = None, []
        for i in range(tokens.shape[0]):
            g = jax.grad(lambda p: ref.example_loss(
                m, ref.nest(p), jnp.asarray(tokens[i]),
                jnp.asarray(targets[i])))(flat)
            sq = sum(float(jnp.sum(v * v)) for v in g.values())
            norms.append(float(jnp.sum(g[ref.EMBED] ** 2)))
            f = min(1.0, c / math.sqrt(sq))
            out = ({p: v * f for p, v in g.items()} if out is None else
                   {p: out[p] + v * f for p, v in g.items()})
        return out, norms

    sa, na = clipped(*a)
    sb, _ = clipped(*b)
    seen = {}
    dp.step(dp.initial_state(params), *a, jax.random.PRNGKey(1), other=b,
            read=lambda p, e, s: seen.__setitem__(p, np.asarray(e)))
    emb = dp.offsets[ref.EMBED][0]
    np.testing.assert_allclose(dp.readings["norms"][:, emb], na, rtol=1e-5)
    assert np.all(np.abs(dp.readings["cross"]) > 0)
    for p, exact in seen.items():
        want = (sa[p] - sb[p]) / dp.job.batch
        np.testing.assert_allclose(exact, want, rtol=1e-4,
                                   atol=1e-6 * float(np.abs(want).max()))


def test_no_cross_leaves_out_twice_the_cross_term(root):
    import jax
    got = {}
    for fault in (None, "no_cross"):
        ref, m, dp, params, (a, _) = _reference(root, fault=fault)
        dp.step(dp.initial_state(params), *a, jax.random.PRNGKey(1))
        got[fault] = dp.readings
    emb = dp.offsets[ref.EMBED][0]
    sound, fault = got[None], got["no_cross"]
    assert np.all(fault["cross"] == 0)
    np.testing.assert_allclose(
        sound["norms"][:, emb] - fault["norms"][:, emb],
        2 * sound["cross"], rtol=1e-3)


def test_no_head_leaves_the_head_use_out_of_the_tied_norm(root):
    """The fault's tied norm is the embedding use's alone: each example's
    jax.grad of the table with the head held at the same value."""
    import jax
    import jax.numpy as jnp
    ref, m, dp, params, (a, _) = _reference(root, fault="no_head")
    flat = ref.flatten(params)
    want = []
    for i in range(a[0].shape[0]):
        g = jax.grad(lambda t: ref.example_loss(
            m, ref.nest({**flat, ref.EMBED: t}), jnp.asarray(a[0][i]),
            jnp.asarray(a[1][i]), head=flat[ref.EMBED]))(flat[ref.EMBED])
        want.append(float(jnp.sum(g * g)))
    dp.step(dp.initial_state(params), *a, jax.random.PRNGKey(1))
    emb = dp.offsets[ref.EMBED][0]
    assert np.all(dp.readings["cross"] == 0)
    np.testing.assert_allclose(dp.readings["norms"][:, emb], want,
                               rtol=1e-5)


# --- the FLOP model and the traffic -----------------------------------------


def test_hand_counts():
    c = read(FILE)
    # 6 x (2304x6912 + 2304x2304 + 2304x11520 + 5760x2304) + 2304x122753
    # (the tied head, once) = 649,103,616; with the norm scales,
    # 6 x 2 x 2304 + 2304 = 29,952, 649,133,568
    assert flops.matmul_params(c) == 649_103_616
    assert flops.params(c) == 649_133_568
    # 6 x 4096 x 649,103,616 + 3 x 4 x 2 x 2048^2 x 36 x 64 x 6 layers
    assert flops.train_step_flops(c, 2, 2048) == pytest.approx(1.7344e13,
                                                               rel=1e-4)


def test_the_traffic_file_gives_seeded_rows_of_the_cell():
    tr = read(TRAFFIC)
    big = 2 ** 40 + 13
    rows = generate.train_rows(tr, read(FILE)["vocab_size"], big)
    assert rows.shape == (tr["rows"], tr["seq"]) == (1024, 2048)
    assert rows.min() >= 0 and rows.max() < 122753
    assert np.array_equal(rows[:4], generate.train_rows(tr, 122753, big)[:4])
    assert not np.array_equal(rows[:4],
                              generate.train_rows(tr, 122753, big + 1)[:4])


# --- the driver -----------------------------------------------------------


def test_the_driver_accepts_the_registry_as_published():
    drv = harness.load_module(REPO, "drivers", "train_step_flat")
    pc = drv.check_mup(read(FILE))
    assert pc.num_layers == 6 and pc.tie_embeddings
    assert pc.residual_multiplier == pytest.approx(1.4 / math.sqrt(40))


@pytest.mark.parametrize("key,value", [
    ("scale_emb", 10), ("scale_depth", 1.0),
    ("published_num_hidden_layers", 6), ("dim_model_base", 2304),
    ("tie_word_embeddings", False)])
def test_the_driver_refuses_a_registry_that_departs_from_the_file(key,
                                                                  value):
    drv = harness.load_module(REPO, "drivers", "train_step_flat")
    cfg = dict(read(FILE), **{key: value})
    with pytest.raises(harness.CellError):
        drv.check_mup(cfg)


def _run(root, capsys, seed):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3"], root=root, allow_cpu=True, cache=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("drop", [False, True])
def test_a_tiny_run_is_correct_unless_the_cross_term_is_dropped(
        root, capsys, monkeypatch, tiny_registry, drop):
    if drop:
        import jax.numpy as jnp
        from repro.core import bk
        monkeypatch.setattr(bk, "tied_cross",
                            lambda g, a, kst: jnp.zeros((g.shape[0],)))
    line = _run(root, capsys, 2 ** 35 + 29)
    assert set(line["compared"]) == set(LIMITS)
    failed = {k for k, c in line["compared"].items()
              if not c["value"] <= c["limit"]}
    if drop:
        assert line["correct"] is False and "cross_gap" in failed
        assert line["compared"]["cross_gap"]["value"] == pytest.approx(1.0)
    else:
        assert line["correct"] is True and not failed


def test_the_calibration_parts_the_faults_from_the_program(root,
                                                           tiny_registry):
    rows = calibrate.calibrate(root, CELL, [2 ** 36 + 7], allow_cpu=True)
    limits = {k: v["limit"] for k, v in LIMITS.items()}

    def failed(reading):
        return {k for k, lim in limits.items() if not reading[k] <= lim}

    row = rows[0]
    assert not failed(row["program"])
    for name in ("control", "half_sum", "norm_sq", "half_batch", "no_cross",
                 "no_head"):
        assert failed(row[name]), name
    assert row["no_cross"]["cross_gap"] == 1.0
    assert {"norm_gap", "cross_gap"} <= failed(row["no_cross"])
    assert {"norm_gap", "cross_gap"} <= failed(row["no_head"])
    assert all(c != 0 for c in row["reference_cross"])

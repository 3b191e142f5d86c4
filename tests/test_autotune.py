"""Measured kernel autotuning (repro.kernels.autotune): table persistence
discipline (versioned, topology-stamped, checksummed, never crashes on a
bad file), record/argmin semantics, and the acceptance bar — the
auto backend's choice equals the measured argmin on every measured bucket,
on any jax backend, with the static model only deciding unmeasured ones."""
import json
import os

import jax
import numpy as np
import pytest

from repro.kernels import autotune, backend


def _tab(path=None, topology=None):
    return autotune.AutotuneTable(
        topology=topology or autotune.topology_stamp(), path=path)


# ---------------------------------------------------------------------------
# Bucketing.
# ---------------------------------------------------------------------------


def test_bucket_dim_next_pow2():
    assert [autotune.bucket_dim(n) for n in (0, 1, 2, 3, 127, 128, 129)] \
        == [0, 1, 2, 4, 128, 128, 256]


def test_bucket_key_covers_the_bucket():
    # one measurement covers every shape in its power-of-two bucket
    assert autotune.bucket_key("norms", 100, 64, 48) \
        == autotune.bucket_key("norms", 128, 64, 64)
    assert autotune.bucket_key("norms", 129, 64, 64) \
        != autotune.bucket_key("norms", 128, 64, 64)
    assert autotune.bucket_key("norms", 128, 64, 64) \
        != autotune.bucket_key("clip_sum", 128, 64, 64)


# ---------------------------------------------------------------------------
# Record / best semantics.
# ---------------------------------------------------------------------------


def test_record_and_best_argmin():
    tab = _tab()
    tab.record("norms", 128, 64, 64, "xla", 100.0)
    tab.record("norms", 128, 64, 64, "pallas", 50.0)
    assert tab.best("norms", 128, 64, 64) == "pallas"
    assert tab.best("norms", 4096, 64, 64) is None  # unmeasured bucket
    # refreshing a measurement updates it
    tab.record("norms", 128, 64, 64, "pallas", 500.0)
    assert tab.best("norms", 128, 64, 64) == "xla"


def test_record_rejects_garbage():
    tab = _tab()
    with pytest.raises(ValueError):
        tab.record("norms", 128, 64, 64, "cuda", 1.0)
    for bad in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError):
            tab.record("norms", 128, 64, 64, "xla", bad)


# ---------------------------------------------------------------------------
# Persistence: round trip + every staleness mode loads EMPTY, never raises.
# ---------------------------------------------------------------------------


def test_save_load_round_trip(tmp_path):
    path = str(tmp_path / "tab.json")
    tab = _tab(path)
    tab.record("norms", 128, 64, 64, "pallas", 42.0)
    tab.record("paged_attn", 256, 64, 64, "xla", 7.0)
    tab.save()
    back = autotune.load(path)
    assert back.stale_reason is None
    assert back.entries == tab.entries
    assert back.best("norms", 100, 33, 64) == "pallas"  # same bucket


@pytest.mark.parametrize("breakage", [
    "missing", "not_json", "truncated", "crc", "version", "topology",
    "not_dict"])
def test_stale_or_corrupt_loads_empty(tmp_path, breakage):
    path = str(tmp_path / "tab.json")
    tab = _tab(path)
    tab.record("norms", 128, 64, 64, "pallas", 42.0)
    tab.save()
    if breakage == "missing":
        os.unlink(path)
    elif breakage == "not_json":
        open(path, "w").write("))) not json (((")
    elif breakage == "truncated":
        blob = open(path).read()
        open(path, "w").write(blob[: len(blob) // 2])
    elif breakage == "crc":
        doc = json.load(open(path))
        doc["entries"]["norms|t128|i64|o64"]["pallas"]["us"] = 1e-9
        json.dump(doc, open(path, "w"))  # edited without re-checksumming
    elif breakage == "version":
        doc = json.load(open(path))
        doc["version"] = autotune.TABLE_VERSION + 1
        json.dump(doc, open(path, "w"))
    elif breakage == "topology":
        pass  # broken via the load-side topology below
    elif breakage == "not_dict":
        json.dump([1, 2, 3], open(path, "w"))
    topo = autotune.topology_stamp()
    if breakage == "topology":
        topo = dict(topo, jax_version="0.0.0", device_count=8192)
    back = autotune.load(path, topology=topo)
    assert back.stale_reason is not None
    assert len(back) == 0
    assert back.best("norms", 128, 64, 64) is None  # clean miss
    # ...and the next sweep/save simply rebuilds the file
    back.record("norms", 128, 64, 64, "xla", 9.0)
    back.save()
    again = autotune.load(path, topology=topo)
    assert again.stale_reason is None and len(again) == 1


def test_save_is_atomic_no_tmp_left(tmp_path):
    path = str(tmp_path / "tab.json")
    tab = _tab(path)
    tab.record("norms", 128, 64, 64, "xla", 1.0)
    tab.save()
    assert not os.path.exists(path + ".tmp")


# ---------------------------------------------------------------------------
# choose_op: measured argmin wins on ANY backend; static model is the
# unmeasured fallback (the old non-TPU short-circuit lives there only).
# ---------------------------------------------------------------------------


def test_choose_op_equals_measured_argmin_everywhere():
    """Acceptance: over a synthetic table with KNOWN winners per bucket,
    auto's choice == the measured argmin on EVERY measured bucket —
    including pallas wins off-TPU, which the static model would never
    pick."""
    tab = _tab()
    want = {}
    rng = np.random.RandomState(0)
    for op in autotune.OPS:
        for t, d in ((128, 64), (256, 128), (1024, 512)):
            xla_us, pal_us = 1.0 + rng.rand(2) * 100
            tab.record(op, t, d, d, "xla", float(xla_us))
            tab.record(op, t, d, d, "pallas", float(pal_us))
            want[(op, t, d)] = "xla" if xla_us <= pal_us else "pallas"
    cfg = backend.EngineConfig(backend="auto")
    for (op, t, d), winner in want.items():
        for on_tpu in (False, True):
            assert backend.choose_op(op, t, d, d, cfg, on_tpu=on_tpu,
                                     table=tab) == winner, (op, t, d)
    assert any(w == "pallas" for w in want.values())  # exercised both ways
    assert any(w == "xla" for w in want.values())


def test_choose_op_unmeasured_falls_back_to_static_model():
    tab = _tab()
    tab.record("norms", 128, 64, 64, "pallas", 1.0)
    cfg = backend.EngineConfig(backend="auto")
    # unmeasured bucket off-TPU: the validation-only short-circuit applies
    assert backend.choose_op("norms", 4096, 1024, 1024, cfg, on_tpu=False,
                             table=tab) == "xla"
    assert backend.choose_op("paged_attn", 4096, 64, 64, cfg, on_tpu=False,
                             table=tab) == "xla"
    assert backend.choose_op("paged_attn", 4096, 64, 64, cfg, on_tpu=True,
                             table=tab) == "pallas"
    # ...but the MEASURED bucket honors the interpret-mode win off-TPU
    assert backend.choose_op("norms", 128, 64, 64, cfg, on_tpu=False,
                             table=tab) == "pallas"


def test_autotune_off_pins_static_model():
    tab = _tab()
    tab.record("norms", 128, 64, 64, "pallas", 1.0)
    cfg = backend.EngineConfig(backend="auto", autotune=False)
    assert backend.choose_op("norms", 128, 64, 64, cfg, on_tpu=False,
                             table=tab) == "xla"


def test_no_table_installed_matches_legacy_static_choice():
    assert autotune.installed_table() is None
    cfg = backend.EngineConfig(backend="auto")
    assert backend.choose_op("norms", 128, 64, 64, cfg, on_tpu=False) \
        == backend.choose_linear_path(128, 64, 64, cfg, on_tpu=False)


# ---------------------------------------------------------------------------
# Installation plumbing.
# ---------------------------------------------------------------------------


def test_install_and_use_table_scoping():
    base, override = _tab(), _tab()
    base.record("norms", 128, 64, 64, "xla", 1.0)
    override.record("norms", 128, 64, 64, "pallas", 1.0)
    try:
        autotune.install(base)
        assert autotune.installed_table() is base
        with autotune.use_table(override):
            assert autotune.installed_table() is override
        assert autotune.installed_table() is base
    finally:
        autotune.install(None)
    assert autotune.installed_table() is None


def test_install_default_survives_stale_file(tmp_path):
    root = str(tmp_path)
    # no file at all -> empty table installed, auto == static model
    try:
        tab = autotune.install_default(root)
        assert len(tab) == 0 and tab.stale_reason == "missing"
        # garbage on disk -> still an empty install, never a crash
        os.makedirs(os.path.dirname(tab.path), exist_ok=True)
        open(tab.path, "w").write("garbage")
        tab2 = autotune.install_default(root)
        assert len(tab2) == 0 and tab2.stale_reason is not None
    finally:
        autotune.install(None)


# ---------------------------------------------------------------------------
# The AutoBackend actually dispatches (and stays numerically right) on a
# table-driven choice.
# ---------------------------------------------------------------------------


def test_auto_backend_dispatch_and_value_parity_under_table():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2, 128, 64))
    g = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 48)) * 0.1
    f = jax.random.uniform(jax.random.fold_in(key, 2), (2,))
    tab = _tab()
    tab.record("clip_sum", 128, 64, 64, "pallas", 1.0)  # dout 48 -> o64
    tab.record("clip_sum", 128, 64, 64, "xla", 2.0)
    with backend.scoped("auto"):
        eng = backend.active()
        assert eng._pick("clip_sum", a, g) is eng._xla  # static: off-TPU
        with autotune.use_table(tab):
            assert eng._pick("clip_sum", a, g) is eng._pallas
            got = eng.clipped_sum_linear(a, g, f)
    ref = backend.make_engine("xla").clipped_sum_linear(a, g, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_auto_paged_impl_hint_consults_table():
    args = autotune.paged_attn_data((2, 128, 64, 64))
    q, kp, vp, pt, pos = args
    t, din, dout = autotune.paged_attn_dims(q, pt, kp.shape[1], vp.shape[-1])
    tab = _tab()
    tab.record("paged_attn", t, din, dout, "pallas", 1.0)
    tab.record("paged_attn", t, din, dout, "xla", 2.0)
    eng = backend.make_engine("auto")
    assert eng.paged_impl() == "xla"  # no hints off-TPU: static rule
    assert eng.paged_impl(t=t, din=din, dout=dout) == "xla"  # no table
    with autotune.use_table(tab):
        assert eng.paged_impl(t=t, din=din, dout=dout) == "pallas"
        got = eng.paged_attn(q, kp, vp, pt, pos, scale=0.125)
    ref = backend.make_engine("xla").paged_attn(q, kp, vp, pt, pos,
                                                scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The live sweep.
# ---------------------------------------------------------------------------


def test_sweep_measures_and_persists(tmp_path):
    path = str(tmp_path / "tab.json")
    tab = autotune.sweep(ops=("norms",), shapes=((2, 128, 64, 64),),
                         table=_tab(path))
    slot = tab.lookup("norms", 128, 64, 64)
    assert set(slot) == {"xla", "pallas"}
    assert all(v["us"] > 0 for v in slot.values())
    back = autotune.load(path)
    assert back.entries == tab.entries
    assert back.best("norms", 128, 64, 64) in ("xla", "pallas")


# ---------------------------------------------------------------------------
# Topology stamp.
# ---------------------------------------------------------------------------


def test_topology_stamp_keys_and_crc_stability():
    stamp = autotune.topology_stamp()
    assert set(stamp) == {"jax_backend", "device_kind", "device_count",
                          "xla_flags", "jax_version"}
    assert stamp["jax_version"] == jax.__version__
    assert autotune.stamp_crc(stamp) == autotune.stamp_crc(stamp)
    assert autotune.stamp_crc(dict(stamp, device_count=8192)) \
        != autotune.stamp_crc(stamp)
    # a topology change moves the default table path: clean miss on disk too
    assert autotune.default_path("/x", stamp) \
        != autotune.default_path("/x", dict(stamp, jax_version="0.0.0"))

"""Persistent compile cache (repro.launch.compile_cache): manifest/sweep
integrity discipline (corrupt entries deleted and rebuilt warm, never a
crash), jax-version staleness, the semantic program index, and the
acceptance bar — cached and uncached executions are BITWISE identical for
the DP train step and the decode engine."""
import hashlib
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache as cc


@pytest.fixture(autouse=True)
def _no_external_dir(monkeypatch):
    """These cases place the cache themselves: a JAX_COMPILATION_CACHE_DIR
    inherited from the environment would otherwise take precedence."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


@pytest.fixture
def cache_off():
    """Guarantee the process-global jax cache config is restored."""
    yield
    cc.disable()


def _valid_blob(data=b"fake executable"):
    """Bytes in jax's on-disk entry format (compressed, time-framed) —
    what a COMPLETE write leaves. Adoption decode-validates, so fakes
    must be decodable."""
    from jax._src import compilation_cache as jcc
    return jcc.compress_executable(jcc.combine_executable_and_time(data, 1))


def _fake_entry(dirpath, name, blob=None):
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, name + "-cache")
    with open(path, "wb") as fh:
        fh.write(_valid_blob(name.encode()) if blob is None else blob)
    return path


# ---------------------------------------------------------------------------
# Semantic program key / index.
# ---------------------------------------------------------------------------


def test_program_key_stable_and_order_independent():
    a = cc.program_key(entry="train", arch="tiny", mesh="none")
    b = cc.program_key(mesh="none", arch="tiny", entry="train")
    assert a == b
    assert cc.program_key(entry="serve", arch="tiny", mesh="none") != a
    assert cc.program_key(entry="train", arch="tiny", mesh="none",
                          jax_version="0.0.0") != a


def test_record_program_round_trip(tmp_path):
    root = str(tmp_path)
    key = cc.record_program({"entry": "train", "arch": "tiny"}, root=root)
    cc.record_program({"entry": "train", "arch": "tiny"}, root=root)
    cc.record_program({"entry": "serve", "arch": "tiny"}, root=root)
    progs = cc.warmed_programs(root)
    assert progs[key]["runs"] == 2
    assert progs[key]["parts"]["entry"] == "train"
    assert len(progs) == 2


def test_record_program_survives_torn_index(tmp_path):
    root = str(tmp_path)
    os.makedirs(cc.compile_dir(root), exist_ok=True)
    open(os.path.join(cc.compile_dir(root), "programs.json"),
         "w").write("{torn")
    assert cc.record_program({"entry": "train"}, root=root) is not None
    assert len(cc.warmed_programs(root)) == 1


# ---------------------------------------------------------------------------
# Sweep: adopt / keep / drop-corrupt / drop-missing / stale-jax wipe.
# ---------------------------------------------------------------------------


def test_sweep_adopts_then_keeps(tmp_path):
    d = str(tmp_path / "compile")
    _fake_entry(d, "aaa")
    _fake_entry(d, "bbb")
    stats = cc.sweep(d)
    assert stats == {"kept": 0, "adopted": 2, "dropped_corrupt": 0,
                     "dropped_missing": 0, "wiped_stale_jax": 0}
    stats = cc.sweep(d)  # idempotent second pass: everything known
    assert stats["kept"] == 2 and stats["adopted"] == 0


def test_sweep_deletes_corrupt_entry_for_warm_rebuild(tmp_path):
    d = str(tmp_path / "compile")
    good = _fake_entry(d, "good")
    bad = _fake_entry(d, "bad")
    open(bad[:-len("-cache")] + "-atime", "w").write("0")
    cc.sweep(d)
    with open(bad, "ab") as fh:  # bit rot after the manifest was written
        fh.write(b"XXXX")
    stats = cc.sweep(d)
    assert stats["dropped_corrupt"] == 1 and stats["kept"] == 1
    assert not os.path.exists(bad)  # jax recompiles warm, no per-start warn
    assert not os.path.exists(bad[:-len("-cache")] + "-atime")
    assert os.path.exists(good)
    # the corrupt entry is gone from the manifest too, not double-counted
    stats = cc.sweep(d)
    assert stats == {"kept": 1, "adopted": 0, "dropped_corrupt": 0,
                     "dropped_missing": 0, "wiped_stale_jax": 0}


def test_sweep_never_adopts_torn_entry(tmp_path):
    """A process killed mid-write (jax's entry write is NOT atomic — the
    service fault injection hits this for real) leaves a truncated
    compressed stream. Adopting it would hand XLA's C++ deserializer
    bytes that ABORT the process, so the sweep must delete it instead;
    the executable then rebuilds warm."""
    d = str(tmp_path / "compile")
    whole = _valid_blob(b"compiled program")
    _fake_entry(d, "ok")
    torn = _fake_entry(d, "torn", blob=whole[: len(whole) // 2])
    open(torn[:-len("-cache")] + "-atime", "wb").write(b"\0" * 8)
    stats = cc.sweep(d)
    assert stats["dropped_corrupt"] == 1 and stats["adopted"] == 1
    assert not os.path.exists(torn)
    assert not os.path.exists(torn[:-len("-cache")] + "-atime")
    stats = cc.sweep(d)  # gone from the manifest, not double-counted
    assert stats == {"kept": 1, "adopted": 0, "dropped_corrupt": 0,
                     "dropped_missing": 0, "wiped_stale_jax": 0}


def test_sweep_drops_vanished_entries(tmp_path):
    d = str(tmp_path / "compile")
    keep = _fake_entry(d, "keep")
    gone = _fake_entry(d, "gone")
    cc.sweep(d)
    os.unlink(gone)
    stats = cc.sweep(d)
    assert stats["dropped_missing"] == 1 and stats["kept"] == 1
    assert os.path.exists(keep)


def test_sweep_rebuilds_torn_manifest_by_adoption(tmp_path):
    d = str(tmp_path / "compile")
    _fake_entry(d, "aaa")
    cc.sweep(d)
    open(os.path.join(d, "manifest.json"), "w").write("{torn json")
    stats = cc.sweep(d)  # never a crash; files re-adopted
    assert stats["adopted"] == 1 and stats["kept"] == 0
    stats = cc.sweep(d)
    assert stats["kept"] == 1


def test_sweep_wipes_entries_from_another_jax(tmp_path):
    d = str(tmp_path / "compile")
    _fake_entry(d, "old")
    open(os.path.join(d, "old-atime"), "w").write("0")
    # a manifest legitimately written (crc OK) by a different jax version
    payload = {"version": cc.MANIFEST_VERSION, "jax_version": "0.0.0",
               "entries": {"old-cache": 123}}
    blob = json.dumps(payload, sort_keys=True)
    json.dump({"crc32": zlib.crc32(blob.encode()), **payload},
              open(os.path.join(d, "manifest.json"), "w"))
    stats = cc.sweep(d)
    assert stats["wiped_stale_jax"] == 2  # entry + its atime companion
    assert not os.path.exists(os.path.join(d, "old-cache"))
    # fresh entries written under THIS jax adopt cleanly afterwards
    _fake_entry(d, "new")
    assert cc.sweep(d)["adopted"] == 1


# ---------------------------------------------------------------------------
# enable(): end-to-end against the real jax cache, corruption included.
# ---------------------------------------------------------------------------


def test_enable_populates_and_survives_corruption(tmp_path, cache_off):
    root = str(tmp_path)
    assert cc.enable(root) == cc.compile_dir(root)
    assert cc.enabled_dir() == cc.compile_dir(root)

    def f(x):
        return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((64, 64))
    first = jax.jit(f)(x)
    entries = [n for n in os.listdir(cc.compile_dir(root))
               if n.endswith("-cache")]
    assert entries, "persistent cache wrote no entries"
    assert cc.sweep(cc.compile_dir(root))["adopted"] == len(entries)
    # corrupt every entry; re-enable must sweep them out and a fresh trace
    # must still produce the right answer (warm rebuild, no crash)
    for name in entries:
        with open(os.path.join(cc.compile_dir(root), name), "r+b") as fh:
            fh.seek(0)
            fh.write(b"\x00" * 16)
    stats = cc.sweep(cc.compile_dir(root))
    assert stats["dropped_corrupt"] == len(entries)
    assert cc.enable(root) is not None
    again = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())(x)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))


def test_enable_is_best_effort_on_unwritable_root(tmp_path, cache_off):
    blocker = tmp_path / "flat"
    blocker.write_text("not a directory")
    with pytest.warns(UserWarning, match="compile cache disabled"):
        assert cc.enable(str(blocker)) is None  # reported, not raised
    assert cc.enabled_dir() is None


def test_external_cache_dir_is_used_and_never_swept(tmp_path, monkeypatch,
                                                    cache_off):
    """JAX_COMPILATION_CACHE_DIR wins over the repo-local root, and the
    directory is left exactly as its owner put it: no sweep, no deletes,
    no manifest — even of an entry the sweep would call torn."""
    ext = tmp_path / "external"
    torn = _fake_entry(str(ext), "torn", blob=b"not a cache entry")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ext))
    assert cc.compile_dir(str(tmp_path / "root")) == str(ext)
    assert cc.enable(str(tmp_path / "root")) == str(ext)
    assert jax.config.jax_compilation_cache_dir == str(ext)
    assert os.path.exists(torn)
    assert sorted(os.listdir(ext)) == ["torn-cache"]
    assert not (tmp_path / "root").exists()


# ---------------------------------------------------------------------------
# Acceptance: cached vs uncached executions are BITWISE identical.
# ---------------------------------------------------------------------------


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _train_step_digest():
    """Trace a FRESH tiny DP train step (new closures -> new trace; with
    the cache enabled the compile deserializes from disk) and digest the
    updated params + metrics."""
    from repro import optim
    from repro.configs import get_config
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import init_params
    from repro.launch.inputs import concrete_train_batch
    from repro.models.transformer import build_model

    cfg = get_config("tiny")
    m = build_model(cfg)
    params = init_params(m.spec, jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 4, 16, jax.random.PRNGKey(1))
    dpc = DPConfig(mode="per_layer", sigma=1.0, sampling_rate=0.1, steps=10,
                   adaptive=True)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=4)
    opt_state, dp_state = init_fn(params)
    p2, _, _, met = jax.jit(step_fn)(params, opt_state, dp_state, batch,
                                     jax.random.PRNGKey(5))
    return _digest((p2, met.loss))


def _engine_tokens():
    from repro.configs import get_config
    from repro.core.spec import init_params
    from repro.launch.engine import DecodeEngine
    from repro.launch.inputs import synthetic_requests
    from repro.models.transformer import build_model

    cfg = get_config("tiny")
    model = build_model(cfg)
    params = init_params(model.spec, jax.random.PRNGKey(0))
    reqs = synthetic_requests(cfg.vocab_size, 2, min_len=1, max_len=6,
                              seed=7)
    eng = DecodeEngine(model, params, num_slots=2, cache_len=32,
                       prefill_chunk=4)
    rids = [eng.submit(r, max_new_tokens=4) for r in reqs]
    done = eng.run()
    return [done[r].tokens for r in rids]


def test_train_step_bitwise_identical_cached_vs_uncached(tmp_path,
                                                         cache_off):
    cold = _train_step_digest()  # uncached baseline
    assert cc.enable(str(tmp_path)) is not None
    compiling = _train_step_digest()  # populates the cache
    warm = _train_step_digest()  # deserializes from it
    assert cold == compiling == warm


def test_engine_decode_bitwise_identical_cached_vs_uncached(tmp_path,
                                                            cache_off):
    cold = _engine_tokens()
    assert cc.enable(str(tmp_path)) is not None
    compiling = _engine_tokens()
    warm = _engine_tokens()
    assert cold == compiling == warm

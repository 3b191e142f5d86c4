"""Persistent compile/startup cache for the jitted train/serve programs.

PR 3 measured ~19s of retrace+compile for ONE production dryrun — and every
train/serve/service worker re-pays that cold at startup. This module wires
jax's persistent compilation cache to a repo-local directory so compiled
executables survive the process: the second (and every later) startup
deserializes instead of recompiling. Fleet economics: bake the populated
cache directory into the worker image and thousands of workers skip both
autotuning (repro.kernels.autotune) and compilation.

What jax's cache keys on already subsumes our semantic key — the post-
optimization HLO module, compile options, jax/jaxlib version, and the
accelerator config all hash into the entry name — so a change to the model
config, mesh, ghost backend, BK execution, clipping mode, or jax version
produces a different module hash and therefore a CLEAN MISS (recompile),
never a stale hit. On top of that this module adds:

  * an integrity sweep with the crc32 discipline from the PR 6 checkpoint
    store: ``manifest.json`` records a checksum per cache entry; at
    `enable()` time corrupt/truncated entries are silently deleted (jax
    would only warn-and-recompile, but a torn file would otherwise warn on
    EVERY startup forever) and new entries from previous runs are adopted.
    A jax-version change wipes the dead entries wholesale. The manifest
    itself is checksummed and rebuilt from the files if torn.
  * a ``programs.json`` index mapping our SEMANTIC key — (entry point,
    model config, mesh, backend, execution, clipping mode, jax version) —
    to run counts, so an operator can see which programs a cache warm-up
    actually covered (`warmed_programs()`).

Entry points call `enable()` under their ``--cache`` knob (train, serve,
service, dryrun) and `record_program()` after building their step. The
directory rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache, as JAX
    itself reads it. It belongs to whoever set it, so it is never swept
    and nothing in it is deleted.
  * unset: ``<repo>/.cache/compile`` (``REPRO_CACHE_DIR`` or
    ``--cache-dir`` move the cache root), swept as above. The path is
    fixed so that every run of the checkout finds the same entries.

A cache root that cannot be used is reported with a warning and the run
compiles uncached.
"""
from __future__ import annotations

import json
import os
import warnings
import zlib

import jax

MANIFEST_VERSION = 1
_MANIFEST = "manifest.json"
_PROGRAMS = "programs.json"

_ENABLED_DIR: str | None = None


def cache_root(override: str | None = None) -> str:
    from repro.kernels.autotune import repo_cache_root
    return repo_cache_root(override)


def external_dir() -> str | None:
    """The cache directory placed from outside (JAX_COMPILATION_CACHE_DIR),
    or None."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def compile_dir(root: str | None = None) -> str:
    return external_dir() or os.path.join(cache_root(root), "compile")


def program_key(**parts) -> str:
    """Stable id for one compiled program's semantic coordinates."""
    blob = json.dumps({k: str(v) for k, v in sorted(parts.items())},
                      sort_keys=True)
    return f"{zlib.crc32(blob.encode()):08x}"


# ---------------------------------------------------------------------------
# Integrity sweep (crc32 manifest over the serialized executables).
# ---------------------------------------------------------------------------


def _file_crc(path: str) -> int:
    crc = 0
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            crc = zlib.crc32(chunk, crc)
    return crc


def _entry_decodes(path: str) -> bool:
    """Can jax's cache layer decode this entry's compressed payload?

    jax writes cache entries with a plain (NON-atomic) write_bytes, so a
    process killed mid-write — exactly what the service's fault injection
    does — leaves a truncated compressed stream on disk. XLA's C++
    executable deserializer can ABORT the whole process on such bytes
    (heap corruption, not a catchable error), so a torn entry must never
    be adopted into the manifest. The compression checksum (zstd frame /
    zlib adler32) reliably rejects any truncation."""
    from jax._src import compilation_cache as jcc
    try:
        with open(path, "rb") as fh:
            raw = fh.read()
    except OSError:
        return False
    try:
        jcc.extract_executable_and_time(jcc.decompress_executable(raw))
        return True
    except Exception:  # noqa: BLE001 - torn/garbage payload
        return False


def _atomic_json(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


def _load_manifest(dirpath: str) -> dict | None:
    """The entries dict, or None if the manifest is missing/torn/stale
    (caller rebuilds from the files)."""
    path = os.path.join(dirpath, _MANIFEST)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("version") != MANIFEST_VERSION:
        return None
    payload = {"version": doc.get("version"),
               "jax_version": doc.get("jax_version"),
               "entries": doc.get("entries")}
    blob = json.dumps(payload, sort_keys=True)
    if zlib.crc32(blob.encode()) != doc.get("crc32"):
        return None
    if doc.get("jax_version") != jax.__version__:
        # serialized executables from another jax are dead weight: report
        # stale so the sweep wipes them (jax's key gives the clean miss
        # anyway; this keeps the directory from growing forever)
        return {"__stale_jax__": True}
    if not isinstance(doc.get("entries"), dict):
        return None
    return doc["entries"]


def _save_manifest(dirpath: str, entries: dict) -> None:
    payload = {"version": MANIFEST_VERSION, "jax_version": jax.__version__,
               "entries": entries}
    blob = json.dumps(payload, sort_keys=True)
    _atomic_json(os.path.join(dirpath, _MANIFEST),
                 {"crc32": zlib.crc32(blob.encode()), **payload})


def sweep(dirpath: str) -> dict:
    """Verify every cache entry against the manifest; delete corrupt or
    truncated files (they rebuild warm on next use), adopt entries written
    by previous processes, drop records for files that vanished. Returns
    {kept, adopted, dropped_corrupt, dropped_missing, wiped_stale_jax}."""
    os.makedirs(dirpath, exist_ok=True)
    manifest = _load_manifest(dirpath)
    stats = {"kept": 0, "adopted": 0, "dropped_corrupt": 0,
             "dropped_missing": 0, "wiped_stale_jax": 0}
    if manifest is not None and manifest.get("__stale_jax__"):
        # another jax wrote these executables: clean miss by construction,
        # so reclaim the space rather than verifying dead entries
        for name in os.listdir(dirpath):
            if name.endswith("-cache") or name.endswith("-atime"):
                try:
                    os.unlink(os.path.join(dirpath, name))
                    stats["wiped_stale_jax"] += 1
                except OSError:
                    pass
        manifest = {}
    if manifest is None:
        manifest = {}  # torn/missing manifest: rebuild by adoption below
    entries = {}
    corrupt: set[str] = set()
    for name in sorted(os.listdir(dirpath)):
        if not name.endswith("-cache"):
            continue
        path = os.path.join(dirpath, name)
        try:
            crc = _file_crc(path)
        except OSError:
            stats["dropped_missing"] += 1
            continue
        known = manifest.get(name)
        if known is None:
            # adoption is the integrity gate: entries already in the
            # manifest passed it once (crc covers bit rot thereafter)
            if _entry_decodes(path):
                entries[name] = crc
                stats["adopted"] += 1
            else:
                try:
                    os.unlink(path)
                except OSError:
                    pass
                atime = path[:-len("-cache")] + "-atime"
                if os.path.exists(atime):
                    try:
                        os.unlink(atime)
                    except OSError:
                        pass
                stats["dropped_corrupt"] += 1
                corrupt.add(name)
        elif known == crc:
            entries[name] = crc
            stats["kept"] += 1
        else:
            # bit rot / torn write: delete so jax recompiles warm instead
            # of warning about the undecodable entry on every startup
            try:
                os.unlink(path)
            except OSError:
                pass
            atime = path[:-len("-cache")] + "-atime"
            if os.path.exists(atime):
                try:
                    os.unlink(atime)
                except OSError:
                    pass
            stats["dropped_corrupt"] += 1
            corrupt.add(name)
    stats["dropped_missing"] += sum(1 for n in manifest
                                    if n.endswith("-cache")
                                    and n not in entries
                                    and n not in corrupt)
    _save_manifest(dirpath, entries)
    return stats


# ---------------------------------------------------------------------------
# Enable / disable.
# ---------------------------------------------------------------------------


def enable(root: str | None = None, *,
           min_compile_secs: float = 0.0) -> str | None:
    """Point jax's persistent compilation cache at the directory the rule in
    the module doc gives, sweeping it first unless it was placed from
    outside. Idempotent. Returns the directory, or None (with a warning)
    when it cannot be used — compilation then stays uncached."""
    global _ENABLED_DIR
    dirpath = compile_dir(root)
    if external_dir() is None:
        try:
            sweep(dirpath)
        except OSError as e:
            warnings.warn(f"compile cache disabled: cannot use {dirpath}: "
                          f"{type(e).__name__}: {e}")
            _ENABLED_DIR = None
            return None
    jax.config.update("jax_compilation_cache_dir", dirpath)
    # jax memoizes "is the cache used" at the FIRST compilation of the
    # process; a long-lived process (tests, notebooks) that compiled
    # anything before enable() has latched False — reset to pristine so
    # the new directory takes effect
    _reset_jax_cache_state()
    # default thresholds skip sub-second / small programs — the exact
    # programs a CPU test fleet compiles; cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _ENABLED_DIR = dirpath
    return dirpath


def disable() -> None:
    """Stop caching new compilations (tests; already-compiled programs are
    unaffected)."""
    global _ENABLED_DIR
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache_state()
    _ENABLED_DIR = None


def _reset_jax_cache_state() -> None:
    from jax.experimental.compilation_cache import compilation_cache as jcc
    jcc.reset_cache()


def enabled_dir() -> str | None:
    return _ENABLED_DIR


# ---------------------------------------------------------------------------
# Semantic program index.
# ---------------------------------------------------------------------------


def record_program(parts: dict, *, root: str | None = None) -> str | None:
    """Note that a program with these semantic coordinates compiled (or
    re-dispatched) under the cache; returns its key. Best-effort."""
    try:
        dirpath = _ENABLED_DIR or compile_dir(root)
        os.makedirs(dirpath, exist_ok=True)
        key = program_key(**parts)
        path = os.path.join(dirpath, _PROGRAMS)
        try:
            with open(path) as fh:
                doc = json.load(fh)
            if not isinstance(doc, dict):
                doc = {}
        except (OSError, ValueError):
            doc = {}
        row = doc.get(key) or {"parts": {k: str(v) for k, v in
                                         sorted(parts.items())}, "runs": 0}
        row["runs"] = int(row.get("runs", 0)) + 1
        doc[key] = row
        _atomic_json(path, doc)
        return key
    except Exception:  # noqa: BLE001
        return None


def warmed_programs(root: str | None = None) -> dict:
    """The semantic index: which (entry, config, mesh, backend, ...)
    programs this cache has seen, and how often."""
    try:
        with open(os.path.join(compile_dir(root), _PROGRAMS)) as fh:
            doc = json.load(fh)
        return doc if isinstance(doc, dict) else {}
    except (OSError, ValueError):
        return {}

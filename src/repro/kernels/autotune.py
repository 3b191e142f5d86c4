"""Measured kernel autotuning: an empirical table behind the `auto` backend.

The `auto` backend picks, per ghost op, between the xla reference path and
the pallas kernel. Two sources decide that choice:

  * the timing sweep (`sweep()` / ``python -m repro.kernels.autotune
    --sweep``) measures the registered backends per (op, shape-bucket) on
    representative data and records the median wall time; every row of
    the table is such a measurement, and its argmin wins on ANY jax
    backend (including the interpret-mode kernels off-TPU — if they
    measured faster, they are faster);
  * the static flop model `repro.kernels.backend.choose_linear_path`
    decides every bucket the sweep has not measured.

The table persists to a versioned on-disk JSON file keyed by the
**topology stamp** (jax backend, device kind, device count, XLA flags, jax
version) with a crc32 over the canonical payload — a table written on a
different topology, a different schema version, or a torn/corrupt file
loads as an EMPTY table (clean miss, never a crash) and is simply rebuilt
by the next sweep. Shapes are bucketed to the next power of two per
dimension so one measurement covers the whole bucket.

Installation is EXPLICIT: library code never reads the filesystem behind
your back. Entry points (train/serve/service CLIs) call
`install_default()` under their ``--autotune`` knob; tests scope a
synthetic table with `use_table(...)`. `EngineConfig.autotune=False`
disables consultation even with a table installed.
"""
from __future__ import annotations

import argparse
import contextlib
import contextvars
import dataclasses
import json
import os
import time
import zlib

import jax

TABLE_VERSION = 2

# every engine op the auto backend dispatches on
OPS = ("norms", "clip_sum", "linear_clip", "scale_contract", "paged_attn")

_BACKEND_CHOICES = ("xla", "pallas")


# ---------------------------------------------------------------------------
# Topology stamp + cache locations.
# ---------------------------------------------------------------------------


def device_kind() -> str:
    """The device kind measurements are conditioned on. Raises when JAX
    finds no device: a stamp must name the hardware it describes."""
    return jax.devices()[0].device_kind


def topology_stamp() -> dict:
    """What a timing measurement is conditioned on. Tables (and the
    compile-cache manifest) keyed on this stamp never leak measurements
    across machines, device counts, XLA flag sets, or jax versions."""
    return {
        "jax_backend": jax.default_backend(),
        "device_kind": device_kind(),
        "device_count": jax.device_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "jax_version": jax.__version__,
    }


def stamp_crc(stamp: dict | None = None) -> str:
    blob = json.dumps(stamp or topology_stamp(), sort_keys=True)
    return f"{zlib.crc32(blob.encode()):08x}"


def repo_cache_root(override: str | None = None) -> str:
    """Repo-local cache root: <repo>/.cache (REPRO_CACHE_DIR overrides).

    Repo-local on purpose: pre-warming a fleet image = building the image
    with this directory populated (docs: README "Autotuning & compilation
    cache")."""
    if override:
        return override
    env = os.environ.get("REPRO_CACHE_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))  # src/repro/kernels
    repo = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(repo, ".cache")


def default_path(cache_root: str | None = None,
                 stamp: dict | None = None) -> str:
    """One table file per topology: autotune/<stamp-crc>.json."""
    return os.path.join(repo_cache_root(cache_root), "autotune",
                        f"table-{stamp_crc(stamp)}.json")


# ---------------------------------------------------------------------------
# Shape bucketing.
# ---------------------------------------------------------------------------


def bucket_dim(n: int) -> int:
    """Next power of two (0 stays 0): one measurement covers the bucket."""
    n = int(n)
    if n <= 0:
        return 0
    return 1 << (n - 1).bit_length()


def bucket_key(op: str, t: int, din: int, dout: int) -> str:
    return f"{op}|t{bucket_dim(t)}|i{bucket_dim(din)}|o{bucket_dim(dout)}"


# ---------------------------------------------------------------------------
# The table.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AutotuneTable:
    """Bucketed (op, shape) -> {backend: {us}} timings for ONE
    topology. `best()` is the measured argmin; buckets it has never seen
    return None so the caller falls back to the static model."""

    topology: dict = dataclasses.field(default_factory=topology_stamp)
    entries: dict = dataclasses.field(default_factory=dict)
    path: str | None = None
    stale_reason: str | None = None  # why a load came back empty

    def record(self, op: str, t: int, din: int, dout: int, backend: str,
               us: float) -> None:
        """Record one measured timing, replacing any earlier one for the
        same bucket and backend."""
        if backend not in _BACKEND_CHOICES:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {_BACKEND_CHOICES}")
        if not (us > 0.0) or us != us or us == float("inf"):
            raise ValueError(f"bad timing {us!r} for {op}")
        key = bucket_key(op, t, din, dout)
        self.entries.setdefault(key, {})[backend] = {"us": float(us)}

    def lookup(self, op: str, t: int, din: int, dout: int) -> dict | None:
        return self.entries.get(bucket_key(op, t, din, dout))

    def best(self, op: str, t: int, din: int, dout: int) -> str | None:
        """Measured argmin for this bucket, or None if unmeasured."""
        slot = self.lookup(op, t, din, dout)
        if not slot:
            return None
        return min(slot, key=lambda b: slot[b]["us"])

    def __len__(self) -> int:
        return len(self.entries)

    # -- persistence -------------------------------------------------------

    def _payload(self) -> dict:
        return {"version": TABLE_VERSION, "topology": self.topology,
                "entries": self.entries}

    def save(self, path: str | None = None) -> str:
        """Atomic, checksummed write (tmp + fsync + os.replace — the PR 6
        checkpoint discipline), so a killed writer leaves either the old
        table or the new one, never a torn file that parses."""
        path = path or self.path or default_path(stamp=self.topology)
        self.path = path
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = self._payload()
        blob = json.dumps(payload, sort_keys=True)
        doc = {"crc32": zlib.crc32(blob.encode()), **payload}
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        return path


def load(path: str | None = None, *,
         topology: dict | None = None) -> AutotuneTable:
    """Load a table; NEVER raises. Missing, unparseable, truncated,
    checksum-mismatched, wrong-version, or wrong-topology files all come
    back as an empty table (with `stale_reason` saying why) — the auto
    backend then falls back to the static model and the next sweep
    rebuilds the file."""
    topo = topology or topology_stamp()
    path = path or default_path(stamp=topo)
    fresh = AutotuneTable(topology=topo, path=path)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except FileNotFoundError:
        fresh.stale_reason = "missing"
        return fresh
    except (OSError, ValueError) as e:
        fresh.stale_reason = f"unreadable: {type(e).__name__}"
        return fresh
    if not isinstance(doc, dict):
        fresh.stale_reason = "malformed"
        return fresh
    if doc.get("version") != TABLE_VERSION:
        fresh.stale_reason = f"version {doc.get('version')!r}"
        return fresh
    payload = {"version": doc.get("version"), "topology": doc.get("topology"),
               "entries": doc.get("entries")}
    blob = json.dumps(payload, sort_keys=True)
    if zlib.crc32(blob.encode()) != doc.get("crc32"):
        fresh.stale_reason = "crc mismatch"
        return fresh
    if doc.get("topology") != topo:
        fresh.stale_reason = "topology mismatch"
        return fresh
    if not isinstance(doc.get("entries"), dict):
        fresh.stale_reason = "malformed entries"
        return fresh
    return AutotuneTable(topology=topo, entries=doc["entries"], path=path)


# ---------------------------------------------------------------------------
# Installed-table resolution (what the auto backend consults at trace time).
# ---------------------------------------------------------------------------

# context-local override (tests / nested scopes) over a process-wide install
_OVERRIDE: contextvars.ContextVar[AutotuneTable | None] = \
    contextvars.ContextVar("autotune_table_override", default=None)
_INSTALLED: AutotuneTable | None = None


def installed_table() -> AutotuneTable | None:
    ov = _OVERRIDE.get()
    if ov is not None:
        return ov
    return _INSTALLED


def install(table: AutotuneTable | None) -> AutotuneTable | None:
    """Process-wide install (entry points); None uninstalls."""
    global _INSTALLED
    _INSTALLED = table
    return table


def install_default(cache_root: str | None = None) -> AutotuneTable:
    """Load the table for the current topology from the cache root and
    install it. Empty/stale/corrupt files install an empty table — auto
    then behaves exactly like the static model until a sweep runs."""
    return install(load(default_path(cache_root)))


@contextlib.contextmanager
def use_table(table: AutotuneTable | None):
    """Scope a table for the dynamic extent of the block (tests)."""
    token = _OVERRIDE.set(table)
    try:
        yield table
    finally:
        _OVERRIDE.reset(token)


# ---------------------------------------------------------------------------
# The measured sweep.
# ---------------------------------------------------------------------------

# (B, T, din, dout) buckets worth measuring by default — a small grid plus
# the production-ish tails. Interpret-mode pallas off-TPU is
# minutes-slow above ~256²; the sweep caps itself unless forced.
SWEEP_SHAPES_QUICK = ((4, 128, 128, 128), (4, 256, 256, 256))
SWEEP_SHAPES_FULL = ((4, 512, 256, 256), (8, 1024, 512, 512),
                     (8, 2048, 1024, 1024))


def _median_us(fn, args, *, warmup: int = 2, iters: int = 5) -> float:
    import numpy as np
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _op_data(op: str, shape):
    """Representative operands for one op at one (B, T, din, dout)."""
    import jax.numpy as jnp
    b, t, din, dout = shape
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (b, t, din))
    g = jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)) * 0.1
    f = jax.random.uniform(jax.random.fold_in(key, 2), (b,))
    c = jnp.full((b,), 0.5)
    if op == "norms":
        return (a, g)
    if op == "clip_sum":
        return (a, g, f)
    if op == "linear_clip":
        return (a, g, c)
    if op == "scale_contract":
        # S=2 stacked residuals (the BK epilogue's layout)
        a2 = jnp.stack([a, a * 0.5])
        g2 = jnp.stack([g, g * 2.0])
        f2 = jnp.stack([f, f])
        return (a2, g2, f2)
    if op == "paged_attn":
        return paged_attn_data(shape)
    raise ValueError(f"unknown op {op!r}; expected one of {OPS}")


def paged_attn_data(shape, *, page_len: int = 16, kv: int = 2, grp: int = 2):
    """Decode-attention operands whose table key maps t -> logical context
    and (din, dout) -> (query head dim, value head dim)."""
    import jax.numpy as jnp
    b, t, din, dout = shape
    dq = min(din, 64)
    dv = min(dout, 64)
    t = max(t, page_len)
    p_tab = -(-t // page_len)
    n_pages = b * p_tab + 1  # + trash page
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (b, kv, grp, dq))
    kpool = jax.random.normal(jax.random.fold_in(key, 1),
                              (n_pages, page_len, kv, dq))
    vpool = jax.random.normal(jax.random.fold_in(key, 2),
                              (n_pages, page_len, kv, dv))
    pt = (jnp.arange(b * p_tab, dtype=jnp.int32).reshape(b, p_tab) + 1)
    pos = jnp.full((b,), t - 1, jnp.int32)
    return (q, kpool, vpool, pt, pos)


def paged_attn_dims(q, pt, page_len: int, dv: int) -> tuple[int, int, int]:
    """(t, din, dout) table coordinates for a paged_attn call."""
    return int(pt.shape[1]) * int(page_len), int(q.shape[-1]), int(dv)


def _op_fn(engine, op: str, shape):
    import functools
    if op == "paged_attn":
        b, t, din, dout = shape
        scale = 1.0 / (min(din, 64) ** 0.5)
        return jax.jit(functools.partial(engine.paged_attn, scale=scale))
    return jax.jit(getattr(engine, {
        "norms": "linear_norms_sq",
        "clip_sum": "clipped_sum_linear",
        "linear_clip": "linear_clip",
        "scale_contract": "scale_contract",
    }[op]))


def measure_op(op: str, shape, *, backends=_BACKEND_CHOICES,
               warmup: int = 2, iters: int = 5) -> dict[str, float]:
    """Median wall µs per backend for one (op, shape). A backend that fails
    to compile or run raises: a sweep that silently dropped it would hand
    `auto` a table in which the other backend always wins."""
    from repro.kernels import backend as KB
    args = _op_data(op, shape)
    out: dict[str, float] = {}
    for name in backends:
        eng = KB.make_engine(name)
        out[name] = _median_us(_op_fn(eng, op, shape), args,
                               warmup=warmup, iters=iters)
    return out


def sweep(*, ops=OPS, shapes=None, table: AutotuneTable | None = None,
          quick: bool = True, save: bool = True,
          cache_root: str | None = None,
          progress=None) -> AutotuneTable:
    """The one-time timing sweep: measure every (op, shape, backend) and
    record the results. Idempotent — rerunning refreshes measurements."""
    if shapes is None:
        shapes = (SWEEP_SHAPES_QUICK if quick
                  else SWEEP_SHAPES_QUICK + SWEEP_SHAPES_FULL)
    if table is None:
        table = load(default_path(cache_root))
    for shape in shapes:
        b, t, din, dout = shape
        for op in ops:
            timings = measure_op(op, shape)
            for name, us in timings.items():
                if op == "paged_attn":
                    q, kp, vp, pt, pos = _op_data(op, shape)
                    tt, di, do = paged_attn_dims(q, pt, kp.shape[1],
                                                 vp.shape[-1])
                else:
                    tt, di, do = t, din, dout
                table.record(op, tt, di, do, name, us)
            if progress is not None:
                progress(op, shape, timings)
    if save:
        table.save()
    return table


# ---------------------------------------------------------------------------
# CLI: pre-warm a fleet image / inspect the installed table.
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measured kernel autotuner (see module docstring)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the timing sweep and persist the table")
    ap.add_argument("--full", action="store_true",
                    help="sweep the production-size shapes too (off-TPU "
                         "this times interpret-mode kernels: slow)")
    ap.add_argument("--show", action="store_true",
                    help="print the persisted table for this topology")
    ap.add_argument("--cache-dir", default=None,
                    help="cache root (default <repo>/.cache or "
                         "$REPRO_CACHE_DIR)")
    args = ap.parse_args(argv)
    path = default_path(args.cache_dir)
    if args.sweep:
        def progress(op, shape, timings):
            t = {k: f"{v:.0f}us" for k, v in timings.items()}
            print(f"# {op} {shape}: {t}", flush=True)
        table = sweep(quick=not args.full, cache_root=args.cache_dir,
                      progress=progress)
        print(f"# wrote {table.path} ({len(table)} buckets)")
    if args.show or not args.sweep:
        table = load(path)
        print(json.dumps({"path": path, "topology": table.topology,
                          "stale_reason": table.stale_reason,
                          "buckets": table.entries}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

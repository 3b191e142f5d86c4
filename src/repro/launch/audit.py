import os
import sys

if __name__ == "__main__":
    # the sharded half of the matrix needs 8 virtual CPU devices, and the
    # flag must land before jax initializes — module code runs top-down,
    # so this executes before the jax import below (in-process importers,
    # e.g. tests, are NOT affected and audit only the unsharded configs)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

"""Static DP-safety audit CLI: the clipping x execution x mesh matrix.

For every config in the matrix this driver builds the REAL train step
(`make_dp_train_step`, tiny arch), runs BOTH static passes —
`repro.analysis.jaxpr_taint` on the closed jaxpr and
`repro.analysis.rules` on the compiled post-SPMD HLO — and aggregates
the findings into benchmarks/AUDIT.json (stamped with the topology record
that keys the autotune table). Any ERROR finding exits non-zero: the
audit is a CI gate, not a report.

The matrix pins `backend="xla"` like launch.dryrun: the fused Pallas
linear_clip kernel applies the factor INSIDE its custom call, which an
operand-level taint pass cannot see through; the xla path is the
bitwise-parity-tested reference for it (tests/test_kernels.py).

`--selftest` proves the auditor has teeth: each seeded violation
(drop the clip multiply, double/drop the noise add, reuse a key, strip
donation) must be flagged by exactly its expected rule.

Usage:
  python -m repro.launch.audit --matrix
  python -m repro.launch.audit --mode ghost_flat --execution twopass --sharded
  python -m repro.launch.audit --selftest
"""
import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp

from repro import optim
from repro.analysis.findings import ERROR, Finding, errors
from repro.analysis.jaxpr_taint import audit_train_step
from repro.analysis.rules import RULES, StepExpectation, run_hlo_rules
from repro.configs import get_config
from repro.core.clipping import base_mode
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import abstract_params
from repro.launch.inputs import train_batch_specs
from repro.models.config import InputShape
from repro.models.transformer import build_model

AUDIT_PATH = os.path.join(os.path.dirname(__file__),
                          "../../../benchmarks/AUDIT.json")

# (mode, execution, sharded): every private clipping mode under both
# executions and both placements where they are defined — per_layer's
# execution knob is a no-op and naive_flat is the single-device oracle,
# so their redundant/unsupported points are omitted rather than faked
MATRIX: tuple = tuple(
    (mode, execution, sharded)
    for mode in ("ghost_flat", "per_group")
    for execution in ("bk", "twopass")
    for sharded in (False, True)
) + (
    ("per_layer", "bk", False),
    ("per_layer", "bk", True),
    ("naive_flat", "bk", False),
)

_SHARDED_MESH = (2, 4)  # (data, model): 8 virtual devices


def _layer_trip(cfg) -> int:
    """Scan trip count of the dominant layer run (mirrors dryrun's helper;
    duplicated because importing dryrun forces a 512-device XLA flag)."""
    n = cfg.num_layers
    runs = [n]
    if getattr(cfg, "num_experts", 0) and getattr(cfg, "first_k_dense", 0):
        runs = [cfg.first_k_dense, n - cfg.first_k_dense]
    return max(runs)


def build_case(mode: str, execution: str, sharded: bool, *,
               arch: str = "tiny", batch: int = 8, seq: int = 16,
               microbatches: int = 2):
    """(step_fn, abstract args, mesh, StepExpectation) for one config."""
    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = None
    if sharded:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(*_SHARDED_MESH)
    assign = nsuper = None
    if mode == "per_group" and not sharded:
        # mirror the per-DEVICE partition the sharded engine would derive
        from repro.launch.sharding import group_shard_assignment
        nsuper = _SHARDED_MESH[1]
        assign = group_shard_assignment(model.layout, nsuper)
    dpc = DPConfig(mode=mode, sigma=1.0, sampling_rate=1e-3, steps=100,
                   adaptive=True, microbatches=microbatches,
                   execution=execution, backend="xla",
                   group_assignment=assign, num_supergroups=nsuper)
    init_fn, step_fn, _plan = make_dp_train_step(
        model.loss_fn, model.spec, model.layout, optim.adam(1e-4), dpc,
        batch_size=batch, mesh=mesh)
    params_abs = abstract_params(model.spec)
    opt_abs, dp_abs = jax.eval_shape(init_fn, params_abs)
    batch_abs = train_batch_specs(cfg, InputShape("audit", seq, batch,
                                                  "train"))
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
    args = (params_abs, opt_abs, dp_abs, batch_abs, key_abs)
    expect = StepExpectation(
        mode=base_mode(mode), execution=execution, sharded=sharded,
        layer_trip=_layer_trip(cfg),
        donated_leaves=len(jax.tree_util.tree_leaves(
            (params_abs, opt_abs, dp_abs))))
    return step_fn, args, mesh, expect


def audit_config(mode: str, execution: str, sharded: bool, *,
                 arch: str = "tiny", donate: bool = True,
                 jaxpr_only: bool = False) -> dict:
    """Run both passes on one config; returns the AUDIT.json record."""
    t0 = time.time()
    step_fn, args, mesh, expect = build_case(mode, execution, sharded,
                                             arch=arch)
    findings: list[Finding] = list(audit_train_step(step_fn, args))
    if not jaxpr_only:
        jitted = jax.jit(step_fn,
                         donate_argnums=(0, 1, 2) if donate else ())
        hlo = jitted.lower(*args).compile().as_text()
        findings.extend(run_hlo_rules(hlo, expect, mesh))
    errs = errors(findings)
    return {
        "mode": mode, "execution": execution, "sharded": sharded,
        "arch": arch,
        "status": "error" if errs else "ok",
        "num_errors": len(errs),
        "findings": [f.to_dict() for f in findings],
        "elapsed_s": round(time.time() - t0, 2),
    }


# ---------------------------------------------------------------------------
# Seeded violations: the auditor's own mutation tests (also used by
# tests/test_audit.py). Each mutation surgically breaks ONE invariant in
# the real engine and must be flagged by exactly its expected rule.
# ---------------------------------------------------------------------------

# mutation -> the single rule that must flag it
MUTATIONS = {
    "drop_clip": "JAXPR-CLIP-PATH",        # factor computed unmarked/raw
    "double_noise": "JAXPR-NOISE-ONCE",    # noise added twice per leaf
    "drop_noise": "JAXPR-NOISE-ONCE",      # noise skipped entirely
    "reuse_key": "JAXPR-KEY-LINEAGE",      # PR-6 class: constant key fold
    "strip_donation": "HLO-DONATION",      # PR-7 class: donation dropped
}


@contextlib.contextmanager
def seeded_violation(name: str):
    """Monkeypatch the engine into one specific DP bug (restored on exit).

    `strip_donation` is a no-op here — callers pass `donate=False` to
    `audit_config` instead (the bug lives in the jit call, not the step).
    """
    from repro.core import clipping, dp_sgd
    if name == "drop_clip":
        # the factor math inlined WITHOUT the dp_clip_factor marker — the
        # numerics still clip, but nothing proves it; structurally this is
        # what an ad-hoc reimplementation at a call site would look like
        orig = clipping.flat_clip_factors
        clipping.flat_clip_factors = lambda total, c: jnp.minimum(
            1.0, jnp.asarray(c, jnp.float32) / jnp.sqrt(total + 1e-12))
        try:
            yield
        finally:
            clipping.flat_clip_factors = orig
    elif name == "double_noise":
        orig = dp_sgd.add_noise_to_grads

        def twice(spec, layout, grads, stds, key, dtype=jnp.float32):
            once = orig(spec, layout, grads, stds, key, dtype)
            return orig(spec, layout, once, stds, key, dtype)

        dp_sgd.add_noise_to_grads = twice
        try:
            yield
        finally:
            dp_sgd.add_noise_to_grads = orig
    elif name == "drop_noise":
        orig = dp_sgd.add_noise_to_grads
        dp_sgd.add_noise_to_grads = \
            lambda spec, layout, grads, stds, key, dtype=jnp.float32: grads
        try:
            yield
        finally:
            dp_sgd.add_noise_to_grads = orig
    elif name == "reuse_key":
        # every leaf folds the SAME constant: exactly the PR-6 failure
        # shape (process-randomized hash() collapsed cross-process, here
        # collapsed across leaves)
        orig = dp_sgd.stable_hash
        dp_sgd.stable_hash = lambda s: 0
        try:
            yield
        finally:
            dp_sgd.stable_hash = orig
    elif name == "strip_donation":
        yield
    else:
        raise ValueError(f"unknown mutation {name!r}; "
                         f"known: {sorted(MUTATIONS)}")


def run_selftest(arch: str = "tiny") -> list[str]:
    """Each seeded violation must raise exactly its expected rule (and the
    unmutated tree must stay green). Returns a list of failure strings."""
    failures = []
    base = audit_config("ghost_flat", "bk", False, arch=arch,
                        jaxpr_only=True)
    if base["status"] != "ok":
        failures.append(f"green config not green: {base['findings']}")
    for name, want_rule in MUTATIONS.items():
        donate = name != "strip_donation"
        jaxpr_only = name != "strip_donation"
        with seeded_violation(name):
            rec = audit_config("ghost_flat", "bk", False, arch=arch,
                               donate=donate, jaxpr_only=jaxpr_only)
        got = {f["rule"] for f in rec["findings"]
               if f["severity"] == ERROR}
        if got != {want_rule}:
            failures.append(
                f"mutation {name}: expected exactly {{{want_rule}}}, "
                f"got {sorted(got)}")
        else:
            print(f"[selftest ok] {name:16s} -> {want_rule}", flush=True)
    return failures


# ---------------------------------------------------------------------------
# The matrix driver + CLI.
# ---------------------------------------------------------------------------


def run_matrix(*, arch: str = "tiny", out_path: str | None = None,
               configs=MATRIX) -> dict:
    from repro.kernels.autotune import topology_stamp
    need = _SHARDED_MESH[0] * _SHARDED_MESH[1]
    records = []
    for mode, execution, sharded in configs:
        if sharded and jax.device_count() < need:
            records.append({"mode": mode, "execution": execution,
                            "sharded": True, "arch": arch,
                            "status": "skipped", "num_errors": 0,
                            "findings": [],
                            "reason": f"needs {need} devices "
                                      f"(have {jax.device_count()})"})
            print(f"[skip] {mode}/{execution}/sharded: "
                  f"{records[-1]['reason']}", flush=True)
            continue
        rec = audit_config(mode, execution, sharded, arch=arch)
        records.append(rec)
        tag = f"{mode}/{execution}/{'sharded' if sharded else 'unsharded'}"
        print(f"[{rec['status']:5s}] {tag:35s} "
              f"{rec['num_errors']} error(s), "
              f"{len(rec['findings'])} finding(s), "
              f"{rec['elapsed_s']}s", flush=True)
        for f in rec["findings"]:
            if f["severity"] == ERROR:
                print(f"    {f['rule']} @ {f['location']}: {f['message']}",
                      flush=True)
    report = {
        "generated_by": "repro.launch.audit",
        "arch": arch,
        "topology": topology_stamp(),
        "rules": {rid: {"severity": sev, "invariant": inv}
                  for rid, (sev, inv) in RULES.items()},
        "num_errors": sum(r["num_errors"] for r in records),
        "configs": records,
    }
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as fh:
            json.dump(report, fh, indent=1)
        print(f"wrote {out_path}", flush=True)
    return report


def build_audit_parser() -> argparse.ArgumentParser:
    """CLI surface (tests/test_docs.py introspects this for doc drift)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--matrix", action="store_true",
                    help="run the full clipping x execution x mesh matrix "
                         "(the default when no single config is given)")
    ap.add_argument("--mode", default=None,
                    help="audit one mode (ghost_flat|per_group|per_layer|"
                         "naive_flat)")
    ap.add_argument("--execution", default="bk", choices=["bk", "twopass"])
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--selftest", action="store_true",
                    help="seeded-violation suite: each mutation must trip "
                         "exactly its expected rule")
    ap.add_argument("--out", default=AUDIT_PATH,
                    help="AUDIT.json path (default: benchmarks/AUDIT.json)")
    return ap


def main() -> int:
    args = build_audit_parser().parse_args()

    rc = 0
    if args.selftest:
        failures = run_selftest(arch=args.arch)
        for f in failures:
            print(f"[selftest FAIL] {f}", flush=True)
        rc |= 1 if failures else 0
        if not args.matrix and args.mode is None:
            return rc

    configs = MATRIX
    if args.mode is not None:
        configs = ((args.mode, args.execution, args.sharded),)
    report = run_matrix(arch=args.arch, out_path=args.out, configs=configs)
    if report["num_errors"]:
        print(f"AUDIT FAILED: {report['num_errors']} ERROR finding(s)",
              flush=True)
        rc |= 1
    else:
        print(f"audit green: {len(report['configs'])} config(s), "
              f"0 ERROR findings", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

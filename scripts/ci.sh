#!/usr/bin/env bash
# CI: tier-1 tests, the CLI smokes, the 8-virtual-device sharded stage and
# the static DP-safety audit. Speed is measured on the chip by bench/run.py,
# not here.
set -euo pipefail
cd "$(dirname "$0")"
# the sharded-engine subprocess test is covered by the explicit 8-device
# stage below — deselect it here so CI pays the ~4 min suite once (the
# bare tier-1 command `scripts/test.sh` still runs everything)
./test.sh --deselect \
    tests/test_sharded.py::test_sharded_engine_checks_subprocess

# ---- serving-engine smoke: ragged request set served through the slot
# pool on CPU, with fewer slots than requests so admission happens
# MID-FLIGHT into recycled slots (parity vs the oracle is asserted by
# tests/test_engine.py in the tier-1 stage above; this exercises the CLI).
cd ..
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --arch tiny --mode engine --batch 4 \
        --slots 3 --prompt-len 12 --min-prompt-len 3 --gen 16

# ---- paged data-plane smoke: the same ragged traffic through the block
# pool with a common system prompt (its full pages are shared
# physically), then a deliberately starved pool (--num-pages below the
# working set) so admission has to evict registered prefixes through the
# host spill tier and re-admit them. Token parity for all of this is
# asserted by tests/test_paged.py; these runs exercise the CLI wiring
# end to end.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --arch tiny --mode engine --batch 4 \
        --slots 3 --prompt-len 12 --min-prompt-len 3 --gen 16 \
        --paging on --page-len 8 --shared-prefix 16
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --arch tiny --mode engine --batch 4 \
        --slots 2 --prompt-len 24 --min-prompt-len 24 --gen 16 \
        --paging on --page-len 8 --num-pages 12

# ---- multi-tenant smoke: two tenants on one engine through the CLI (no
# adapter dirs -> both serve the base model; mixed-pool parity, hot-swap
# bitwise verification, and zero-recompile asserts run in tier-1 via
# tests/test_engine.py / tests/test_swap.py; the end-to-end train ->
# publish -> swap loop is examples/multi_tenant_serve.py).
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --arch tiny --mode engine --batch 4 \
        --slots 3 --prompt-len 12 --min-prompt-len 3 --gen 16 \
        --tenants 2 --lora-rank 4

# ---- doc drift: CLI flags <-> docs, link targets, the generated
# engine-stats table (also part of tier-1; re-run here standalone so a
# docs-only change failing CI names this stage, not the whole suite).
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -q tests/test_docs.py
cd scripts

# ---- crash-safe service smoke: the REAL kill -9 variant of the fault
# matrix (tier-1 runs the same points in-process via tests/test_service.py).
# A reference service runs 8 steps uninterrupted; a second one is killed
# by --fault-at (os._exit mid-publish) and resumed. The resumed run must
# be bitwise identical to the reference — checkpoint shards, sampler
# stream, AND ledger bytes — and the replayed epsilon must be monotone.
cd ..
SVC_ROOT="$(mktemp -d /tmp/repro_svc_ci.XXXXXX)"
SVC_ARGS=(--arch tiny --steps 8 --batch 8 --seq 32 --docs 64 --sigma 0.8
          --checkpoint-every 3 --log-every 100)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.service --service-dir "$SVC_ROOT/ref" \
        "${SVC_ARGS[@]}"
for fault in post-ledger-append:5 pre-ckpt-rename:6; do
    dir="$SVC_ROOT/fault-${fault//:/-}"
    rc=0
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.launch.service --service-dir "$dir" \
            "${SVC_ARGS[@]}" --fault-at "$fault" || rc=$?
    [ "$rc" -eq 86 ] || { echo "fault $fault: expected exit 86, got $rc"; exit 1; }
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.launch.service --service-dir "$dir" "${SVC_ARGS[@]}"
done
PYTHONPATH="src:tests${PYTHONPATH:+:$PYTHONPATH}" python - "$SVC_ROOT" <<'EOF'
import sys
import faults
from repro.core.accounting import RdpAccountant
root = sys.argv[1]
ref = faults.state_digest(f"{root}/ref")
for fault in ("post-ledger-append-5", "pre-ckpt-rename-6"):
    got = faults.state_digest(f"{root}/fault-{fault}")
    assert got == ref, f"{fault}: resumed state differs from reference"
recs = faults.ledger_records(f"{root}/ref")
acct, eps_seq = RdpAccountant(), []
for r in recs:
    acct.spend(r["q"], r["sigma"])
    eps_seq.append(acct.epsilon(1e-5))
assert eps_seq == sorted(eps_seq) and eps_seq[0] > 0, "epsilon not monotone"
print(f"service smoke OK: {len(recs)} ledgered steps, "
      f"eps={eps_seq[-1]:.4f}, kill/resume bitwise-identical")
EOF
rm -rf "$SVC_ROOT"
cd scripts

# ---- autotune round trip: the timing sweep persists its table in one
# process and a SECOND process (a fleet worker, after pre-warm) loads it
# and resolves `auto` to the measured argmin on every measured bucket.
cd ..
AT_ROOT="$(mktemp -d /tmp/repro_autotune_ci.XXXXXX)"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.kernels.autotune --sweep --cache-dir "$AT_ROOT"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - "$AT_ROOT" <<'EOF'
import sys
from repro.kernels import autotune, backend
tab = autotune.install_default(sys.argv[1])
assert tab.stale_reason is None and len(tab) > 0, tab.stale_reason
cfg = backend.EngineConfig(backend="auto")
for key, slot in tab.entries.items():
    op, t, di, do = key.split("|")
    t, di, do = (int(x[1:]) for x in (t, di, do))
    want = min(slot, key=lambda b: slot[b]["us"])
    got = backend.choose_op(op, t, di, do, cfg)
    assert got == want, (key, got, want)
print(f"autotune round-trip OK: {len(tab)} buckets, auto == measured "
      "argmin in a second process")
EOF
rm -rf "$AT_ROOT"
cd scripts

# ---- sharded stage: the multi-device engine on 8 virtual CPU devices ----
# Runs the full sharded check suite (parity + the zero-model-axis-norm-
# collectives HLO assertion) with the forced device count.
cd ..
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python tests/sharded_checks.py

# ---- static DP-safety audit: the full clipping x execution x mesh matrix ----
# Both analyzer passes (jaxpr taint + HLO rules) on every supported config;
# writes benchmarks/AUDIT.json, exits non-zero on any ERROR finding. The
# seeded-violation selftest first proves the auditor still has teeth.
# (the CLI forces its own 8-device count before jax loads)
python -m repro.launch.audit --selftest
python -m repro.launch.audit --matrix

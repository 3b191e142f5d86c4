"""Mutation tests for the static DP-safety auditor (ISSUE 9 tentpole).

The auditor is only worth its CI gate if seeded violations actually trip
it: each mutation here surgically breaks ONE invariant in the real
engine (drop the clip multiply, double/drop the noise add, collapse the
key fold, strip donation) and must be flagged by EXACTLY its expected
rule — no more, no less. The green configs prove the unmutated tree
passes, so a firing rule is signal, not noise.

The sharded half of the matrix needs 8 devices and runs in CI via
`python -m repro.launch.audit --matrix` (the CLI sets
--xla_force_host_platform_device_count before jax loads); here the
collective-leak rule is exercised hermetically with a stub mesh over a
synthetic HLO module instead.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.analysis import hlo as hlo_mod
from repro.analysis.findings import (ERROR, INFO, SEVERITIES, WARNING,
                                     Finding, errors, worst_severity)
from repro.analysis.rules import (RULES, StepExpectation,
                                  rule_collective_leak, run_hlo_rules)
from repro.launch import audit as audit_mod


def _error_rules(rec: dict) -> list[str]:
    return sorted({f["rule"] for f in rec["findings"]
                   if f["severity"] == ERROR})


# ---------------------------------------------------------------------------
# Green baselines: the unmutated engine passes both static passes.
# ---------------------------------------------------------------------------


def test_green_ghost_flat_full_audit():
    rec = audit_mod.audit_config("ghost_flat", "bk", False)
    assert rec["status"] == "ok", rec["findings"]
    assert rec["num_errors"] == 0
    rules_seen = {f["rule"] for f in rec["findings"]}
    # the positive evidence is recorded, not silently skipped
    assert {"HLO-BWD-COUNT", "HLO-DONATION",
            "HLO-SHAPE-STABLE"} <= rules_seen


@pytest.mark.parametrize("mode,execution",
                         [("per_layer", "bk"), ("ghost_flat", "twopass"),
                          ("naive_flat", "bk")])
def test_green_jaxpr_pass(mode, execution):
    rec = audit_mod.audit_config(mode, execution, False, jaxpr_only=True)
    assert rec["status"] == "ok", rec["findings"]


# ---------------------------------------------------------------------------
# The teeth: every seeded violation trips exactly its expected rule.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(audit_mod.MUTATIONS))
def test_mutation_trips_exactly_expected_rule(name):
    want = audit_mod.MUTATIONS[name]
    donate = name != "strip_donation"
    jaxpr_only = name != "strip_donation"
    with audit_mod.seeded_violation(name):
        rec = audit_mod.audit_config("ghost_flat", "bk", False,
                                     donate=donate, jaxpr_only=jaxpr_only)
    assert rec["status"] == "error"
    assert _error_rules(rec) == [want], rec["findings"]


def test_double_noise_message_counts_draws():
    with audit_mod.seeded_violation("double_noise"):
        rec = audit_mod.audit_config("ghost_flat", "bk", False,
                                     jaxpr_only=True)
    assert any("2 noise draws" in f["message"] for f in rec["findings"])


def test_reuse_key_names_colliding_leaves():
    with audit_mod.seeded_violation("reuse_key"):
        rec = audit_mod.audit_config("ghost_flat", "bk", False,
                                     jaxpr_only=True)
    errs = [f for f in rec["findings"] if f["severity"] == ERROR]
    assert errs and all(f["rule"] == "JAXPR-KEY-LINEAGE" for f in errs)
    # each finding names a PAIR of distinct leaves sharing a signature
    assert all(" ~ " in f["location"] and " and " in f["message"]
               for f in errs)


def test_backward_count_catches_execution_lie():
    # compile the REAL twopass program, then audit it under the CLAIM that
    # it is bk: the rules engine must count 2 transposed layer loops and
    # refuse the claim (this is the measured half of tests/test_bk.py
    # turned into a gate)
    step_fn, args, mesh, expect = audit_mod.build_case(
        "ghost_flat", "twopass", False)
    hlo = (jax.jit(step_fn, donate_argnums=(0, 1, 2))
           .lower(*args).compile().as_text())
    assert not errors(run_hlo_rules(hlo, expect, mesh))
    lied = dataclasses.replace(expect, execution="bk")
    errs = errors(run_hlo_rules(hlo, lied, mesh))
    assert [f.rule for f in errs] == ["HLO-BWD-COUNT"]
    assert "2 backward" in errs[0].message


# ---------------------------------------------------------------------------
# Collective-leak rule, hermetic: stub 2x4 mesh + synthetic HLO.
# ---------------------------------------------------------------------------


class _StubDev:
    def __init__(self, i):
        self.id = i


class _StubMesh:
    """Just enough mesh surface for mesh_device_coords: a (2, 4) device
    array with row-major ids and (data, model) axis names."""

    axis_names = ("data", "model")
    devices = np.array([[_StubDev(d * 4 + m) for m in range(4)]
                        for d in range(2)], dtype=object)


def _synth_hlo(site: str) -> str:
    # all-reduce over {0,1,2,3}/{4,5,6,7}: membership varies only along
    # the model axis of the 2x4 stub mesh
    return f"""HloModule synth

%add (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}}

ENTRY %main (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8] parameter(0)
  ROOT %ar = f32[8] all-reduce(%p0), replica_groups={{{{0,1,2,3}},{{4,5,6,7}}}}, to_apply=%add, metadata={{op_name="jit(step_fn)/{site}/all-reduce"}}
}}
"""


def test_coll_leak_flags_per_device_mode():
    # per_layer promises ZERO model-axis norm traffic; any norm psum
    # crossing the model axis is a leak of per-example norm data
    expect = StepExpectation(mode="per_layer", sharded=True)
    fs = rule_collective_leak(_synth_hlo("per_example_norm_psum"),
                              expect, _StubMesh())
    errs = errors(fs)
    assert len(errs) == 1 and errs[0].rule == "HLO-COLL-LEAK"
    assert "model" in errs[0].message


def test_coll_leak_whitelists_ghost_flat_norm_psum():
    expect = StepExpectation(mode="ghost_flat", sharded=True)
    fs = rule_collective_leak(_synth_hlo("flat_norm_psum"),
                              expect, _StubMesh())
    assert not errors(fs)
    assert any(f.severity == INFO and "whitelisted" in f.message
               for f in fs)


def test_coll_leak_rejects_unwhitelisted_site_even_for_ghost_flat():
    expect = StepExpectation(mode="ghost_flat", sharded=True)
    fs = rule_collective_leak(_synth_hlo("per_example_norm_psum"),
                              expect, _StubMesh())
    assert any(f.severity == ERROR for f in fs)
    # and the missing whitelisted psum is itself called out
    assert any(f.severity == WARNING and "flat_norm_psum" in f.message
               for f in fs)


def test_coll_leak_ignores_data_axis_norm_psum():
    # {0,4}-style groups vary only the DATA coordinate: per-device modes
    # are allowed to reduce norms across data shards
    text = _synth_hlo("per_example_norm_psum").replace(
        "{{0,1,2,3},{4,5,6,7}}", "{{0,4},{1,5},{2,6},{3,7}}")
    expect = StepExpectation(mode="per_layer", sharded=True)
    fs = rule_collective_leak(text, expect, _StubMesh())
    assert not errors(fs)


# ---------------------------------------------------------------------------
# HLO header parsing + findings plumbing.
# ---------------------------------------------------------------------------


def test_entry_aliases_parse():
    text = ('HloModule jit_step, input_output_alias={ {0}: (0, {}, '
            'may-alias), {1}: (2, {}, must-alias) }, '
            'entry_computation_layout={(f32[4])->f32[4]}\n')
    assert hlo_mod.entry_aliases(text) == [
        {"output_index": (0,), "param": 0, "kind": "may-alias"},
        {"output_index": (1,), "param": 2, "kind": "must-alias"},
    ]
    assert hlo_mod.entry_aliases("HloModule bare\n") == []


def test_dynamic_shape_instrs_ignores_iota_attrs():
    stable = ('ENTRY %e (p: f32[4]) -> f32[4] {\n'
              '  %p = f32[4] parameter(0)\n'
              '  ROOT %g = f32[4] all-gather(%p), dimensions={0}, '
              'replica_groups=[2,2]<=[4]\n}\n')
    assert hlo_mod.dynamic_shape_instrs(stable) == []
    dyn = stable.replace("f32[4] all-gather", "f32[<=4] all-gather")
    assert [n for n, _ in hlo_mod.dynamic_shape_instrs(dyn)] == ["g"]


def test_findings_helpers():
    f1 = Finding("HLO-BWD-COUNT", INFO, "fine")
    f2 = Finding("HLO-DONATION", ERROR, "bad", "entry")
    assert errors([f1, f2]) == [f2]
    assert worst_severity([f1]) == INFO
    assert worst_severity([f1, f2]) == ERROR
    assert SEVERITIES.index(ERROR) < SEVERITIES.index(INFO)
    d = f2.to_dict()
    assert d == {"rule": "HLO-DONATION", "severity": ERROR,
                 "message": "bad", "location": "entry"}


def test_rule_catalog_is_closed():
    # every rule id the passes can emit is documented in the catalog
    assert set(RULES) == {
        "JAXPR-CLIP-PATH", "JAXPR-NOISE-ONCE", "JAXPR-KEY-LINEAGE",
        "HLO-COLL-LEAK", "HLO-BWD-COUNT", "HLO-DONATION",
        "HLO-SHAPE-STABLE"}
    for rid, (sev, invariant) in RULES.items():
        assert sev in SEVERITIES and invariant
    assert set(audit_mod.MUTATIONS.values()) <= set(RULES)

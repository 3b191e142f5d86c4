"""Sharded execution engine: ownership assignment, the axis-classifying
collectives parser (pure units), and the full 8-virtual-device check suite
(subprocess, so the forced device count never leaks into this process).

The executable contract under test is the paper's Sec 4: per-device
(`per_group`) clipping crosses the model axis with ZERO norm collectives
while `ghost_flat` pays exactly its (B,) total-norm psum — asserted from
compiled HLO by `tests/sharded_checks.py`, alongside sharded == single-
device parity of grads, norms² and quantile state.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis.hlo import _axes_of_groups, _parse_replica_groups
from repro.core.spec import GroupLayout, P
from repro.launch.sharding import group_shard_assignment

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# Ownership assignment (layout groups -> owning model shard).
# ---------------------------------------------------------------------------


def test_assignment_blocked_column_parallel_tracks_blocks():
    """blocks == model_size on a column-parallel weight: block j -> shard j
    (exact Megatron ownership)."""
    spec = {"dense_blocks": {"mlp": {"gate_up": {
        "w": P((3, 16, 32), stack=1, blocks=4)}}}}
    layout = GroupLayout(spec)
    assign = group_shard_assignment(layout, 4)
    assert len(assign) == layout.num_groups == 12
    assert assign == tuple([0, 1, 2, 3] * 3)  # (layer, block) row-major


def test_assignment_stacked_contiguous_pipeline_split():
    spec = {"blocks": {"mlp": {"down": {"w": P((8, 16, 16), stack=1)}}}}
    layout = GroupLayout(spec)
    assign = group_shard_assignment(layout, 4)
    assert assign == (0, 0, 1, 1, 2, 2, 3, 3)


def test_assignment_singletons_round_robin_and_range():
    spec = {"embed": {"w": P((64, 8))},
            "head": {"w": P((8, 64))},
            "final_norm": {"s": P((8,), init="ones")}}
    layout = GroupLayout(spec)
    assign = group_shard_assignment(layout, 4)
    assert len(assign) == 3
    assert len(set(assign)) == 3  # balanced, not all on shard 0
    assert all(0 <= a < 4 for a in assign)


def test_assignment_matches_layout_length_on_real_model():
    from repro.configs import get_config
    from repro.models.transformer import build_model
    m = build_model(get_config("tiny"))
    for msize in (2, 4, 16):
        assign = group_shard_assignment(m.layout, msize)
        assert len(assign) == m.layout.num_groups
        assert max(assign) < msize


# ---------------------------------------------------------------------------
# replica_groups parsing + axis classification.
# ---------------------------------------------------------------------------


def _coords_2x4():
    # (data=2, model=4) row-major: id = d*4 + m
    return {d * 4 + m: (d, m) for d in range(2) for m in range(4)}


def test_parse_replica_groups_literal_and_iota():
    assert _parse_replica_groups("{{0,1,2,3},{4,5,6,7}}", 8) == \
        [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert _parse_replica_groups("{}", 4) == [[0, 1, 2, 3]]
    # iota v2: [2,4]<=[8] -> two consecutive groups of 4
    assert _parse_replica_groups("[2,4]<=[8]", 8) == \
        [[0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota: [4,2]<=[2,4]T(1,0) -> stride-4 pairs
    assert _parse_replica_groups("[4,2]<=[2,4]T(1,0)", 8) == \
        [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert _parse_replica_groups("[1,1]<=[totally-bogus]", 8) is None


def test_axes_of_groups_classification():
    coords, axes = _coords_2x4(), ("data", "model")
    assert _axes_of_groups([[0, 1, 2, 3], [4, 5, 6, 7]], coords, axes) == \
        ("model",)
    assert _axes_of_groups([[0, 4], [1, 5], [2, 6], [3, 7]], coords, axes) == \
        ("data",)
    assert _axes_of_groups([[0, 1, 2, 3, 4, 5, 6, 7]], coords, axes) == \
        ("data", "model")
    # degenerate singleton groups span nothing
    assert _axes_of_groups([[i] for i in range(8)], coords, axes) == ()


def test_classify_collectives_from_synthetic_hlo():
    from repro.analysis.hlo import classify_collectives

    class FakeDev:
        def __init__(self, i):
            self.id = i

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.array([[FakeDev(d * 4 + m) for m in range(4)]
                            for d in range(2)])

    hlo = """\
HloModule test

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %ar0 = f32[8]{0} all-reduce(f32[8]{0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add, metadata={op_name="jit(f)/flat_norm_psum/psum"}
  ROOT %ar1 = f32[8]{0} all-reduce(f32[8]{0} %ar0), replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add, metadata={op_name="jit(f)/grad_psum/psum"}
}
"""
    rows = classify_collectives(hlo, FakeMesh())
    by_site = {r["site"].split("/")[-2]: r for r in rows}
    assert by_site["flat_norm_psum"]["axes"] == ("model",)
    assert by_site["grad_psum"]["axes"] == ("data",)
    assert by_site["flat_norm_psum"]["bytes"] == 32.0


# ---------------------------------------------------------------------------
# The full engine contract on a real 8-device debug mesh (subprocess).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_engine_checks_subprocess():
    """Parity (grads / norms² / quantile state, incl. microbatches and the
    LoRA trainable_key path) and the zero-model-axis-norm-collectives
    assertion — see tests/sharded_checks.py for the check list."""
    env = dict(os.environ, PYTHONPATH=SRC)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "sharded_checks.py")],
        capture_output=True, text=True, env=env, timeout=1500)
    m = re.search(r"RESULT (.*)", out.stdout)
    assert m, (out.stdout[-2000:], out.stderr[-3000:])
    results = json.loads(m.group(1))
    bad = {k: v for k, v in results.items()
           if k != "hlo_axis_counts" and v != "ok"}
    assert not bad, bad
    assert out.returncode == 0, out.stderr[-3000:]
    # the Sec-4 contract, restated here so the numbers are visible in CI
    assert results["hlo_axis_counts"]["per_group"] == 0
    assert results["hlo_axis_counts"]["ghost_flat"] >= 1

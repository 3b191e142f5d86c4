"""The DP step's phase map (`repro.analysis.hlo.op_phases`): every
instruction of a compiled step gets one phase from the named scopes of
`make_dp_train_step` (core.dp_sgd.PHASE_*), the Pallas norm and clipped-sum
kernels land in the backward, every noise draw in noise_update.

The single-device step compiles here; the shard_map step compiles on 4
virtual CPU devices in a subprocess, so the forced device count never leaks
into this process."""
import collections
import contextlib
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.analysis import hlo
from repro.configs import get_config
from repro.core import dp_sgd
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import abstract_params
from repro.kernels import backend as KB
from repro.models.transformer import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
B, T = 4, 16
# ops under a norm or clipped-sum kernel's scope: the kernel's custom call
# on the chip, its interpret-mode loop here
_KERNEL = re.compile(r'op_name="[^"]*\b(ghost_norm|clip_reduce)\b')


def phase_problems(text: str) -> list:
    """What the map gets wrong on a compiled step: instructions with no
    phase, kernels outside the backward, noise draws outside
    noise_update, a phase that never occurs, containers in the map."""
    phases = hlo.op_phases(text)
    bad = []
    for comp, instrs in hlo.parse_module(text).items():
        for ins in instrs:
            if ins.op in hlo.CONTAINER_OPS:
                if ins.name in phases:
                    bad.append(("container mapped", ins.name))
                continue
            got = phases.get(ins.name)
            if got is None:
                bad.append(("no phase", comp, ins.name, ins.op))
            elif _KERNEL.search(ins.rest) and got != hlo.BACKWARD:
                bad.append(("kernel not in backward", ins.name, got))
            elif "dp_noise_add:" in ins.rest and got != hlo.NOISE_UPDATE:
                bad.append(("noise not in noise_update", ins.name, got))
    missing = {hlo.FORWARD, hlo.BACKWARD, hlo.NOISE_UPDATE} - set(
        phases.values())
    if missing:
        bad.append(("phases never seen", sorted(missing)))
    return bad


def tiny_step_text(backend: str = "pallas") -> str:
    """The compiled HLO of a two-layer tiny per_layer private step; with
    `pallas`, the clipped sums as the separate `ghost_norm` and
    `clip_reduce` kernels (interpret mode here), as the qwen3-4b cell runs
    them."""
    cfg = dataclasses.replace(get_config("tiny"), num_layers=2)
    m = build_model(cfg)
    dpc = DPConfig(mode="per_layer", sigma=1.0, sampling_rate=0.1, steps=10,
                   backend=backend, autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=B)
    params = abstract_params(m.spec)
    opt_abs, dp_abs = jax.eval_shape(init_fn, params)
    batch = {k: jax.ShapeDtypeStruct((B, T), jnp.int32)
             for k in ("tokens", "targets")}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with KB.scoped(backend, prefer_fused=False):
        return jax.jit(step_fn).lower(params, opt_abs, dp_abs, batch,
                                      key).compile().as_text()


def test_phase_scopes_avoid_the_auditors_markers():
    for name in (dp_sgd.PHASE_CLIP, dp_sgd.PHASE_NOISE, dp_sgd.PHASE_UPDATE):
        assert "norm" not in name.lower()  # filter_model_norm_rows
        assert not name.startswith("dp_noise_add")  # jaxpr_taint


@pytest.fixture(scope="module")
def per_layer_text():
    return tiny_step_text()


def test_every_instruction_of_the_step_gets_one_phase(per_layer_text):
    assert phase_problems(per_layer_text) == []


def test_the_norm_and_clipped_sum_kernels_are_in_the_backward(
        per_layer_text):
    phases = hlo.op_phases(per_layer_text)
    kernels = [ins.name for instrs in hlo.parse_module(per_layer_text)
               .values() for ins in instrs if _KERNEL.search(ins.rest)
               and ins.op not in hlo.CONTAINER_OPS]
    assert kernels and {phases[k] for k in kernels} == {hlo.BACKWARD}
    assert hlo.container_ops(per_layer_text)  # the layer loops


_AFTER_BACKWARD = re.compile(
    r'op_name="[^"]*\b(bk_epilogue_contract|dp_tied_cross)\b')


def test_the_bk_epilogue_and_the_tied_cross_term_are_in_the_backward():
    """A ghost_flat BK step of the tied model: the epilogue's clipped sums
    and the cross term run after the transposed pass, not transposed
    themselves, and count as backward like per_layer's clipped sums."""
    cfg = get_config("minicpm-2b", reduced=True)
    m = build_model(cfg)
    dpc = DPConfig(mode="ghost_flat", sigma=1.0, sampling_rate=0.1,
                   steps=10, backend="xla", autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=B)
    params = abstract_params(m.spec)
    opt_abs, dp_abs = jax.eval_shape(init_fn, params)
    batch = {k: jax.ShapeDtypeStruct((B, T), jnp.int32)
             for k in ("tokens", "targets")}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = jax.jit(step_fn).lower(params, opt_abs, dp_abs, batch,
                                  key).compile().as_text()
    phases = hlo.op_phases(text)
    after = {ins.name: ins.rest for instrs in hlo.parse_module(text).values()
             for ins in instrs if _AFTER_BACKWARD.search(ins.rest)
             and ins.op not in hlo.CONTAINER_OPS}
    scopes = {_AFTER_BACKWARD.search(r).group(1) for r in after.values()}
    assert scopes == {"bk_epilogue_contract", "dp_tied_cross"}
    assert {phases[k] for k in after} == {hlo.BACKWARD}
    assert phase_problems(text) == []


def test_the_scopes_add_no_op(monkeypatch):
    def op_counts(text):
        return collections.Counter(ins.op for instrs in hlo.parse_module(
            text).values() for ins in instrs)

    scoped = op_counts(tiny_step_text("xla"))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert op_counts(tiny_step_text("xla")) == scoped


# 4 virtual devices, a 2x2 (data, model) mesh: the per_group step under
# shard_map, its HLO written where the test reads it
_SHARDED = """
import jax, jax.numpy as jnp
from repro import optim
from repro.configs import get_config
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import abstract_params
from repro.launch.mesh import make_debug_mesh
from repro.models.transformer import build_model
m = build_model(get_config("tiny"))
dpc = DPConfig(mode="per_group", sigma=1.0, sampling_rate=0.1, steps=10,
               backend="xla")
init_fn, step_fn, _ = make_dp_train_step(
    m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=%d,
    mesh=make_debug_mesh(2, 2))
params = abstract_params(m.spec)
opt_abs, dp_abs = jax.eval_shape(init_fn, params)
batch = {k: jax.ShapeDtypeStruct((%d, %d), jnp.int32)
         for k in ("tokens", "targets")}
key = jax.ShapeDtypeStruct((2,), jnp.uint32)
text = jax.jit(step_fn).lower(params, opt_abs, dp_abs, batch,
                              key).compile().as_text()
open(%r, "w").write(text)
"""


def test_the_sharded_per_group_step_gets_one_phase_per_instruction(
        tmp_path):
    out = str(tmp_path / "step.hlo")
    env = dict(os.environ, PYTHONPATH=SRC)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"])
    res = subprocess.run([sys.executable, "-c", _SHARDED % (B, B, T, out)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    with open(out) as fh:
        text = fh.read()
    assert "all-reduce" in text  # the shard_map step's collectives
    assert phase_problems(text) == []


# A hand-written module: a scoped forward fusion, a transposed one, a
# custom_vjp backward, the noise, the update, a copy XLA added (no
# op_name), hoisted loop-invariant code outside every scope, and a layer
# loop whose body holds an op with no op_name.
_SYNTH = """HloModule step

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g = f32[8] get-tuple-element(%p), index=1
  %inner = f32[8] copy(%g)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %inner)
}

%cond (cp: (s32[], f32[8])) -> pred[] {
  %cp = (s32[], f32[8]) parameter(0)
  %ci = s32[] get-tuple-element(%cp), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%ci, %n), direction=LT
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  %rope = f32[8] cosine(%x), metadata={op_name="jit(step_fn)/cos"}
  %fwd = f32[8] multiply(%rope, %x), metadata={op_name="jit(step_fn)/dp_phase_clip/jvp()/mul"}
  %zero = s32[] constant(0)
  %tup = (s32[], f32[8]) tuple(%zero, %fwd)
  %loop = (s32[], f32[8]) while(%tup), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/dp_phase_clip/transpose(jvp())/while"}
  %lg = f32[8] get-tuple-element(%loop), index=1
  %bwd = f32[8] add(%lg, %x), metadata={op_name="jit(step_fn)/dp_phase_clip/transpose(jvp())/add"}
  %ghost_norm.1 = f32[8] custom-call(%bwd), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/dp_phase_clip/transpose(dp_phase_clip)/jvp(ghost_norm)/pallas_call"}
  %noise = f32[8] add(%ghost_norm.1, %x), metadata={op_name="jit(step_fn)/dp_phase_noise/dp_noise_add:head.w/add"}
  %moved = f32[8] copy(%noise)
  ROOT %upd = f32[8] subtract(%x, %moved), metadata={op_name="jit(step_fn)/dp_phase_update/sub"}
}
"""


@pytest.mark.parametrize("name,phase", [
    ("fwd", hlo.FORWARD), ("bwd", hlo.BACKWARD),
    ("ghost_norm.1", hlo.BACKWARD), ("noise", hlo.NOISE_UPDATE),
    ("upd", hlo.NOISE_UPDATE),
    # no phase of their own: from the loop that calls the body, from the
    # first user (hoisted code), from the first user (XLA's copy)
    ("inner", hlo.BACKWARD), ("rope", hlo.FORWARD),
    ("moved", hlo.NOISE_UPDATE),
])
def test_op_phases_on_a_hand_written_module(name, phase):
    assert hlo.op_phases(_SYNTH)[name] == phase


def test_containers_are_left_out_of_the_map():
    assert hlo.container_ops(_SYNTH) == {"loop"}
    assert "loop" not in hlo.op_phases(_SYNTH)

"""The Pallas kernels of the main path compile for a TPU v5e at qwen3-4b
widths (d_model 2560, d_ff 9728, 8 KV heads of 128, B=4, T=512), and BK's
epilogue at MiniCPM-2B's (d_model 2304, d_ff 5760, vocab 122,753, B=2,
T=2048).

The chip is described, not attached: the TPU compiler refuses here what the
chip would refuse (misaligned blocks, too much VMEM), and no test needs a
device. The topology is described inside a fixture, never at import, so
that only the worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from repro.kernels.clip_reduce import clip_reduce, scale_contract
from repro.kernels.fused_clip import fused_norm_clip
from repro.kernels.ghost_norm import ghost_norm, ghost_norm_blocked
from repro.kernels.paged_attn import paged_attn

B, T, D, FF, VOCAB = 4, 512, 2560, 9728, 151936
KV, G, HD, PAGE = 8, 4, 128, 16
PAGES = 4 * 43 + 1  # 4 slots x 688-token horizon, plus the trash page
MC_D, MC_FF, MC_VOCAB = 2304, 5760, 122753  # MiniCPM-2B


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # the TPU library logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _bf16(shape):
    return (shape, jnp.bfloat16)


def _f32(shape):
    return (shape, jnp.float32)


def _i32(shape):
    return (shape, jnp.int32)


CASES = {
    "ghost_norm": (lambda a, g: ghost_norm(a, g),
                   [_bf16((B, T, D)), _bf16((B, T, FF))]),
    "ghost_norm_blocked": (lambda a, g: ghost_norm_blocked(a, g, 2),
                           [_bf16((B, T, D)), _bf16((B, T, FF))]),
    # the largest layer inside the 12 MiB VMEM guard of the backend engine
    "fused_norm_clip": (lambda a, g, c: fused_norm_clip(a, g, c),
                        [_bf16((B, T, 512)), _bf16((B, T, 1024)),
                         _f32((B,))]),
    "clip_reduce": (lambda a, g, f: clip_reduce(a, g, f),
                    [_bf16((B, T, D)), _bf16((B, T, FF)), _f32((B,))]),
    # derived tiles at the untied head (no tile divides 151936: a ragged
    # last block, and the largest VMEM working set, 2560 x 2560) and at
    # the fused gate+up (2432-wide blocks)
    "clip_reduce.head": (lambda a, g, f: clip_reduce(a, g, f),
                         [_bf16((B, T, D)), _bf16((B, T, VOCAB)),
                          _f32((B,))]),
    "clip_reduce.gate_up": (lambda a, g, f: clip_reduce(a, g, f),
                            [_bf16((B, T, D)), _bf16((B, T, 2 * FF)),
                             _f32((B,))]),
    "bk_scale_contract": (lambda a, g, f: scale_contract(a, g, f),
                          [_bf16((4, B, T, D)), _bf16((4, B, T, FF)),
                           _f32((4, B))]),
    # derived tiles at MiniCPM-2B widths, B=2, T=2048: the tied head into
    # the table's (V, d) layout (48 blocks of 2560 over V = 122,753, a
    # ragged last one, about 83 MiB of VMEM) and the stacked gate+up of six
    # layers (2304 -> 11520, 5 blocks of 2304)
    "bk_scale_contract.head": (lambda a, g, f: scale_contract(a, g, f),
                               [_bf16((2, 2048, MC_VOCAB)),
                                _bf16((2, 2048, MC_D)), _f32((2,))]),
    "bk_scale_contract.gate_up": (lambda a, g, f: scale_contract(a, g, f),
                                  [_bf16((6, 2, 2048, MC_D)),
                                   _bf16((6, 2, 2048, 2 * MC_FF)),
                                   _f32((6, 2))]),
    "paged_attn": (lambda q, k, v, pt, pos: paged_attn(
        q, k, v, pt, pos, scale=HD ** -0.5),
        [_bf16((4, KV, G, HD)), _bf16((PAGES, PAGE, KV, HD)),
         _bf16((PAGES, PAGE, KV, HD)), _i32((4, 43)), _i32((4,))]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e_at_qwen3_4b_widths(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernel_lines = [ln for ln in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernel_lines, f"{name}: no Mosaic kernel in the compiled program"
    kernel = name.partition(".")[0]
    assert any(f"{kernel}/pallas_call" in ln for ln in kernel_lines)


def test_tiny_dp_step_phases_for_v5e(one_chip):
    """The phase map on the TPU compiler's instruction names: every op of
    a compiled private step gets one phase, every `ghost_norm` and
    `clip_reduce` kernel lands in the backward, every noise draw in
    noise_update, and each kernel's instruction name still holds the
    kernel's name, which `ghost_norm_roofline.train` matches."""
    import dataclasses
    import re

    from repro import optim
    from repro.analysis import hlo
    from repro.configs import get_config
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import abstract_params
    from repro.kernels import backend as KB
    from repro.models.transformer import build_model

    b, t = 4, 128
    m = build_model(dataclasses.replace(get_config("tiny"), num_layers=2))
    dpc = DPConfig(mode="per_layer", sigma=1.0, sampling_rate=0.1, steps=10,
                   backend="pallas", autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=b)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, abstract_params(m.spec))
    opt_abs, dp_abs = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(init_fn, params))
    batch = {k: on_chip(jax.ShapeDtypeStruct((b, t), jnp.int32))
             for k in ("tokens", "targets")}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    with KB.scoped("pallas", interpret=False, prefer_fused=False):
        text = jax.jit(step_fn).lower(params, opt_abs, dp_abs, batch,
                                      key).compile().as_text()
    phases = hlo.op_phases(text)
    kernels = {}
    for instrs in hlo.parse_module(text).values():
        for ins in instrs:
            if ins.op in hlo.CONTAINER_OPS:
                assert ins.name not in phases
                continue
            assert phases[ins.name] is not None, ins.name
            if "dp_noise_add:" in ins.rest:
                assert phases[ins.name] == hlo.NOISE_UPDATE, ins.name
            k = re.search(r'op_name="[^"]*\b(ghost_norm|clip_reduce)\)?'
                          r'/pallas_call"', ins.rest)
            if k and 'custom_call_target="tpu_custom_call"' in ins.rest:
                kernels.setdefault(k.group(1), []).append(ins.name)
                assert phases[ins.name] == hlo.BACKWARD, ins.name
    assert set(kernels) == {"ghost_norm", "clip_reduce"}
    for kernel, names in kernels.items():
        assert all(kernel in n for n in names), names
    assert {hlo.FORWARD, hlo.BACKWARD, hlo.NOISE_UPDATE} <= set(
        phases.values())


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    yield from _eqns(sub)


def test_cell_step_clip_reduce_takes_bf16_unpadded(one_chip):
    """The qwen3-4b cell's step (one layer: every linear shape of the cell
    once) for a described v5e: each `clip_reduce` call takes the bf16
    activations and gradients as they are, (B·T, din) and (B·T, dout), and
    returns the (din, dout) f32 sum itself, so no pad of G and no slice of
    the sum surround it, the untied head's included."""
    import dataclasses
    import re

    from repro import optim
    from repro.configs import get_config
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import abstract_params
    from repro.kernels import backend as KB
    from repro.models.transformer import build_model

    m = build_model(dataclasses.replace(get_config("qwen3-4b"), num_layers=1))
    dpc = DPConfig(mode="per_layer", execution="bk", sigma=1.0,
                   sampling_rate=B / 1024, steps=10, adaptive=True,
                   backend="pallas", autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=B)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, abstract_params(m.spec))
    opt_abs, dp_abs = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(init_fn, params))
    batch = {k: on_chip(jax.ShapeDtypeStruct((B, T), jnp.int32))
             for k in ("tokens", "targets")}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    with KB.scoped("pallas", interpret=False):
        traced = jax.jit(step_fn).trace(params, opt_abs, dp_abs, batch, key)
        text = traced.lower().compile().as_text()
    # inside each kernel the MXU's dot takes the bf16 blocks as they are
    dots = [[str(v.aval.dtype) for v in dot.invars]
            for k in _eqns(traced.jaxpr.jaxpr)
            if k.primitive.name == "pallas_call"
            and str(k.params["name"]) == "clip_reduce"
            for dot in _eqns(k.params["jaxpr"])
            if dot.primitive.name == "dot_general"]
    assert dots and all(d == ["bfloat16", "bfloat16"] for d in dots), dots
    call = re.compile(
        r"= f32\[(\d+),(\d+)\]\S* custom-call\(.*operand_layout_constraints="
        r"\{f32\[4\]\{0\}, (\w+)\[(\d+),(\d+)\]\{1,0\}, "
        r"(\w+)\[(\d+),(\d+)\]\{1,0\}\}")
    shapes = []
    for ln in text.splitlines():
        if ('custom_call_target="tpu_custom_call"' not in ln
                or not re.search(r"clip_reduce\)?/pallas_call", ln)):
            continue
        mt = call.search(ln)
        assert mt, ln[:300]
        din, dout, ta, ra, ca, tg, rg, cg = mt.groups()
        assert (ta, tg) == ("bf16", "bf16"), ln[:300]
        assert (ra, ca, rg, cg) == (str(B * T), din, str(B * T), dout)
        shapes.append((int(din), int(dout)))
    assert sorted(shapes) == sorted([(D, 48 * HD), (32 * HD, D),
                                     (D, 2 * FF), (FF, D), (D, VOCAB)])


def test_minicpm_cell_step_compiles_for_v5e_and_fits(one_chip):
    """The `train.minicpm-2b.ghost_flat.t2048` cell's whole step for a
    described v5e: MiniCPM-2B as published cut to 6 of 40 layers, tied and
    muP, ghost_flat through BK, B=2, T=2048, the Pallas kernels as `auto`
    picks them on the chip. The tied head's clipped sum is one
    `bk_scale_contract` into the table's (V, d) layout, the cross term is
    in the program, and the compiler's memory estimate fits one chip's
    16 GB, checked here before the step is run on a chip."""
    import dataclasses
    import re

    from repro import optim
    from repro.configs import get_config
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import abstract_params
    from repro.kernels import backend as KB
    from repro.models.transformer import build_model

    b, t = 2, 2048
    cfg = dataclasses.replace(get_config("minicpm-2b"), num_layers=6)
    m = build_model(cfg)
    dpc = DPConfig(mode="ghost_flat", execution="bk", sigma=1.0,
                   sampling_rate=b / 1024, steps=1000, adaptive=False,
                   backend="pallas", autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=b)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, abstract_params(m.spec))
    opt_abs, dp_abs = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(init_fn, params))
    batch = {k: on_chip(jax.ShapeDtypeStruct((b, t), jnp.int32))
             for k in ("tokens", "targets")}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    with KB.scoped("pallas", interpret=False):
        compiled = jax.jit(step_fn, donate_argnums=(0, 1, 2)).lower(
            params, opt_abs, dp_abs, batch, key).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"minicpm-2b-6l ghost_flat BK step, v5e memory_analysis: "
          f"arguments {mem.argument_size_in_bytes} B, outputs "
          f"{mem.output_size_in_bytes} B, aliased {mem.alias_size_in_bytes}"
          f" B, temporaries {mem.temp_size_in_bytes} B, total {total} B")
    assert total < 16e9
    # (1, V, d) out, V = 122,753 rows
    heads = [int(mt.group(1)) for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "bk_scale_contract" in ln
             for mt in [re.search(r"= f32\[1,(\d+),2304\]", ln)] if mt]
    assert heads and all(v >= 122753 for v in heads), heads
    assert "dp_tied_cross" in text


def test_minicpm_step_scale_contract_takes_bf16_unpadded(one_chip):
    """The MiniCPM cell's step (two of its layers, so each layer weight is
    a stack of S = 2 and the tied head a stack of one) for a described v5e:
    each `bk_scale_contract` dot takes bf16 blocks, the tied head's
    included, and each call takes the cached residuals as they are, (S,
    B·T, din) and (S, B·T, dout), and returns the (S, din, dout) f32 sum
    itself, so no pad of the residuals and no slice of the sum surround
    it."""
    import dataclasses
    import re

    from repro import optim
    from repro.configs import get_config
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import abstract_params
    from repro.kernels import backend as KB
    from repro.models.transformer import build_model

    b, t, s = 2, 2048, 2
    cfg = dataclasses.replace(get_config("minicpm-2b"), num_layers=s)
    m = build_model(cfg)
    dpc = DPConfig(mode="ghost_flat", execution="bk", sigma=1.0,
                   sampling_rate=b / 1024, steps=1000, adaptive=False,
                   backend="pallas", autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=b)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, abstract_params(m.spec))
    opt_abs, dp_abs = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(init_fn, params))
    batch = {k: on_chip(jax.ShapeDtypeStruct((b, t), jnp.int32))
             for k in ("tokens", "targets")}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    with KB.scoped("pallas", interpret=False):
        traced = jax.jit(step_fn).trace(params, opt_abs, dp_abs, batch, key)
        text = traced.lower().compile().as_text()
    dots = [[str(v.aval.dtype) for v in dot.invars]
            for k in _eqns(traced.jaxpr.jaxpr)
            if k.primitive.name == "pallas_call"
            and str(k.params["name"]) == "bk_scale_contract"
            for dot in _eqns(k.params["jaxpr"])
            if dot.primitive.name == "dot_general"]
    assert len(dots) == 5 and all(d == ["bfloat16", "bfloat16"]
                                  for d in dots), dots
    call = re.compile(
        r"= f32\[(\d+),(\d+),(\d+)\]\S* custom-call\(.*"
        r"operand_layout_constraints=\{f32\[\d+,2\]\{1,0\}, "
        r"(\w+)\[(\d+),(\d+),(\d+)\]\{2,1,0\}, "
        r"(\w+)\[(\d+),(\d+),(\d+)\]\{2,1,0\}\}")
    shapes = []
    for ln in text.splitlines():
        if ('custom_call_target="tpu_custom_call"' not in ln
                or not re.search(r"bk_scale_contract\)?/pallas_call", ln)):
            continue
        mt = call.search(ln)
        assert mt, ln[:300]
        ss, din, dout, ta, sa, ra, ca, tg, sg, rg, cg = mt.groups()
        assert (ta, tg) == ("bf16", "bf16"), ln[:300]
        assert (sa, ra, ca) == (ss, str(b * t), din), ln[:300]
        assert (sg, rg, cg) == (ss, str(b * t), dout), ln[:300]
        shapes.append((int(ss), int(din), int(dout)))
    d, ff = cfg.d_model, cfg.d_ff
    assert sorted(shapes) == sorted([
        (1, cfg.vocab_size, d), (s, d, 3 * d), (s, d, d), (s, d, 2 * ff),
        (s, ff, d)]), shapes

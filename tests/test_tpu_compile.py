"""The Pallas kernels of the main path compile for a TPU v5e at qwen3-4b
widths (d_model 2560, d_ff 9728, 8 KV heads of 128, B=4, T=512).

The chip is described, not attached: the TPU compiler refuses here what the
chip would refuse (misaligned blocks, too much VMEM), and no test needs a
device. The topology is described inside a fixture, never at import, so
that only the worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bk import scale_contract
from repro.kernels.clip_reduce import clip_reduce
from repro.kernels.fused_clip import fused_norm_clip
from repro.kernels.ghost_norm import ghost_norm, ghost_norm_blocked
from repro.kernels.paged_attn import paged_attn

B, T, D, FF = 4, 512, 2560, 9728
KV, G, HD, PAGE = 8, 4, 128, 16
PAGES = 4 * 43 + 1  # 4 slots x 688-token horizon, plus the trash page


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
    "bk_scale_contract": (lambda a, g, f: scale_contract(a, g, f),
                          [_bf16((4, B, T, D)), _bf16((4, B, T, FF)),
                           _f32((4, B))]),
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
    assert any(f"{name}/pallas_call" in ln for ln in kernel_lines)


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

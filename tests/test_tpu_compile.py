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

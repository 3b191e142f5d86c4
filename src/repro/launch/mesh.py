"""Production meshes.

Single pod:  (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with `Auto` axes. Meshes default to `Explicit` axes,
    under which `with_sharding_constraint` turns into an assert and every
    computation must name its mesh; the step code shards through GSPMD
    propagation and manual `shard_map` bodies, which is what `Auto` means."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes forming the data-parallel plane."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def named_shard_map(f, mesh, *, in_specs, out_specs):
    """`jax.shard_map` (manual SPMD) with the replication check off.

    The sharded DP train step relies on values that ARE replicated but that
    the checker cannot prove so (masked per-shard contributions joined by a
    psum), hence check_vma off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

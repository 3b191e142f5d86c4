"""Executable sharded-engine checks (needs >= 8 devices BEFORE jax init).

Run directly (the CI 8-virtual-device stage does):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python tests/sharded_checks.py

or through `tests/test_sharded.py`, which spawns this module in a
subprocess so the forced device count never leaks into the main test
process. Prints one `RESULT {json}` line; exit code 0 iff every check
passed.

Checks (sharded == single-device, same math different communication):
  * clip-level parity — grads, per-group norms², clip counts — for
    per_layer / ghost_flat / per_group (bk AND the twopass fallback);
  * full-step parity after 2 steps (params, quantile thresholds, metrics)
    for all three modes, plus microbatches=2;
  * the DP-LoRA trainable_key path (ghost_flat on a reduced qwen3-4b);
  * the Sec-4 communication contract from compiled HLO: per-device
    (per_group) has ZERO model-axis collectives in norm computation,
    ghost_flat has >= 1 (launch.hlo_analysis.model_axis_norm_collectives);
  * the quantile contract: shard-local clip counts psum'd over the data
    plane (quantile.update_thresholds counts_axes=) reproduce the
    single-device geometric update bit-for-bit on every shard;
  * checkpoint round-trip of model-sharded params (the train.py --mesh
    resume path): save -> restore with target shardings (zlib fallback
    codec forced) -> one more step bitwise-equal to the uninterrupted run.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro import optim
from repro.configs import get_config
from repro.core.clipping import dp_clipped_gradients, sharded_clipped_gradients
from repro.core.dp_sgd import DPConfig, make_dp_train_step
from repro.core.spec import abstract_params, init_params
from repro.launch.hlo_analysis import model_axis_norm_collectives
from repro.launch.inputs import concrete_train_batch
from repro.launch.mesh import make_debug_mesh, named_shard_map
from repro.launch.sharding import group_shard_assignment
from repro.models.transformer import build_model

B, T = 8, 16


def _close(a, b, rtol=2e-4, atol=2e-6):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol)


def _sharded_clip(m, mesh, params, batch, bsz, mode, execution, assign_arr,
                  trainable_key=None, **mode_kw):
    """Run sharded_clipped_gradients inside shard_map; global outputs."""
    dax = tuple(a for a in mesh.axis_names if a != "model")
    d_size = int(np.prod([mesh.shape[a] for a in dax]))

    def body(params, batch):
        res = sharded_clipped_gradients(
            m.loss_fn, params, batch, m.layout, mode=mode,
            batch_size=bsz // d_size, data_size=d_size, data_axes=dax,
            model_axis="model", shard_assignment=assign_arr,
            trainable_key=trainable_key, execution=execution, **mode_kw)
        return tuple(res)  # plain tuple: out_specs prefix-match

    f = named_shard_map(body, mesh, in_specs=(PS(), PS(dax)),
                        out_specs=(PS(), PS(None, dax), PS(), PS()))
    from repro.core.clipping import ShardedClipResult
    return ShardedClipResult(*jax.jit(f)(params, batch))


def check_clip_parity(m, mesh, params, batch, assign, results):
    assign_arr = jnp.asarray(np.asarray(assign), jnp.int32)
    M = int(mesh.shape["model"])
    th = jnp.linspace(0.3, 0.6, m.layout.num_groups)
    gth = jnp.linspace(0.3, 0.6, M)
    cases = [
        ("per_layer", "bk", dict(thresholds=th), dict(thresholds=th)),
        ("ghost_flat", "bk", dict(flat_threshold=0.5),
         dict(flat_threshold=0.5)),
        ("ghost_flat", "twopass", dict(flat_threshold=0.5),
         dict(flat_threshold=0.5)),
        ("per_group", "bk", dict(group_thresholds=gth),
         dict(group_assignment=assign_arr, group_thresholds=gth)),
        ("per_group", "twopass", dict(group_thresholds=gth),
         dict(group_assignment=assign_arr, group_thresholds=gth)),
    ]
    for mode, execution, skw, rkw in cases:
        name = f"clip_parity_{mode}_{execution}"
        try:
            got = _sharded_clip(m, mesh, params, batch, B, mode, execution,
                                assign_arr, **skw)
            want = dp_clipped_gradients(m.loss_fn, params, batch, m.layout,
                                        mode=mode, batch_size=B,
                                        execution=execution, **rkw)
            np.testing.assert_allclose(np.asarray(got.norms_sq),
                                       np.asarray(want.norms_sq),
                                       rtol=1e-4, atol=1e-7)
            _close(got.grads, want.grads)
            results[name] = "ok"
        except Exception as e:  # noqa: BLE001
            results[name] = f"{type(e).__name__}: {e}"


def _two_steps(m, dpc, params, batch, mesh=None):
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.sgd(0.1), dpc, batch_size=B,
        mesh=mesh)
    opt_state, dp_state = init_fn(params)
    step = jax.jit(step_fn)
    p, o, d = params, opt_state, dp_state
    for _ in range(2):
        p, o, d, met = step(p, o, d, batch, jax.random.PRNGKey(5))
    return p, d, met


def check_step_parity(m, mesh, params, batch, assign, results):
    M = int(mesh.shape["model"])
    for mode, nmb in (("per_layer", 1), ("ghost_flat", 1), ("per_group", 1),
                      ("ghost_flat", 2), ("per_group", 2)):
        name = f"step_parity_{mode}" + (f"_mb{nmb}" if nmb > 1 else "")
        try:
            kw = dict(mode=mode, sigma=1.0, sampling_rate=0.1, steps=10,
                      adaptive=True, microbatches=nmb)
            if mode == "per_group":
                kw.update(group_assignment=assign, num_supergroups=M)
            dpc = DPConfig(**kw)
            p1, d1, met1 = _two_steps(m, dpc, params, batch)
            p2, d2, met2 = _two_steps(m, dpc, params, batch, mesh=mesh)
            _close(p1, p2)
            _close(d1.qstate.thresholds, d2.qstate.thresholds)
            np.testing.assert_allclose(float(met1.clip_fraction),
                                       float(met2.clip_fraction), atol=1e-5)
            np.testing.assert_allclose(float(met1.loss), float(met2.loss),
                                       rtol=1e-5)
            results[name] = "ok"
        except Exception as e:  # noqa: BLE001
            results[name] = f"{type(e).__name__}: {e}"


def check_lora(mesh4, results):
    """DP-LoRA trainable_key path on a (2, 2) mesh."""
    name = "clip_parity_lora_ghost_flat"
    try:
        cfg = dataclasses.replace(get_config("qwen3-4b", reduced=True),
                                  lora_rank=4)
        m = build_model(cfg)
        params = init_params(m.spec, jax.random.PRNGKey(0))
        batch = concrete_train_batch(cfg, 4, T, jax.random.PRNGKey(1))
        lay = m.layout
        assign_arr = jnp.asarray(
            np.asarray(group_shard_assignment(lay, 2)), jnp.int32)

        def body(params, batch):
            return tuple(sharded_clipped_gradients(
                m.loss_fn, params, batch, lay, mode="ghost_flat",
                batch_size=2, data_size=2, data_axes=("data",),
                model_axis="model", shard_assignment=assign_arr,
                flat_threshold=0.5, trainable_key="lora"))

        f = named_shard_map(body, mesh4, in_specs=(PS(), PS("data")),
                            out_specs=(PS(), PS(None, "data"), PS(), PS()))
        from repro.core.clipping import ShardedClipResult
        got = ShardedClipResult(*jax.jit(f)(params, batch))
        want = dp_clipped_gradients(m.loss_fn, params, batch, lay,
                                    mode="ghost_flat", batch_size=4,
                                    flat_threshold=0.5, trainable_key="lora")
        assert set(got.grads) == {"lora"}
        np.testing.assert_allclose(np.asarray(got.norms_sq),
                                   np.asarray(want.norms_sq), rtol=1e-4,
                                   atol=1e-7)
        _close(got.grads, want.grads)
        results[name] = "ok"
    except Exception as e:  # noqa: BLE001
        results[name] = f"{type(e).__name__}: {e}"


def check_quantile_sharded(mesh, results):
    """One geometric update from GLOBAL counts: shard-local clip counts +
    the data-plane psum inside update_thresholds must reproduce the
    single-device quantile state exactly (replicated across every shard,
    asserted by the PS() out_spec)."""
    from repro.core.quantile import (clip_counts, init_quantile_state,
                                     update_thresholds)
    name = "quantile_sharded_parity"
    try:
        k = 5
        norms = jax.random.uniform(jax.random.PRNGKey(3), (k, B)) * 0.8
        state = init_quantile_state(np.linspace(0.2, 1.0, k), sigma_b=3.0)
        key = jax.random.PRNGKey(7)
        want = update_thresholds(
            state, clip_counts(norms, state.thresholds), B, key)
        dax = tuple(a for a in mesh.axis_names if a != "model")

        def body(norms_local):
            local = clip_counts(norms_local, state.thresholds)
            return update_thresholds(state, local, B, key,
                                     counts_axes=dax).thresholds

        f = named_shard_map(body, mesh, in_specs=(PS(None, dax),),
                            out_specs=PS())
        got = jax.jit(f)(norms)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.thresholds))
        results[name] = "ok"
    except Exception as e:  # noqa: BLE001
        results[name] = f"{type(e).__name__}: {e}"


def check_checkpoint_roundtrip(m, mesh, params, batch, results):
    """train.py --mesh resume path: 2 sharded steps -> save (params STORED
    model-sharded, zlib fallback codec) -> restore with target shardings
    -> step 3 bitwise-equal to the uninterrupted run."""
    import shutil
    import tempfile

    from repro.checkpoint import store as store_mod
    from repro.checkpoint.store import load_checkpoint, save_checkpoint
    from repro.launch.sharding import params_shardings

    name = "checkpoint_roundtrip_sharded"
    had_zstd = store_mod.zstd
    tmp = tempfile.mkdtemp(prefix="ckpt_roundtrip_")
    try:
        dpc = DPConfig(mode="ghost_flat", sigma=1.0, sampling_rate=0.1,
                       steps=10, adaptive=True)
        init_fn, step_fn, _ = make_dp_train_step(
            m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc,
            batch_size=B, mesh=mesh)
        pshard = params_shardings(m.spec, mesh)
        step = jax.jit(step_fn,
                       in_shardings=(pshard, None, None, None, None),
                       out_shardings=(pshard, None, None, None))
        opt_state, dp_state = init_fn(params)
        p = jax.device_put(params, pshard)
        key = jax.random.PRNGKey(11)
        for _ in range(2):
            p, opt_state, dp_state, _ = step(p, opt_state, dp_state, batch,
                                             key)

        tree = {"params": p, "opt": opt_state, "dp": dp_state}
        store_mod.zstd = None  # force + cover the stdlib zlib fallback
        path = save_checkpoint(tmp, 2, tree)
        import msgpack
        with open(os.path.join(path, "manifest.msgpack"), "rb") as fh:
            assert msgpack.unpackb(fh.read())["codec"] == "zlib"
        nil = jax.tree_util.tree_map(lambda _: None,
                                     {"opt": opt_state, "dp": dp_state})
        restored = load_checkpoint(
            tmp, 2, tree, shardings={"params": pshard, **nil})
        for leaf, sh in zip(jax.tree_util.tree_leaves(restored["params"]),
                            jax.tree_util.tree_leaves(pshard)):
            assert leaf.sharding == sh, (leaf.sharding, sh)
        # resumed step == uninterrupted step, bitwise
        a = step(p, opt_state, dp_state, batch, key)
        b = step(restored["params"], restored["opt"], restored["dp"],
                 batch, key)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        results[name] = "ok"
    except Exception as e:  # noqa: BLE001
        results[name] = f"{type(e).__name__}: {e}"
    finally:
        store_mod.zstd = had_zstd
        shutil.rmtree(tmp, ignore_errors=True)


def check_hlo_axis_contract(m, mesh, params, batch, assign, results):
    """Sec 4, asserted from compiled HLO: per-device clipping moves ZERO
    norm information across the model axis; flat clipping must."""
    M = int(mesh.shape["model"])
    params_abs = abstract_params(m.spec)
    batch_abs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
    counts = {}
    for mode in ("ghost_flat", "per_group"):
        name = f"hlo_axis_{mode}"
        try:
            kw = dict(mode=mode, sigma=1.0, sampling_rate=0.1, steps=10,
                      backend="xla")
            if mode == "per_group":
                kw.update(group_assignment=assign, num_supergroups=M)
            init_fn, step_fn, _ = make_dp_train_step(
                m.loss_fn, m.spec, m.layout, optim.adam(1e-3), DPConfig(**kw),
                batch_size=B, mesh=mesh)
            opt_abs, dp_abs = jax.eval_shape(init_fn, params_abs)
            hlo = jax.jit(step_fn).lower(params_abs, opt_abs, dp_abs,
                                         batch_abs,
                                         key_abs).compile().as_text()
            n = sum(r["count"] for r in model_axis_norm_collectives(hlo, mesh))
            counts[mode] = n
            ok = (n == 0) if mode == "per_group" else (n >= 1)
            results[name] = ("ok" if ok else
                             f"model-axis norm collectives = {n}")
        except Exception as e:  # noqa: BLE001
            results[name] = f"{type(e).__name__}: {e}"
    results["hlo_axis_counts"] = counts


def main() -> int:
    assert jax.device_count() >= 8, (
        f"need 8 devices, got {jax.device_count()}; run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    results: dict = {}
    try:
        cfg = get_config("tiny")
        m = build_model(cfg)
        params = init_params(m.spec, jax.random.PRNGKey(0))
        batch = concrete_train_batch(cfg, B, T, jax.random.PRNGKey(1))
        mesh = make_debug_mesh(2, 4)
        mesh4 = jax.sharding.Mesh(
            np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
        assign = group_shard_assignment(m.layout, 4)

        check_clip_parity(m, mesh, params, batch, assign, results)
        check_step_parity(m, mesh, params, batch, assign, results)
        check_lora(mesh4, results)
        check_quantile_sharded(mesh, results)
        check_checkpoint_roundtrip(m, mesh, params, batch, results)
        check_hlo_axis_contract(m, mesh, params, batch, assign, results)
    except Exception:  # noqa: BLE001
        results["fatal"] = traceback.format_exc()[-2000:]
    print("RESULT " + json.dumps(results), flush=True)
    failed = [k for k, v in results.items()
              if k != "hlo_axis_counts" and v != "ok"]
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

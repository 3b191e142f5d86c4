"""Smoke run of the system's two hot paths on a TPU, through the CLIs.

    python chip_smoke.py             # one chip: train + serve at qwen3-4b
    python chip_smoke.py --chips 4   # four chips: the sharded step only

One process drives the chip. With no option it runs, at qwen3-4b's
published widths cut to 4 layers:

  * train: 3 steps each of non_private, per_layer, ghost_flat and
    per_group through `repro.launch.train.run` (B=4, T=512, BK execution,
    `--backend auto`, `--autotune off` so kernel choice comes from the
    committed cost model only);
  * parity: the ghost_flat clipping pass under the pallas and the xla
    ghost-op backends, compared on per-example norms² and loss;
  * serve: `repro.launch.serve.run` with the paged `DecodeEngine` (8 ragged
    requests of 64-512 prompt tokens behind a 128-token shared prefix, 4
    slots, 32 generated tokens), then the paged-attention kernel against
    its XLA gather path on the engine's own page pools.

`--chips 4` runs per_group and ghost_flat on a 2x2 (data x model) mesh and
the same steps unsharded on one device (`--group-count 2`, the same
supergroups), and compares their loss lines.

Every phase prints its compile seconds, a steady step or token time (a
smoke reading, not a benchmark), the process's `peak_bytes_in_use` and the
implementation each ghost op resolved to. A phase fails on a non-finite
reading, a BK step whose compiled HLO shows other than one backward pass,
a kernel that `auto` chose but the compiled program lacks, or a backend
disagreement beyond the tolerances below. The last line of a passing run
is one JSON object naming the device; a failing run, or one that finds no
TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# per-example norms²: both backends get the same bf16 residuals, but the
# two programs fuse the backward differently, so single bf16 roundings of
# the cotangents (2^-8 relative) can differ element-wise. Summed over a
# 512-token gram they stay far below 1e-2; a wrong tile, a dropped block or
# a missed off-diagonal doubling moves a norm by tens of percent.
NORMS_RTOL = 1e-2
# loss: same forward math in both programs, bf16 activations.
LOSS_RTOL = 2e-3
# paged attention: the XLA path runs its f32 dots at TPU default precision
# (one bf16 pass) while the kernel accumulates in f32, and the two
# associate the softmax differently; outputs are O(1) averages of bf16
# values. A wrong page or mask gives O(1) errors.
PAGED_TOL = 2e-2
# sharded vs unsharded loss lines: identical noise keys and math, but the
# data-axis psum and the per-shard batch reorder bf16 reductions.
SHARDED_LOSS_RTOL = 1e-2

TRAIN_MODES = ("non_private", "per_layer", "ghost_flat", "per_group")
SHARDED_MODES = ("per_group", "ghost_flat")
# the kernels each auto-dispatched op can compile to (launch order aside)
OP_KERNELS = {
    "norms": {"ghost_norm", "ghost_norm_blocked"},
    "clip_sum": {"clip_reduce"},
    "linear_clip": {"fused_norm_clip", "ghost_norm"},
    "scale_contract": {"bk_scale_contract"},
    "paged_attn": {"paged_attn"},
}


class SmokeFailure(Exception):
    """A phase produced a wrong, missing or non-finite result."""


@dataclasses.dataclass(frozen=True)
class Size:
    """The shapes a smoke run uses; FULL on the chip, TINY in CPU tests."""

    arch: str
    layers: int | None
    batch: int
    seq: int
    docs: int
    requests: int
    prompt_min: int
    prompt_max: int
    shared_prefix: int
    slots: int
    gen: int
    page_len: int

    def model_args(self) -> list[str]:
        out = ["--arch", self.arch]
        if self.layers is not None:
            out += ["--layers", str(self.layers)]
        return out


FULL = Size(arch="qwen3-4b", layers=4, batch=4, seq=512, docs=64,
            requests=8, prompt_min=64, prompt_max=512, shared_prefix=128,
            slots=4, gen=32, page_len=16)
TINY = Size(arch="tiny", layers=None, batch=4, seq=32, docs=32, requests=4,
            prompt_min=4, prompt_max=16, shared_prefix=8, slots=2, gen=4,
            page_len=8)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), from its own events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def _check_finite(what: str, values) -> None:
    bad = [v for v in values if not math.isfinite(v)]
    if bad:
        raise SmokeFailure(f"{what}: non-finite values {bad}")


def kernels_in(hlo_text: str) -> set[str]:
    """Names of the Pallas kernels compiled into a TPU program."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
            names.add(m.group(1) if m else "<unnamed>")
    return names


def missing_kernels(choices: dict, hlo_text: str) -> list[str]:
    """Ops that `auto` sent to Pallas but whose kernel the program lacks."""
    present = kernels_in(hlo_text)
    return sorted({op for (op, *_), impl in choices.items()
                   if impl == "pallas" and not OP_KERNELS[op] & present})


def _choice_summary(choices: dict) -> str:
    counts: dict = {}
    for (op, *_), impl in choices.items():
        counts[(op, impl)] = counts.get((op, impl), 0) + 1
    return ", ".join(f"{op}->{impl} x{n}"
                     for (op, impl), n in sorted(counts.items())) or "none"


def _peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats()
    return (str(stats["peak_bytes_in_use"])
            if stats and "peak_bytes_in_use" in stats else "not reported")


def _clock_delta(clock, since: float) -> str:
    return "not measured" if clock is None else f"{clock.total - since:.2f}s"


def _train_argv(size: Size, mode: str, *, steps: int = 3,
                extra: tuple = ()) -> list[str]:
    return size.model_args() + [
        "--clipping", mode, "--execution", "bk", "--backend", "auto",
        "--autotune", "off", "--steps", str(steps),
        "--batch", str(size.batch), "--seq", str(size.seq),
        "--docs", str(size.docs), "--log-every", "1", *extra]


def train_phase(mode: str, size: Size = FULL, *, clock=None,
                extra: tuple = ()):
    """Three steps of one clipping mode through the training CLI; returns
    the `TrainRun` after checking it."""
    from repro.analysis.hlo import backward_passes
    from repro.kernels import backend as KB
    from repro.launch import train

    since = clock.total if clock is not None else 0.0
    with KB.recording_choices() as choices:
        report = train.run(_train_argv(size, mode, extra=extra))
    for key in ("loss", "grad_norm", "mean_threshold"):
        _check_finite(f"{mode} {key}", [m[key] for m in report.metrics])
    text = report.compiled.as_text()
    passes = backward_passes(text, report.cfg.num_layers)
    if passes != 1:
        raise SmokeFailure(f"{mode}: compiled step shows {passes} backward "
                           "passes, want 1")
    missing = missing_kernels(choices, text)
    if missing:
        raise SmokeFailure(f"{mode}: auto chose pallas for {missing} but "
                           "the compiled step holds no such kernel")
    steady = (statistics.median(report.step_s[1:])
              if len(report.step_s) > 1 else float("nan"))
    print(f"# train {mode}: compile {_clock_delta(clock, since)} "
          f"(step program {report.compile_s:.2f}s); steady step "
          f"{steady * 1e3:.1f} ms [smoke reading, not a benchmark]; "
          f"peak_bytes_in_use {_peak_bytes()}; backward passes {passes}; "
          f"ghost ops: {_choice_summary(choices)}; kernels: "
          f"{sorted(kernels_in(text)) or 'none'}", flush=True)
    return report


def parity_phase(size: Size = FULL, *, clock=None) -> None:
    """ghost_flat clipping under the pallas and the xla backends: step-0
    per-example norms² and loss must agree (NORMS_RTOL, LOSS_RTOL)."""
    import jax
    import numpy as np

    from repro.core.clipping import dp_clipped_gradients
    from repro.core.spec import init_params
    from repro.kernels import backend as KB
    from repro.launch.inputs import concrete_train_batch
    from repro.launch.train import build_arg_parser, config_from_args
    from repro.models.transformer import build_model

    since = clock.total if clock is not None else 0.0
    cfg = config_from_args(build_arg_parser().parse_args(size.model_args()))
    model = build_model(cfg)
    params = init_params(model.spec, jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, size.batch, size.seq,
                                 jax.random.PRNGKey(1))

    def clip(p, b):
        res = dp_clipped_gradients(model.loss_fn, p, b, model.layout,
                                   mode="ghost_flat", batch_size=size.batch,
                                   flat_threshold=1.0, execution="bk")
        return res.norms_sq, res.loss

    out, kernels = {}, {}
    for name in ("pallas", "xla"):
        # the backend scope is read at trace time and is not part of jit's
        # cache key: each backend gets a function of its own to trace
        fn = jax.jit(lambda p, b: clip(p, b))
        with KB.scoped(name, autotune=False):
            compiled = fn.lower(params, batch).compile()
        kernels[name] = kernels_in(compiled.as_text())
        out[name] = jax.device_get(compiled(params, batch))
    # (off-TPU the pallas backend interprets: no kernel in either program)
    if jax.default_backend() == "tpu" and (kernels["xla"]
                                           or not kernels["pallas"]):
        raise SmokeFailure(f"parity: programs hold kernels {kernels}, want "
                           "some under pallas and none under xla")
    (n_p, l_p), (n_x, l_x) = out["pallas"], out["xla"]
    _check_finite("parity norms", np.ravel(n_p).tolist()
                  + np.ravel(n_x).tolist() + [float(l_p), float(l_x)])
    norm_err = float(np.max(np.abs(n_p - n_x) / np.maximum(np.abs(n_x),
                                                           1e-30)))
    loss_err = abs(float(l_p) - float(l_x)) / abs(float(l_x))
    print(f"# parity ghost_flat pallas vs xla: compile "
          f"{_clock_delta(clock, since)}; max rel err norms² {norm_err:.2e} "
          f"(tol {NORMS_RTOL:g}), loss {loss_err:.2e} (tol {LOSS_RTOL:g}); "
          f"kernels: {sorted(kernels['pallas'])} vs none; "
          f"peak_bytes_in_use {_peak_bytes()}", flush=True)
    if not norm_err <= NORMS_RTOL or not loss_err <= LOSS_RTOL:
        raise SmokeFailure("pallas and xla ghost-op backends disagree: "
                           f"norms² {norm_err:.3e}, loss {loss_err:.3e}")


def _serve_argv(size: Size) -> list[str]:
    return size.model_args() + [
        "--mode", "engine", "--paging", "on", "--backend", "auto",
        "--autotune", "off", "--batch", str(size.requests),
        "--slots", str(size.slots), "--min-prompt-len", str(size.prompt_min),
        "--prompt-len", str(size.prompt_max), "--gen", str(size.gen),
        "--shared-prefix", str(size.shared_prefix),
        "--page-len", str(size.page_len)]


def serve_phase(size: Size = FULL, *, clock=None) -> None:
    """The paged decode engine through the serving CLI, then the paged
    attention kernel against its XLA gather path at the engine's pools."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import backend as KB
    from repro.launch import serve

    since = clock.total if clock is not None else 0.0
    with KB.recording_choices() as choices:
        report = serve.run(_serve_argv(size))
        eng = report.engine
        # the engine's decode program, traced under the CLI's engine scope
        with KB.scoped("auto", autotune=False):
            s = eng.num_slots
            text = eng._decode.lower(
                eng.params, eng.cache, jnp.zeros((s,), jnp.int32),
                jnp.ones((s,), bool), jnp.zeros((s,), jnp.int32)
            ).compile().as_text()
    compile_s = _clock_delta(clock, since)
    toks = report.tokens
    if toks.shape != (size.requests, size.gen) or (toks < 0).any() \
            or (toks >= report.cfg.vocab_size).any():
        raise SmokeFailure(f"serve: bad token grid {toks.shape} "
                           f"(min {toks.min()}, max {toks.max()})")
    if not eng.paged:
        raise SmokeFailure("serve: the engine did not page its KV cache")
    missing = missing_kernels(choices, text)
    if missing:
        raise SmokeFailure(f"serve: auto chose pallas for {missing} but the "
                           "decode program holds no such kernel")

    # the same traffic again: a first pass compiles whatever the prefix
    # hits newly need and keeps layer 0's pools and the page tables at the
    # first step with every slot live; the second pass is the warm reading
    for r in report.requests:
        eng.submit(r, max_new_tokens=size.gen)
    live = None
    while eng.num_pending or eng.num_live:
        eng.step()
        if live is None and eng.num_live == s:
            # slices and host copies: the next step donates the cache
            live = (eng.cache["dense_blocks_kpool"][0],
                    eng.cache["dense_blocks_vpool"][0],
                    np.asarray(eng.cache["pt"]), np.asarray(eng.cache["pos"]))
    if live is None:
        raise SmokeFailure(f"serve: the pool never held {s} live slots")
    steps0 = eng.stats["decode_dispatches"] + eng.stats["prefill_dispatches"]
    warm_since = clock.total if clock is not None else 0.0
    t0 = time.perf_counter()
    for r in report.requests:
        eng.submit(r, max_new_tokens=size.gen)
    eng.run()
    warm = time.perf_counter() - t0
    steps = (eng.stats["decode_dispatches"]
             + eng.stats["prefill_dispatches"] - steps0)

    # paged attention, pallas vs xla, on pools and tables of a full pool
    kpool, vpool, pt, pos = live
    kv, hd = kpool.shape[-2], kpool.shape[-1]
    grp = report.cfg.num_heads // kv
    q = jax.random.normal(jax.random.PRNGKey(2), (s, kv, grp, hd),
                          kpool.dtype)
    got = {name: np.asarray(jax.jit(
        lambda *a, e=KB.make_engine(name, autotune=False):
        e.paged_attn(*a, scale=hd ** -0.5))(q, kpool, vpool, pt, pos))
        for name in ("pallas", "xla")}
    _check_finite("paged_attn", np.ravel(got["pallas"]).tolist())
    # within atol = rtol = PAGED_TOL  <=>  err <= 1
    err = float(np.max(np.abs(got["pallas"] - got["xla"])
                       / (PAGED_TOL * (1.0 + np.abs(got["xla"])))))
    print(f"# serve paged engine: compile {compile_s}; {steps} pool steps "
          f"in {warm:.3f}s warm ({warm / max(steps, 1) * 1e3:.2f} ms/step, "
          f"compile inside {_clock_delta(clock, warm_since)}) "
          f"[smoke reading, not a benchmark]; pool {tuple(kpool.shape)}; "
          f"positions at full pool {pos.tolist()}; "
          f"peak_bytes_in_use {_peak_bytes()}; ghost ops: "
          f"{_choice_summary(choices)}; kernels: "
          f"{sorted(kernels_in(text)) or 'none'}; paged_attn pallas vs xla "
          f"scaled err {err:.2e} (tol 1)", flush=True)
    if not err <= 1.0:
        raise SmokeFailure(f"paged_attn pallas vs xla differ beyond "
                           f"atol=rtol={PAGED_TOL:g}")


def sharded_phase(size: Size = FULL, *, clock=None) -> None:
    """per_group and ghost_flat on a 2x2 mesh against the same steps on one
    device; loss lines must agree and parameters must live on 4 devices."""
    import jax

    from repro.analysis.hlo import model_axis_norm_collectives

    for mode in SHARDED_MODES:
        mesh_run = train_phase(mode, size, clock=clock,
                               extra=("--mesh", "2x2"))
        one_run = train_phase(mode, size, clock=clock,
                              extra=("--group-count", "2"))
        a = [m["loss"] for m in mesh_run.metrics]
        b = [m["loss"] for m in one_run.metrics]
        worst = max(abs(x - y) / abs(y) for x, y in zip(a, b))
        spread = {len({sh.device for sh in leaf.addressable_shards})
                  for leaf in jax.tree_util.tree_leaves(mesh_run.params)}
        norm_rows = model_axis_norm_collectives(mesh_run.compiled.as_text(),
                                                mesh_run.mesh)
        print(f"# sharded {mode}: loss 2x2 {a} vs 1 device {b}, max rel "
              f"diff {worst:.2e} (tol {SHARDED_LOSS_RTOL:g}); devices per "
              f"parameter {sorted(spread)}; model-axis norm collectives "
              f"{len(norm_rows)}", flush=True)
        if not worst <= SHARDED_LOSS_RTOL:
            raise SmokeFailure(f"sharded {mode}: loss lines differ {worst}")
        if spread != {4}:
            raise SmokeFailure(f"sharded {mode}: parameters on {spread} "
                               "devices, want 4 each")
        if mode == "per_group" and norm_rows:
            raise SmokeFailure(f"per_group: {len(norm_rows)} model-axis norm "
                               "collectives, want 0 (per-device clipping)")
        if mode == "ghost_flat" and not norm_rows:
            raise SmokeFailure("ghost_flat: no model-axis norm psum found; "
                               "the collective classifier sees nothing")
        del mesh_run, one_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + parity + serve on one chip; 4: the "
                         "sharded step on a 2x2 mesh and its reference")
    args = ap.parse_args(argv)
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); this smoke run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"# device {devices[0].device_kind} x{len(devices)}; "
          f"JAX_COMPILATION_CACHE_DIR="
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or 'unset'}",
          flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_phase(FULL, clock=clock)
        else:
            for mode in TRAIN_MODES:
                train_phase(mode, FULL, clock=clock)
            parity_phase(FULL, clock=clock)
            serve_phase(FULL, clock=clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"# all phases passed in {time.perf_counter() - t0:.1f}s, of "
          f"which compile {clock.total:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Driver: the program's private training step (`make_dp_train_step`),
jitted as the training CLI jits it (`launch.train.jit_step`), fed by the
program's Poisson sampler and batch builder, as `launch.train.run` feeds it.

Set-up builds the step and its state once and compiles it ahead of time.
From the initial state it steps `probe_batches` batches, each from the
state made again from the seed and with the window's key, so that every
probe draws the same noise; then it drives the object through
`check_steps` steps on distinct rows of a seeded permutation of the corpus,
and the window continues the same object on Poisson batches. The reference
follows once the window has closed and the program's state is freed.

Compared (worst case over batches, or over leaves):
  loss_gap    |program loss - reference loss| at the initial weights, on
              the first checked batch and on every probe batch
  clip_gap    per leaf, |<x, d> / <d, d> - 1|: x is the difference of the
              program's first Adam moments after the first checked batch
              and after the first probe batch, over (1 - b1), where the
              noise cancels; d is the reference's (S - S_probe) / B, the
              difference of the two clipped sums. It reads the per-example
              norms, clip factors and clipped sums; only the component of
              x along d is compared, since the rounding of the noised
              moments to their dtype swamps the rest, and only on leaves
              of `clip_leaf_min` elements or more, where that rounding
              averages out
  grad_gap    per leaf, |‖g‖ - ‖g_ref‖| of the first step's gradient as Adam
              gets it (the program's ‖mu‖ / (1 - b1) after one step), over
              max(‖g_ref‖ of the leaf, of the median leaf)
  change_gap  the same for ‖p_1 - p_0‖
Leaves whose reference gradient (for clip_gap, |d|) is under a thousandth
of the median leaf's are left out of clip_gap and change_gap.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import generate, harness
from bench.programs import program_config

LEAF_FLOOR = 1e-3  # of the median leaf's reference gradient norm
SLOWEST = 3  # window steps whose spans are logged


def leaf_norms(ref, tree) -> dict:
    import jax
    import jax.numpy as jnp
    flat = ref.flatten(tree)
    vals = jax.jit(lambda t: {p: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for p, v in t.items()})(flat)
    return {p: float(v) for p, v in vals.items()}


def leaf_sizes(ref, m) -> dict:
    return {p: math.prod(s) for p, s in ref.param_shapes(m).items()}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """max over leaves of |prog - ref| / max(ref, median ref)."""
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for p, r in ref.items():
        if keep is not None and p not in keep:
            continue
        gap = abs(prog[p] - r) / max(r, median, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), p
        if gap > worst:
            worst, where = gap, p
    return worst, where


def build(ctx, ref):
    """The program's model and jitted DP step, built as the training CLI
    builds them, once its parameters are checked to be the benchmark's
    layout."""
    import jax.numpy as jnp
    from repro import optim
    from repro.core.dp_sgd import DPConfig, make_dp_train_step
    from repro.core.spec import abstract_params
    from repro.launch.train import jit_step
    from repro.models.transformer import build_model

    tr = ctx.traffic
    pc = program_config(ctx.cfg)
    model = build_model(pc)
    m = ref.Dims.of(ctx.cfg)
    want = {p: (tuple(s), jnp.dtype(ctx.cfg["torch_dtype"]))
            for p, s in ref.param_shapes(m).items()}
    got = {p: (tuple(v.shape), jnp.dtype(v.dtype))
           for p, v in ref.flatten(abstract_params(model.spec)).items()}
    if want != got:
        raise harness.CellError(f"program parameters {got} are not the "
                                f"benchmark's layout {want}")
    dp, opt = tr["dp"], tr["optimizer"]
    b, rows = tr["batch"], tr["rows"]
    dpc = DPConfig(
        mode=dp["clipping"], execution=dp["execution"], epsilon=None,
        sigma=dp["sigma"], sampling_rate=b / rows, steps=tr["horizon"],
        adaptive=dp["adaptive"], init_threshold=dp["init_threshold"],
        target_quantile=dp["target_quantile"], quantile_lr=dp["quantile_lr"],
        quantile_budget_fraction=dp["quantile_budget"],
        noise_strategy="global", backend=tr["backend"], autotune=False)
    optimizer = optim.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                           eps=opt["eps"])
    init_fn, step_fn, _ = make_dp_train_step(
        model.loss_fn, model.spec, model.layout, optimizer, dpc,
        batch_size=b)
    return model, m, init_fn, jit_step(step_fn, model, None)


class Feed:
    """The window's feed and call: sampler (or given rows), batch builder,
    host-to-device copy, the compiled step, and the metrics fetch."""

    def __init__(self, ctx, rows, compiled, key):
        from repro.data import PoissonSampler
        tr = ctx.traffic
        self.ctx, self.rows, self.compiled, self.key = ctx, rows, compiled, key
        self.batch = tr["batch"]
        self.sampler = PoissonSampler(
            num_examples=rows.shape[0], rate=self.batch / rows.shape[0],
            max_batch=self.batch, seed=generate.seed32(ctx.seed, 5))

    def step(self, state, idx=None):
        import jax
        from repro.data import make_lm_batch
        spans = self.ctx.spans
        with spans("step"):
            if idx is None:
                with spans("sample"):
                    idx = self.sampler.next_indices()
            with spans("batch"):
                host = make_lm_batch(self.rows, idx, self.batch)
            with spans("put"):
                batch = jax.device_put(host)
            with spans("dispatch"):
                params, opt_state, dp_state, met = self.compiled(
                    *state, batch, self.key)
            with spans("fetch"):
                met = jax.device_get(met)
        return (params, opt_state, dp_state), float(met.loss), host


class Program:
    """The program's compiled step with its state, driven from a seed: one
    object, built and compiled once, stepped by the window's own feed."""

    def __init__(self, ctx, ref):
        import jax
        from repro.kernels import backend as KB
        from repro.data import make_lm_batch

        self.ctx, self.ref = ctx, ref
        tr = ctx.traffic
        self.model, self.m, init_fn, step = build(ctx, ref)
        dtype = ctx.cfg["torch_dtype"]
        self.init = jax.jit(lambda k: ref.init_params(self.m, k, dtype))
        self.init_state = jax.jit(init_fn)
        self.reset(ctx.seed)
        first = jax.device_put(make_lm_batch(self.rows, self.check_idx[0],
                                             tr["batch"]))
        with KB.recording_choices() as choices:
            self.compiled = step.lower(*self.state, first,
                                       self.key).compile()
        self.choices = [list(k) + [v] for k, v in choices.items()]
        ctx.log(f"step compiled; ghost ops {sorted(self.choices)}")
        self.feed = Feed(ctx, self.rows, self.compiled, self.key)
        b1 = tr["optimizer"]["b1"]
        # the difference of two first moments, over (1 - b1), kept in the
        # moments' dtype
        self._delta = jax.jit(lambda a, b: {
            p: ((a[p].astype("float32") - b[p].astype("float32"))
                / (1 - b1)).astype(a[p].dtype) for p in a})

    def fresh_state(self) -> None:
        """Weights, optimizer and DP state made again from the seed."""
        params = self.init(self.w_key)
        self.state = (params,) + tuple(self.init_state(params))

    def reset(self, seed: int) -> None:
        """Weights, optimizer and DP state, corpus, keys and the checked
        and probe batches from `seed`."""
        import jax
        tr = self.ctx.traffic
        self.seed = seed
        self.w_key = jax.random.PRNGKey(generate.seed32(seed, 10))
        self.fresh_state()
        self.rows = generate.train_rows(tr, self.m.vocab, seed)
        self.key = jax.random.PRNGKey(generate.seed32(seed, 11))
        b = tr["batch"]
        order = generate.rng(seed, 4).permutation(self.rows.shape[0])
        n_check, n_probe = tr["check_steps"], tr["probe_batches"]
        batches = [order[i * b:(i + 1) * b]
                   for i in range(n_check + n_probe)]
        self.check_idx, self.probe_idx = batches[:n_check], batches[n_check:]
        if hasattr(self, "feed"):
            self.feed = Feed(self.ctx, self.rows, self.compiled, self.key)

    def change_norms(self) -> dict:
        """{leaf: ‖p - p_0‖}, p_0 made again from the seed."""
        import jax
        import jax.numpy as jnp
        ref = self.ref
        fn = jax.jit(lambda p, k: {
            q: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)
                                           - v0.astype(jnp.float32))))
            for (q, v), v0 in zip(ref.flatten(p).items(),
                                  ref.flatten(self.init(k)).values())})
        return {p: float(v) for p, v in fn(self.state[0],
                                            self.w_key).items()}

    def check(self) -> tuple:
        """The probe batches, then the checked steps. Returns (readings,
        batches, probes): readings = {losses, probe_losses, grad1, change,
        delta}, delta on the host: {leaf: (mu_1 - mu_probe) / (1 - b1)}."""
        import jax
        b1 = self.ctx.traffic["optimizer"]["b1"]
        out = {"losses": [], "probe_losses": [], "grad1": None,
               "change": None, "delta": None}
        probes, mu_probe = [], None
        for j, idx in enumerate(self.probe_idx):
            if j:
                self.fresh_state()
            state, loss, host = self.feed.step(self.state, idx)
            out["probe_losses"].append(loss)
            probes.append(host)
            if j == 0:
                mu_probe = self.ref.flatten(state[1].mu)
            del state
        self.fresh_state()
        batches = []
        for i, idx in enumerate(self.check_idx):
            self.state, loss, host = self.feed.step(self.state, idx)
            out["losses"].append(loss)
            batches.append(host)
            if i == 0:
                mu = self.state[1].mu
                out["grad1"] = {p: v / (1 - b1) for p, v in leaf_norms(
                    self.ref, mu).items()}
                out["change"] = self.change_norms()
                if mu_probe is not None:
                    out["delta"] = jax.device_get(
                        self._delta(self.ref.flatten(mu), mu_probe))
                    mu_probe = None
        return out, batches, probes

    def free(self) -> None:
        self.state = None
        self.feed.rows = None
        gc.collect()


def run(ctx) -> dict:
    tr = ctx.traffic
    ref = harness.load_module(ctx.cell.root, "reference", ctx.cfg["family"])
    prog = Program(ctx, ref)
    readings, batches, probes = prog.check()

    steps = failed = 0
    slowest = []
    with ctx.window():
        t0 = t_prev = time.perf_counter()
        while True:
            before = dict(ctx.spans.seconds)
            prog.state, loss, _ = prog.feed.step(prog.state)
            steps += 1
            failed += 0 if math.isfinite(loss) else 1
            t1 = time.perf_counter()
            slowest = sorted(slowest + [(t1 - t_prev, steps, {
                k: v - before.get(k, 0.0) for k, v in ctx.spans.seconds.items()
                if k != "window"})], key=lambda s: -s[0])[:SLOWEST]
            t_prev = t1
            if t1 - t0 >= ctx.seconds:
                break
    window_s = t1 - t0
    ctx.read_memory()
    stats = {"steps": steps, "window_s": window_s,
             "host_s": dict(ctx.spans.seconds),
             "host_n": dict(ctx.spans.counts), "choices": prog.choices}
    e2e = {"train_tokens_per_s": steps * tr["batch"] * tr["seq"] / window_s}
    ctx.log(f"window: {steps} steps in {window_s:.3f} s; memory peak "
            f"{ctx.memory_peak_bytes} B")
    for dt, n, spans in slowest:
        ctx.log(f"slow step {n}: {dt * 1e3:.1f} ms; " + ", ".join(
            f"{k} {v * 1e3:.1f}" for k, v in spans.items()))
    prog.free()

    t0 = time.perf_counter()
    r = reference_readings(ctx, ref, prog, batches[0], probes,
                           {"program": readings["delta"]})
    out = gaps(readings, r, leaf_sizes(ref, prog.m), tr["clip_leaf_min"])
    ctx.log(f"reference: {time.perf_counter() - t0:.1f} s; program losses "
            f"{readings['losses'][:1] + readings['probe_losses']} reference "
            f"{r['losses'] + r['probe_losses']}")
    for name, (value, where) in out.items():
        ctx.log(f"{name} {value!r} at {where}")
    return {"attempted": steps, "failed": failed, "e2e": e2e,
            "stats": stats, "compared": {k: v for k, (v, _) in out.items()}}


def projection_stats():
    """jitted (x, d, rows) -> (<x, d>, <d, d>, |x - d|^2) per row (layer)
    of a leaf."""
    import jax
    import jax.numpy as jnp

    def fn(x, d, rows):
        x = x.astype(jnp.float32).reshape(rows, -1)
        d = d.reshape(rows, -1)
        return (jnp.sum(x * d, axis=1), jnp.sum(d * d, axis=1),
                jnp.sum(jnp.square(x - d), axis=1))

    return jax.jit(fn, static_argnums=2)


def reference_readings(ctx, ref, prog, batch, probes, deltas: dict, *,
                       precision="f32", fault=None, keep_delta=False) -> dict:
    """The readings of the reference (or of the control, or of a planted
    fault), in the program's readings' form: its losses at the initial
    weights on `batch` and on every probe, and its first step's gradient and
    change. With the float32 reference, `projections` holds for each
    entry of `deltas` ({name: {leaf: x on the host}}) the per-row sums
    <x, d>, <d, d> and |x - d|^2 against the reference's d. With
    `keep_delta`, `delta` holds the reading's own x, on the host."""
    import jax
    import jax.numpy as jnp
    dp = ref.DPReference(prog.m, ref.Job.of(ctx.traffic),
                         precision=precision, fault=fault)
    params0 = prog.init(prog.w_key)
    state = dp.initial_state(params0)
    key = jax.random.PRNGKey(generate.seed32(ctx.seed, 12))
    stats = projection_stats()
    out = {"projections": {n: {} for n in deltas}, "delta": {}}

    def read(p, exact, stored):
        rows = dp.offsets[p][1]
        for name, delta in deltas.items():
            s = stats(jnp.asarray(delta[p]), exact, rows)
            out["projections"][name][p] = [np.asarray(v, np.float64)
                                           for v in s]
        if keep_delta:
            out["delta"][p] = jax.device_get(stored.astype(
                state["params"][p].dtype))

    probe_loss = [dp.batch_loss(state["params"], h["tokens"], h["targets"])
                  for h in probes[1:]]
    loss, gn, loss_o = dp.step(state, batch["tokens"], batch["targets"],
                               key, other=(probes[0]["tokens"],
                                           probes[0]["targets"]), read=read)
    del params0
    p0 = ref.flatten(prog.init(prog.w_key))
    dist = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    out.update(losses=[loss], probe_losses=[loss_o] + probe_loss, grad1=gn,
               change={p: float(dist(v, p0[p]))
                       for p, v in state["params"].items()})
    del state, p0
    gc.collect()
    return out


def clip_gap(proj: dict, sizes: dict, min_size: int) -> tuple:
    """max over the leaves of at least `min_size` elements of
    |<x, d> / <d, d> - 1|, leaving out leaves whose |d| is under a
    thousandth of the median leaf's. No such leaf reads inf."""
    norms = {p: math.sqrt(float(np.sum(dd))) for p, (_, dd, _) in proj.items()}
    floor = LEAF_FLOOR * float(np.median(list(norms.values())))
    kept = [p for p in proj if norms[p] >= floor and sizes[p] >= min_size]
    if not kept:
        return float("inf"), None
    worst, where = 0.0, kept[0]
    for p in kept:
        xd, dd, _ = proj[p]
        gap = abs(float(np.sum(xd)) / float(np.sum(dd)) - 1.0)
        if not math.isfinite(gap):
            return float("inf"), p
        if gap > worst:
            worst, where = gap, p
    return worst, where


def gaps(prog: dict, ref_out: dict, sizes: dict, min_size: int,
         name: str = "program") -> dict:
    """{name: (value, where)} of the compared numbers of one reading
    against the float32 reference's; `sizes` are the leaves' elements."""
    median_g = float(np.median(list(ref_out["grad1"].values())))
    keep = {p for p, v in ref_out["grad1"].items()
            if v >= LEAF_FLOOR * median_g}
    losses = list(zip(prog["losses"][:1] + prog["probe_losses"],
                      ref_out["losses"] + ref_out["probe_losses"]))
    return {
        "loss_gap": max(((abs(a - b), f"batch {i}")
                         for i, (a, b) in enumerate(losses)),
                        key=lambda t: t[0]),
        "clip_gap": clip_gap(ref_out["projections"][name], sizes, min_size),
        "grad_gap": worst_leaf_gap(prog["grad1"], ref_out["grad1"]),
        "change_gap": worst_leaf_gap(prog["change"], ref_out["change"],
                                     keep),
    }


VARIANTS = (("control", {"precision": "fp8"}),
            ("half_sum", {"fault": "half_sum"}),
            ("norm_sq", {"fault": "norm_sq"}),
            ("half_batch", {"fault": "half_batch"}))


def calibrate(ctx, ref, seeds: list) -> list:
    """One compiled program for all seeds: per seed, fresh weights, corpus
    and state, the probes and checked steps, then against the float32
    reference:
      program     the program's own readings;
      control     the reference computed through fp8, put in the program's
                  place;
      half_sum    the reference with half of the rows left out of the
                  clipped sum, the mean still over B;
      norm_sq     the reference with clip factors from the squared norm;
      half_batch  the reference with half of the batch left out and the
                  mean taken over the rest.
    A step that returns its state unchanged reads 1 on grad_gap and
    change_gap by construction and needs no run."""
    prog = Program(ctx, ref)
    sizes = leaf_sizes(ref, prog.m)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        ctx.seed = seed
        if prog.state is None:
            prog.reset(seed)
        readings, batches, probes = prog.check()
        prog.free()
        got = {"program": readings}
        for name, kw in VARIANTS:
            got[name] = reference_readings(ctx, ref, prog, batches[0], probes,
                                           {}, keep_delta=True, **kw)
        full = reference_readings(ctx, ref, prog, batches[0], probes,
                                  {n: g["delta"] for n, g in got.items()})
        row = {"seed": seed}
        for name, g in got.items():
            row[name] = {k: v for k, (v, _) in gaps(
                g, full, sizes, ctx.traffic["clip_leaf_min"], name).items()}
            row[name]["loss_signed"] = [
                a - b for a, b in zip(g["losses"][:1] + g["probe_losses"],
                                      full["losses"] + full["probe_losses"])]
            row[name]["leaves"] = {
                p: [float(np.sum(xd) / max(np.sum(dd), 1e-30)),
                    float(np.sqrt(np.sum(r) / max(np.sum(dd), 1e-30))),
                    [float(v) for v in xd / np.maximum(dd, 1e-30)]]
                for p, (xd, dd, r) in full["projections"][name].items()}
            g.pop("delta", None)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        ctx.log(str(row))
        del got, full
        gc.collect()
    return rows

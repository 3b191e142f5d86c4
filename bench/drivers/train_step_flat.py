"""Driver: the program's private training step under flat clipping through
book-keeping (`make_dp_train_step`, ghost_flat, execution bk) on a tied muP
model, driven as `train_step.py` drives per-layer clipping: its `Feed`,
`Program`, `reference_readings` and `gaps`, the same probes, checked steps
and window. Added:

  * before anything compiles, a check that the program's config carries
    the file's muP scalars and published depth (`bench/programs.py` checks
    the widths, not these);
  * two readings of the per-example squared norms that the first checked
    step clipped with (the step's `StepMetrics.norms_sq`, (K, B), and
    `tied_cross`, (B,)), against the reference's for the same batch at the
    same weights:
      norm_gap   worst group: max_i |n_i - n_ref_i| / max_i n_ref_i, over
                 the groups of `clip_leaf_min` elements or more (the
                 matrices, where bf16 rounding averages out, as for
                 clip_gap) whose largest reference norm is at least a
                 thousandth of the median group's
      cross_gap  max_i |c_i - c_ref_i| / max_i |c_ref_i| of the tied
                 group's cross term; a step that drops the term reads 1.
At random weights the cross term is about 1/sqrt(T) of the tied norm, so
`clip_gap` alone cannot see it dropped.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import harness
from bench.drivers import train_step as TS
from bench.programs import program_config

# ModelConfig field -> the file's key
MUP = (("scale_emb", "scale_emb"), ("scale_depth", "scale_depth"),
       ("mup_depth", "published_num_hidden_layers"),
       ("dim_model_base", "dim_model_base"))


def check_mup(cfg: dict):
    """The program's config, refused unless its muP scalars and published
    depth are the file's."""
    pc = program_config(cfg)
    bad = {k: (getattr(pc, k, None), cfg[f]) for k, f in MUP
           if getattr(pc, k, None) != cfg[f]}
    if bad:
        raise harness.CellError(f"program config {pc.name} departs from the "
                                f"benchmark's file in its muP scalars: {bad}")
    return pc


class Kept:
    """The compiled step, keeping the metrics of its calls while `log` is a
    list."""

    def __init__(self, compiled):
        self.compiled, self.log = compiled, None

    def __call__(self, *args):
        out = self.compiled(*args)
        if self.log is not None:
            self.log.append(out[3])
        return out


class Program(TS.Program):
    """`train_step.Program`, whose checked steps also read the per-example
    squared norms and the tied cross term of the first checked batch."""

    def __init__(self, ctx, ref):
        check_mup(ctx.cfg)
        super().__init__(ctx, ref)
        self.compiled = self.feed.compiled = Kept(self.compiled)

    def check(self) -> tuple:
        import jax
        self.compiled.log = []
        try:
            out, batches, probes = super().check()
            met = jax.device_get(self.compiled.log[len(self.probe_idx)])
        finally:
            self.compiled.log = None
        out["norms"] = self.ref_order(np.asarray(met.norms_sq, np.float64))
        out["cross"] = np.asarray(met.tied_cross, np.float64)
        return out, batches, probes

    def ref_order(self, norms_kb) -> np.ndarray:
        """(K, B) in the program's group order -> (B, K) in the
        reference's `group_offsets` order."""
        layout = self.model.layout
        offsets = self.ref.group_offsets(self.m)
        out = np.zeros((norms_kb.shape[1],
                        sum(n for _, n in offsets.values())))
        for path, (o, n) in offsets.items():
            g = layout.group(path.rsplit("/", 1)[0])
            if g.count != n:
                raise harness.CellError(f"group {g.name} has {g.count} "
                                        f"rows, the reference's {n}")
            out[:, o: o + n] = norms_kb[g.offset: g.offset + n].T
        return out


class _Seen:
    """The reference module, remembering the DPReference it makes."""

    def __init__(self, ref):
        self.ref, self.made = ref, None

    def __getattr__(self, name):
        return getattr(self.ref, name)

    def DPReference(self, *args, **kw):
        self.made = self.ref.DPReference(*args, **kw)
        return self.made


def reference_readings(ctx, ref, prog, batch, probes, deltas: dict,
                       **kw) -> dict:
    """`train_step.reference_readings`, with the per-example squared norms
    (B, K) and cross term (B,) the reference clipped `batch` with."""
    seen = _Seen(ref)
    out = TS.reference_readings(ctx, seen, prog, batch, probes, deltas, **kw)
    out.update(seen.made.readings)
    return out


def group_gaps(prog, ref, offsets: dict) -> dict:
    """{leaf[row]: max_i |n_i - n_ref_i| / max_i n_ref_i} per group."""
    scale = np.max(ref, axis=0)
    return {f"{p}[{j}]": float(np.max(np.abs(prog[:, o + j] - ref[:, o + j]))
                               / max(scale[o + j], 1e-30))
            for p, (o, n) in offsets.items() for j in range(n)}


def norm_gap(prog, ref, offsets: dict, sizes: dict, min_size: int
             ) -> tuple:
    """max of `group_gaps` over the groups of `min_size` elements or more,
    those of a largest reference norm under a thousandth of the median
    group's left out."""
    scale = np.max(ref, axis=0)
    floor = TS.LEAF_FLOOR * float(np.median(scale))
    gaps_ = group_gaps(prog, ref, offsets)
    worst, where = 0.0, None
    for p, (o, n) in offsets.items():
        for j in range(n):
            name = f"{p}[{j}]"
            if sizes[p] // n < min_size or scale[o + j] < floor:
                continue
            if not math.isfinite(gaps_[name]):
                return float("inf"), name
            if gaps_[name] > worst:
                worst, where = gaps_[name], name
    return worst, where


def cross_gap(prog, ref) -> tuple:
    """max_i |c_i - c_ref_i| / max_i |c_ref_i|, and the worst row."""
    scale = float(np.max(np.abs(ref)))
    gaps = np.abs(np.asarray(prog) - np.asarray(ref))
    i = int(np.argmax(gaps))
    if not scale > 0:
        return float("inf"), f"row {i}"
    return float(gaps[i]) / scale, f"row {i}"


def gaps(prog: dict, ref_out: dict, sizes: dict, min_size: int,
         offsets: dict, name: str = "program") -> dict:
    out = TS.gaps(prog, ref_out, sizes, min_size, name)
    out["norm_gap"] = norm_gap(prog["norms"], ref_out["norms"], offsets,
                               sizes, min_size)
    out["cross_gap"] = cross_gap(prog["cross"], ref_out["cross"])
    return out


def run(ctx) -> dict:
    tr = ctx.traffic
    ref = harness.load_module(ctx.cell.root, "reference", ctx.cfg["family"])
    prog = Program(ctx, ref)
    readings, batches, probes = prog.check()

    steps = failed = 0
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            prog.state, loss, _ = prog.feed.step(prog.state)
            steps += 1
            failed += 0 if math.isfinite(loss) else 1
            t1 = time.perf_counter()
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
    prog.free()

    t0 = time.perf_counter()
    r = reference_readings(ctx, ref, prog, batches[0], probes,
                           {"program": readings["delta"]})
    out = gaps(readings, r, TS.leaf_sizes(ref, prog.m), tr["clip_leaf_min"],
               ref.group_offsets(prog.m))
    ctx.log(f"reference: {time.perf_counter() - t0:.1f} s; program losses "
            f"{readings['losses'][:1] + readings['probe_losses']} reference "
            f"{r['losses'] + r['probe_losses']}; cross term program "
            f"{readings['cross'].tolist()} reference {r['cross'].tolist()}")
    for name, (value, where) in out.items():
        ctx.log(f"{name} {value!r} at {where}")
    ctx.log("norm gap by group " + str(group_gaps(
        readings["norms"], r["norms"], ref.group_offsets(prog.m))))
    return {"attempted": steps, "failed": failed, "e2e": e2e,
            "stats": stats, "compared": {k: v for k, (v, _) in out.items()}}


VARIANTS = TS.VARIANTS + (("no_cross", {"fault": "no_cross"}),
                          ("no_head", {"fault": "no_head"}))


def calibrate(ctx, ref, seeds: list) -> list:
    """As `train_step.calibrate`, with the readings above and two more
    faults of the reference's tied norm: `no_cross`, without its cross term,
    and `no_head`, without the head's use."""
    prog = Program(ctx, ref)
    sizes = TS.leaf_sizes(ref, prog.m)
    offsets = ref.group_offsets(prog.m)
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
            got[name] = reference_readings(ctx, ref, prog, batches[0],
                                           probes, {}, keep_delta=True, **kw)
        full = reference_readings(ctx, ref, prog, batches[0], probes,
                                  {n: g["delta"] for n, g in got.items()})
        row = {"seed": seed}
        for name, g in got.items():
            row[name] = {k: v for k, (v, _) in gaps(
                g, full, sizes, ctx.traffic["clip_leaf_min"], offsets,
                name).items()}
            row[name]["cross"] = np.asarray(g["cross"]).tolist()
            row[name]["norm_gap_by_group"] = group_gaps(
                g["norms"], full["norms"], offsets)
            g.pop("delta", None)
        row["reference_cross"] = np.asarray(full["cross"]).tolist()
        row["reference_norms_sq"] = np.sum(full["norms"], axis=1).tolist()
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        ctx.log(str(row))
        del got, full
        gc.collect()
    return rows

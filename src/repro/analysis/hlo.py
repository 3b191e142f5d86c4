"""Trip-count-aware cost analysis over post-SPMD HLO text.

XLA's built-in `compiled.cost_analysis()` visits every instruction ONCE, so
`lax.scan`/`while` bodies (our layer stacks, microbatch loops, flash
attention blocks) are undercounted by their trip counts — useless for a
roofline. This module re-derives per-device totals from the optimized HLO
text, multiplying loop bodies by their `known_trip_count` annotations:

  flops        — dot ops: 2 * |result| * K (contraction size from the lhs
                 symbol table); elementwise ops: |result|
  bytes        — per instruction: result + operand bytes; fusions count only
                 their boundary (internals never touch HBM)
  collectives  — per kind: count and result bytes, loop-multiplied

Conditionals take the max-flops branch (one branch executes per visit).
This intentionally mirrors HloCostAnalysis semantics where they are sound
and fixes them where they are not (loops).
"""
from __future__ import annotations

import dataclasses
import re
from functools import lru_cache

from repro.core.dp_sgd import PHASE_CLIP, PHASE_NOISE, PHASE_UPDATE

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")


def _parse_instr_line(line: str):
    """'%name = SHAPE opcode(operands), attrs' -> (name, shape, op, rest).

    Robust to tuple shapes with embedded '/*index=N*/' comments and layout
    annotations (which defeat naive '[^=]*' shape groups)."""
    ls = line.strip()
    if not (ls.startswith("%") or ls.startswith("ROOT ")):
        return None
    if " = " not in ls:
        return None
    lhs, rhs = ls.split(" = ", 1)
    name = lhs.replace("ROOT", "").strip().lstrip("%")
    m = _OPCODE.search(rhs)
    if not m:
        return None
    return name, rhs[: m.start()].strip(), m.group(1), rhs[m.end():]

COLLECTIVE_OPS = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "ragged-all-to-all",
}
_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
    "all-reduce-done", "all-gather-done", "collective-permute-done",
}
_TRANSCENDENTAL = {"exponential", "log", "tanh", "rsqrt", "sqrt", "power",
                   "cosine", "sine", "logistic", "expm1", "log1p", "erf",
                   "atan2", "cbrt"}


def _dims(shape_str: str) -> list[tuple[str, list[int]]]:
    """All (dtype, dims) element shapes in a possibly-tuple shape string."""
    return [(m.group(1), [int(d) for d in m.group(2).split(",") if d])
            for m in _SHAPE_RE.finditer(shape_str)]


def _nelems(dims: list[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _shape_bytes(shape_str: str) -> int:
    return sum(_nelems(d) * _DTYPE_BYTES.get(dt, 4)
               for dt, d in _dims(shape_str))


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    bytes: float = 0.0
    transcendentals: float = 0.0
    collectives: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "Totals", mult: float = 1.0) -> None:
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        self.transcendentals += mult * other.transcendentals
        for k, v in other.collectives.items():
            slot = self.collectives.setdefault(k, {"count": 0.0, "bytes": 0.0})
            slot["count"] += mult * v["count"]
            slot["bytes"] += mult * v["bytes"]


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    op: str
    rest: str  # operand list + attributes (the remainder of the line)


def parse_module(text: str) -> dict[str, list[Instr]]:
    comps: dict[str, list[Instr]] = {}
    cur: list[Instr] | None = None
    for line in text.splitlines():
        if line and not line[0].isspace() and "->" in line and "{" in line:
            m = _COMP_HDR.match(line)
            if m:
                cur = []
                comps[m.group(1)] = cur
                continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        parsed = _parse_instr_line(line)
        if parsed:
            cur.append(Instr(*parsed))
    return comps


_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRUEFALSE = re.compile(r"(?:true|false)_computation=%?([\w.\-]+)")
_TRIP = re.compile(r'known_trip_count[^\d]*(\d+)')
_OPERANDS = re.compile(r"%([\w.\-]+)")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DOT_BATCH = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")


def _operand_names(ins: Instr) -> list[str]:
    """The operands of `ins`: the leading %refs before the closing paren of
    the operand list; attribute refs come after "), " — take refs up to the
    first ")" at depth 0."""
    depth, end = 1, len(ins.rest)
    for idx, ch in enumerate(ins.rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = idx
                break
    return _OPERANDS.findall(ins.rest[:end])


class HloAnalyzer:
    def __init__(self, text: str):
        self.comps = parse_module(text)
        self.entry = self._find_entry(text)
        self._memo: dict[str, Totals] = {}

    @staticmethod
    def _find_entry(text: str) -> str:
        for line in text.splitlines():
            if line.startswith("ENTRY"):
                m = _COMP_HDR.match(line)
                if m:
                    return m.group(1)
        raise ValueError("no ENTRY computation found")

    def analyze(self) -> Totals:
        return self._comp(self.entry)

    def _comp(self, name: str) -> Totals:
        if name in self._memo:
            return self._memo[name]
        self._memo[name] = Totals()  # cycle guard
        instrs = self.comps.get(name, [])
        shapes = {i.name: i.shape for i in instrs}
        t = Totals()
        for ins in instrs:
            self._instr(ins, shapes, t)
        self._memo[name] = t
        return t

    def _operand_shapes(self, ins: Instr, shapes: dict[str, str]
                        ) -> list[str]:
        return [shapes.get(o, "") for o in _operand_names(ins)]

    def _instr(self, ins: Instr, shapes: dict[str, str], t: Totals) -> None:
        op = ins.op
        if op in _SKIP_OPS:
            return
        rbytes = _shape_bytes(ins.shape)
        if op == "while":
            body = cond = None
            bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
            cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
            trip_m = _TRIP.search(ins.rest)
            trip = int(trip_m.group(1)) if trip_m else 1
            if bm:
                t.add(self._comp(bm.group(1)), trip)
            if cm:
                t.add(self._comp(cm.group(1)), trip)
            return
        if op == "conditional":
            branches = []
            bm = _BRANCHES.search(ins.rest)
            if bm:
                branches = [b.strip().lstrip("%")
                            for b in bm.group(1).split(",")]
            else:
                branches = _TRUEFALSE.findall(ins.rest)
            if branches:
                subs = [self._comp(b) for b in branches]
                best = max(subs, key=lambda s: s.flops)
                t.add(best)
            return
        if op in ("call", "async-start"):
            cm = _CALLED.search(ins.rest)
            if cm:
                t.add(self._comp(cm.group(1)))
            return
        if op == "fusion":
            cm = _CALLED.search(ins.rest)
            if cm:
                sub = self._comp(cm.group(1))
                t.flops += sub.flops
                t.transcendentals += sub.transcendentals
                for k, v in sub.collectives.items():
                    slot = t.collectives.setdefault(
                        k, {"count": 0.0, "bytes": 0.0})
                    slot["count"] += v["count"]
                    slot["bytes"] += v["bytes"]
            t.bytes += rbytes + sum(_shape_bytes(s)
                                    for s in self._operand_shapes(ins, shapes))
            return
        if op in COLLECTIVE_OPS:
            base = op.replace("-start", "")
            slot = t.collectives.setdefault(base, {"count": 0.0, "bytes": 0.0})
            slot["count"] += 1
            slot["bytes"] += rbytes
            t.bytes += rbytes
            return
        opnd_bytes = sum(_shape_bytes(s)
                         for s in self._operand_shapes(ins, shapes))
        t.bytes += rbytes + opnd_bytes
        if op in ("dot", "dot-general"):
            opshapes = self._operand_shapes(ins, shapes)
            k = 1
            if opshapes and opshapes[0]:
                lhs_dims = _dims(opshapes[0])[0][1]
                cm = _LHS_CONTRACT.search(ins.rest)
                if cm and cm.group(1):
                    for ci in cm.group(1).split(","):
                        ci = int(ci)
                        if ci < len(lhs_dims):
                            k *= lhs_dims[ci]
            nres = sum(_nelems(d) for _, d in _dims(ins.shape))
            t.flops += 2.0 * nres * k
            return
        if op == "convolution":
            # not used by our models; approximate as elementwise
            t.flops += sum(_nelems(d) for _, d in _dims(ins.shape))
            return
        if op == "custom-call":
            cm = _CALLED.search(ins.rest)
            if cm and cm.group(1) in self.comps:
                t.add(self._comp(cm.group(1)))
            return
        # elementwise / reduce / everything else: 1 flop per output element
        nres = sum(_nelems(d) for _, d in _dims(ins.shape))
        t.flops += nres
        if op in _TRANSCENDENTAL:
            t.transcendentals += nres


def analyze_hlo(text: str) -> Totals:
    return HloAnalyzer(text).analyze()


# ---------------------------------------------------------------------------
# Backward-pass counting: assert (don't assume) the BK engine's win.
# ---------------------------------------------------------------------------


def _reachable(an: HloAnalyzer) -> set:
    """Computations reachable from ENTRY (skips dead leftovers)."""
    seen: set[str] = set()
    stack = [an.entry]
    while stack:
        comp = stack.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for ins in an.comps.get(comp, []):
            for m in _CALLED.finditer(ins.rest):
                if m.group(1) in an.comps:
                    stack.append(m.group(1))
            bm = _BRANCHES.search(ins.rest)
            if bm:
                stack.extend(b.strip().lstrip("%")
                             for b in bm.group(1).split(","))
            stack.extend(_TRUEFALSE.findall(ins.rest))
    return seen


def _comp_has(an: HloAnalyzer, comp: str, pred, memo: dict) -> bool:
    """Does `comp` (transitively) contain an instruction matching pred?"""
    if comp in memo:
        return memo[comp]
    memo[comp] = False  # cycle guard
    for ins in an.comps.get(comp, []):
        if pred(ins):
            memo[comp] = True
            return True
        for m in _CALLED.finditer(ins.rest):
            if m.group(1) in an.comps and _comp_has(an, m.group(1), pred,
                                                    memo):
                memo[comp] = True
                return True
    return memo[comp]


_TRANSPOSED = re.compile(r'op_name="[^"]*transpose\(jvp')
_S32_CONST = re.compile(r"^\s*(-?\d+)\)")


def _trip_count(an: HloAnalyzer, ins: Instr) -> int | None:
    """Iterations of a `while`: its known_trip_count annotation, else the
    one s32 scalar constant its condition compares the counter against
    (the TPU compiler rewrites layer loops, e.g. into double-buffered
    `wide.*` loops, and drops the annotation)."""
    t = _TRIP.search(ins.rest)
    if t:
        return int(t.group(1))
    cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
    if not cm:
        return None
    bounds = {int(m.group(1)) for c in an.comps.get(cm.group(1), [])
              if c.op == "constant" and c.shape.startswith("s32[]")
              for m in [_S32_CONST.match(c.rest)] if m}
    return bounds.pop() if len(bounds) == 1 else None


def _layer_loops(text: str, trip: int) -> tuple[int, int]:
    """(forward, backward) counts of innermost dot-bearing layer loops.

    A scanned layer stack of depth L lowers to one `while` of L iterations
    per traversal direction (`_trip_count`). Direction comes from
    JAX's op_name metadata: the transposed (reverse) scan of a backward
    pass tags its body `transpose(jvp(while))/...`, the forward scan
    `jvp(while)`/`while`. Outer loops that merely CONTAIN trip-matching
    loops (e.g. a microbatch scan whose trip count collides with L) are
    excluded, as are dot-free bookkeeping loops (data pipelines, quantile
    updates).
    """
    an = HloAnalyzer(text)
    has_dot: dict = {}
    has_inner: dict = {}
    has_transpose: dict = {}

    def is_dot(ins):
        # the TPU compiler emits matmuls as `convolution`
        return ins.op in ("dot", "dot-general", "convolution")

    def is_trip_while(ins):
        return ins.op == "while" and _trip_count(an, ins) == trip

    def is_transposed(ins):
        return bool(_TRANSPOSED.search(ins.rest))

    fwd = bwd = 0
    for comp in _reachable(an):
        for ins in an.comps.get(comp, []):
            if not is_trip_while(ins):
                continue
            bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
            if not bm or bm.group(1) not in an.comps:
                continue
            body = bm.group(1)
            if not _comp_has(an, body, is_dot, has_dot):
                continue
            if _comp_has(an, body, is_trip_while, has_inner):
                continue  # outer loop wrapping the real layer loops
            if _comp_has(an, body, is_transposed, has_transpose):
                bwd += 1
            else:
                fwd += 1
    return fwd, bwd


def backward_passes(text: str, layer_trip: int) -> int:
    """Full model backward passes in a compiled train step.

    Counts the transposed (reverse-iterating) layer-stack loops — see
    `_layer_loops`. The BK engine's claim is thereby asserted from the
    compiled HLO, not assumed: ONE backward pass for execution=bk (and
    per_layer / non_private), TWO for the `*_twopass` flat/group drivers —
    at any microbatch count (each microbatch body repeats the same
    structure; loops are counted statically). For models with several
    homogeneous stack runs pass the depth of the run of interest.
    """
    return _layer_loops(text, layer_trip)[1]


# ---------------------------------------------------------------------------
# Collective attribution: which program sites emit the bytes.
# ---------------------------------------------------------------------------

_OPNAME = re.compile(r'op_name="([^"]*)"')


def _comp_multiplicities(an: HloAnalyzer) -> dict[str, float]:
    """Visit multiplicity of every computation from ENTRY (loop-aware)."""
    mult: dict[str, float] = {}

    def visit(comp: str, m: float):
        mult[comp] = mult.get(comp, 0.0) + m
        for ins in an.comps.get(comp, []):
            if ins.op == "while":
                t = _TRIP.search(ins.rest)
                trip = int(t.group(1)) if t else 1
                bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
                cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                if bm:
                    visit(bm.group(1), m * trip)
                if cm:
                    visit(cm.group(1), m * trip)
            elif ins.op == "conditional":
                bs = _BRANCHES.search(ins.rest)
                names = ([b.strip().lstrip("%") for b in
                          bs.group(1).split(",")] if bs
                         else _TRUEFALSE.findall(ins.rest))
                for n in names:
                    visit(n, m)
            elif ins.op in ("fusion", "call", "custom-call", "async-start"):
                cm2 = _CALLED.search(ins.rest)
                if cm2 and cm2.group(1) in an.comps:
                    visit(cm2.group(1), m)

    visit(an.entry, 1.0)
    return mult


def collective_breakdown(text: str, top: int = 15) -> list[dict]:
    """Attribute collective result-bytes to source op_name sites.

    Loop multipliers are applied by locating each collective's enclosing
    computations through the analyzer's call graph (a site inside the
    36-layer scan counts 36x). Returns the top sites by total bytes.
    """
    an = HloAnalyzer(text)
    mult = _comp_multiplicities(an)
    sites: dict[tuple[str, str], dict] = {}
    for comp, instrs in an.comps.items():
        m = mult.get(comp, 0.0)
        if m == 0:
            continue
        for ins in instrs:
            base = ins.op.replace("-start", "")
            if base not in {"all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute"}:
                continue
            if ins.op.endswith("-done"):
                continue
            nm = _OPNAME.search(ins.rest)
            site = nm.group(1) if nm else "<unattributed>"
            # trim jit prefixes for readability
            site = site.split("jit(step_fn)/")[-1][:120]
            key = (base, site)
            slot = sites.setdefault(key, {"bytes": 0.0, "count": 0.0})
            slot["bytes"] += m * _shape_bytes(ins.shape)
            slot["count"] += m
    rows = [{"kind": k[0], "site": k[1], **v} for k, v in sites.items()]
    rows.sort(key=lambda r: -r["bytes"])
    return rows[:top]


# ---------------------------------------------------------------------------
# Axis classification: WHICH mesh axes does each collective cross?
#
# The paper's per-device-clipping claim (Sec 4) is an axis statement: flat
# clipping moves per-example norm information across the MODEL axis; per-
# device clipping must not. Post-SPMD collectives carry `replica_groups`
# (flat device-id groups), so given the mesh's device->coordinate map we can
# decide, per collective, the set of mesh axes along which its groups vary —
# and tests can assert "zero model-axis collectives in norm computation"
# from the compiled HLO rather than assume it.
# ---------------------------------------------------------------------------

_REPLICA_GROUPS = re.compile(
    r"replica_groups=(\{\}|\{\{[\d,{} ]*\}\}|\[[\d,]+\]<=\[[\d,]+\]"
    r"(?:T\([\d,]+\))?)")
_SOURCE_TARGET = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_PAIR = re.compile(r"\{(\d+),(\d+)\}")
_IOTA_RG = re.compile(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def mesh_device_coords(mesh) -> dict[int, tuple[int, ...]]:
    """device id -> mesh coordinates, read off the mesh's device array
    (robust to non-row-major physical orderings)."""
    import numpy as np
    coords: dict[int, tuple[int, ...]] = {}
    for idx in np.ndindex(*mesh.devices.shape):
        coords[int(mesh.devices[idx].id)] = tuple(int(i) for i in idx)
    return coords


def _parse_replica_groups(s: str, n_devices: int) -> list[list[int]] | None:
    """Flat device-id groups from either HLO replica_groups syntax."""
    import numpy as np
    if s == "{}":
        return [list(range(n_devices))]
    if s.startswith("{{"):
        return [[int(x) for x in grp.split(",") if x]
                for grp in re.findall(r"\{([\d, ]+)\}", s.replace(" ", ""))]
    m = _IOTA_RG.match(s)
    if not m:  # unknown format: caller treats as spanning everything
        return None
    gshape = [int(d) for d in m.group(1).split(",")]
    dims = [int(d) for d in m.group(2).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(3):
        ids = ids.transpose([int(p) for p in m.group(3).split(",")])
    return ids.reshape(gshape[0], -1).tolist()


def _axes_of_groups(groups: list[list[int]], coords: dict,
                    axis_names: tuple) -> tuple[str, ...]:
    """Mesh axes along which membership varies within any group."""
    spanned = set()
    for grp in groups:
        if len(grp) < 2:
            continue
        base = coords.get(grp[0])
        if base is None:
            return tuple(axis_names)  # ids outside the mesh: assume global
        for gid in grp[1:]:
            c = coords.get(gid)
            if c is None:
                return tuple(axis_names)
            for a, (x, y) in enumerate(zip(base, c)):
                if x != y:
                    spanned.add(axis_names[a])
    return tuple(a for a in axis_names if a in spanned)


def classify_collectives(text: str, mesh) -> list[dict]:
    """Per-site collective rows with the mesh axes each one crosses.

    Returns [{kind, site, axes: tuple[str,...], count, bytes}], loop-
    multiplied like `collective_breakdown`. `site` is the trimmed op_name
    (jax name_stack), so engine-inserted collectives wrapped in
    `jax.named_scope(...)` are attributable (e.g. 'flat_norm_psum').
    An unparsable replica_groups conservatively spans every axis.
    """
    coords = mesh_device_coords(mesh)
    axis_names = tuple(mesh.axis_names)
    n_dev = len(coords)
    an = HloAnalyzer(text)
    mult = _comp_multiplicities(an)
    sites: dict[tuple, dict] = {}
    for comp, instrs in an.comps.items():
        m = mult.get(comp, 0.0)
        if m == 0:
            continue
        for ins in instrs:
            base = ins.op.replace("-start", "")
            if base not in {"all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute"}:
                continue
            if ins.op.endswith("-done"):
                continue
            if base == "collective-permute":
                pm = _SOURCE_TARGET.search(ins.rest)
                groups = ([[int(a), int(b)] for a, b in
                           _PAIR.findall(pm.group(1))] if pm else None)
            else:
                gm = _REPLICA_GROUPS.search(ins.rest)
                groups = (_parse_replica_groups(gm.group(1), n_dev)
                          if gm else None)
            axes = (tuple(axis_names) if groups is None
                    else _axes_of_groups(groups, coords, axis_names))
            nm = _OPNAME.search(ins.rest)
            site = nm.group(1) if nm else "<unattributed>"
            site = site.split("jit(step_fn)/")[-1][:160]
            key = (base, axes, site)
            slot = sites.setdefault(key, {"bytes": 0.0, "count": 0.0})
            slot["bytes"] += m * _shape_bytes(ins.shape)
            slot["count"] += m
    rows = [{"kind": k[0], "axes": k[1], "site": k[2], **v}
            for k, v in sites.items()]
    rows.sort(key=lambda r: -r["bytes"])
    return rows


def summarize_axis_rows(rows: list[dict]) -> dict:
    """Aggregate `classify_collectives` rows to {axes-key: {count, bytes}}.

    Keys are '+'-joined spanned axes ('model', 'data', 'data+model', ...)
    or 'intra' for degenerate single-device groups — the shape consumed by
    BENCH_sharded.json and the zero-model-norm-traffic assertions.
    """
    out: dict[str, dict] = {}
    for r in rows:
        key = "+".join(r["axes"]) or "intra"
        slot = out.setdefault(key, {"count": 0.0, "bytes": 0.0})
        slot["count"] += r["count"]
        slot["bytes"] += r["bytes"]
    return out


def filter_model_norm_rows(rows: list[dict], *,
                           model_axis: str = "model") -> list[dict]:
    """Rows that BOTH cross the model axis AND belong to norm computation
    (site mentions 'norm' — the engine names its norm psums via
    `jax.named_scope`). Per-device clipping must yield []; flat clipping
    pays exactly its (B,) total-norm psum here."""
    return [r for r in rows
            if model_axis in r["axes"] and "norm" in r["site"].lower()]


def collective_axis_summary(text: str, mesh) -> dict:
    return summarize_axis_rows(classify_collectives(text, mesh))


def model_axis_norm_collectives(text: str, mesh, *,
                                model_axis: str = "model") -> list[dict]:
    return filter_model_norm_rows(classify_collectives(text, mesh),
                                  model_axis=model_axis)


# ---------------------------------------------------------------------------
# Entry-computation structure: donation aliases + shape stability.
#
# These feed the HLO rules engine (repro.analysis.rules). Donation shows up
# on the HloModule header line as
#   input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }
# mapping output tuple indices to entry parameter numbers. A jit with
# donate_argnums that silently fails to alias (the PR-7 corruption class
# was the inverse: an alias map applied to the WRONG buffers after cache
# deserialization) is statically visible here.
# ---------------------------------------------------------------------------

_ALIAS_PAIR = re.compile(
    r"\{([\d, ]*)\}:\s*\((\d+),\s*\{[\d, ]*\}(?:,\s*(may-alias|must-alias))?\)")


def _balanced_attr(line: str, attr: str) -> str | None:
    """The `{...}` payload of `attr={...}` with nested braces balanced."""
    tag = attr + "={"
    start = line.find(tag)
    if start < 0:
        return None
    start += len(attr) + 1
    depth = 0
    for idx in range(start, len(line)):
        ch = line[idx]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                return line[start:idx + 1]
    return None


def entry_aliases(text: str) -> list[dict]:
    """Donation map of the module: [{output_index, param, kind}].

    Parsed from the HloModule header's `input_output_alias` attribute;
    empty when the executable donates nothing."""
    for line in text.splitlines():
        if "input_output_alias=" not in line:
            continue
        blob = _balanced_attr(line, "input_output_alias")
        if blob is None:
            continue
        return [
            {"output_index": tuple(int(x) for x in
                                   m.group(1).replace(" ", "").split(",")
                                   if x),
             "param": int(m.group(2)),
             "kind": m.group(3) or "may-alias"}
            for m in _ALIAS_PAIR.finditer(blob)
        ]
    return []


def entry_param_count(text: str) -> int:
    """Number of (flat) parameters of the ENTRY computation."""
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR.match(line)
            if m:
                args = m.group(2)
                return args.count(": ") if args.strip() else 0
    raise ValueError("no ENTRY computation found")


def dynamic_shape_instrs(text: str) -> list[tuple[str, str]]:
    """(name, shape) of instructions with bounded-dynamic dims (`[<=N,...]`).

    A data-dependent entry shape means recompiles (or padding bugs) under
    traffic — the serving/training programs must be shape-stable. The
    check inspects parsed instruction SHAPES only, so `<=` inside iota
    replica_groups attrs (e.g. `[16]<=[16]`) never false-positives."""
    out = []
    for line in text.splitlines():
        if "<=" not in line:
            continue
        parsed = _parse_instr_line(line)
        if parsed and "<=" in parsed[1]:
            out.append((parsed[0], parsed[1]))
    return out


# ---------------------------------------------------------------------------
# Step phases: which phase of the DP step each instruction belongs to.
#
# make_dp_train_step wraps each phase of the step in one named scope
# (core.dp_sgd.PHASE_*), so the op_name of every instruction the step's JAX
# code emits names its phase. A profiler trace names each device op by its
# instruction name, so this map splits the step's device time by phase.
# ---------------------------------------------------------------------------

FORWARD, BACKWARD, NOISE_UPDATE = "forward", "backward", "noise_update"
# their device events span the events of the computations they call
CONTAINER_OPS = frozenset({"while", "conditional", "call"})
_PHASE_SCOPE = re.compile(
    rf"\b({PHASE_CLIP}|{PHASE_NOISE}|{PHASE_UPDATE})\b")
# JAX names a transposed op `transpose(jvp(...))/...`, and the backward of a
# custom_vjp `transpose(<scope>)/jvp(<fn>)/...`
_TRANSPOSE = re.compile(r"transpose\(")
# clipping work on the backward's residuals that runs after the transposed
# pass, not transposed itself: the BK epilogue's clipped sums
# (core.bk.contract_clipped) and a tied group's cross term (core.bk.
# TIED_CROSS), as the clipped sums and norms of per_layer are backward
_AFTER_BACKWARD = re.compile(r"\b(bk_epilogue_contract|dp_tied_cross)\b")


def _called(ins: Instr) -> list[str]:
    """The computations an instruction calls."""
    names = [m.group(1) for m in _CALLED.finditer(ins.rest)]
    bm = _BRANCHES.search(ins.rest)
    if bm:
        names += [b.strip().lstrip("%") for b in bm.group(1).split(",")]
    return names + _TRUEFALSE.findall(ins.rest)


def _named_phase(ins: Instr) -> str | None:
    """The phase the instruction's own op_name names, if any."""
    nm = _OPNAME.search(ins.rest)
    m = _PHASE_SCOPE.search(nm.group(1)) if nm else None
    if m is None:
        return None
    if m.group(1) != PHASE_CLIP:
        # XLA fuses the noise's `g + z` into the optimizer's elementwise
        # fusion, and a fusion carries its root's op_name: one phase
        return NOISE_UPDATE
    name = nm.group(1)
    if _TRANSPOSE.search(name) or _AFTER_BACKWARD.search(name):
        return BACKWARD
    return FORWARD


def _phases(comps: dict[str, list[Instr]]) -> dict[str, str | None]:
    """{instruction: phase} for every instruction, containers included.

    An instruction whose op_name names no phase (XLA's own copies, a
    reducer's body, loop-invariant code JAX hoisted out of a scoped loop)
    takes the phase of the instruction that calls its computation; failing
    that, of its first user that has one, else of its first operand that
    has one, until nothing changes. Only an instruction with no phased
    caller, user or operand, however far removed, keeps None."""
    phase: dict[str, str | None] = {}
    caller: dict[str, str] = {}
    for instrs in comps.values():
        for ins in instrs:
            phase[ins.name] = _named_phase(ins)
            for c in _called(ins):
                caller.setdefault(c, ins.name)
    flow = {}  # comp -> [(instr, its users then its operands)]
    for comp, instrs in comps.items():
        operands = {ins.name: [o for o in _operand_names(ins) if o in phase]
                    for ins in instrs}
        users: dict[str, list[str]] = {ins.name: [] for ins in instrs}
        for ins in instrs:
            for o in operands[ins.name]:
                if o in users:
                    users[o].append(ins.name)
        flow[comp] = [(ins.name, users[ins.name] + operands[ins.name])
                      for ins in instrs]
    changed = True
    while changed:
        changed = False
        for comp, links in flow.items():
            outer = phase.get(caller.get(comp, ""))
            for name, near in links:
                if phase[name] is not None:
                    continue
                got = outer or next(
                    (phase[n] for n in near if phase[n] is not None), None)
                if got is not None:
                    phase[name] = got
                    changed = True
    return phase


def op_phases(text: str) -> dict[str, str | None]:
    """{instruction name: phase} over every computation of a compiled
    DP step's module, containers (`CONTAINER_OPS`) left out.

    forward       under the clip scope and not transposed
    backward      under the clip scope and transposed: the norms, the
                  clipped sums and forward ops rematerialised in the
                  backward; and the clipping work on the backward's
                  residuals after it (the BK epilogue's
                  `bk_epilogue_contract`, a tied group's `dp_tied_cross`)
    noise_update  under the noise or the update scope
    None          outside every phase scope (see `_phases`)
    """
    comps = parse_module(text)
    phase = _phases(comps)
    return {ins.name: phase[ins.name] for instrs in comps.values()
            for ins in instrs if ins.op not in CONTAINER_OPS}


def container_ops(text: str) -> set[str]:
    """Names of the module's `while`, `conditional` and `call`
    instructions, whose device events span their bodies' events."""
    return {ins.name for instrs in parse_module(text).values()
            for ins in instrs if ins.op in CONTAINER_OPS}

"""The traced DP step split by phase, and the device's wait between runs of
the step program split by what the host was doing.

Reads the `.xplane.pb` that the traced window left under `bench/.run/`
(the harness removes it once the readers have run):

  * the step program is the program with most device time in the window;
  * its compiled HLO is in the trace: the `/host:metadata` plane holds one
    `HloProto` per program, and the program's own `repro.analysis.hlo.
    op_phases` maps each instruction to a phase of the step (forward,
    backward, noise_update) through the named scopes of
    `core.dp_sgd.make_dp_train_step`;
  * phase time is the summed device time of the non-container `XLA Ops`
    events inside the step program's runs (a `while`, `conditional` or
    `call` event spans the events of its body, which the trace lists too);
  * the wait between runs is the idle time in the window outside every
    program run on the `XLA Modules` line, the host's doing: idle inside a
    run is not counted.

A phase reads nothing where the program has no `op_phases`, or where ops
with no phase, or op names the map does not hold, take more than
`UNATTRIBUTED_MAX` of the step's non-container device time: a wrong
attribution gives no reading rather than a wrong one.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import sys

from bench import trace as T

RUN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".run")
METADATA_PLANE = "/host:metadata"
PHASES = ("forward", "backward", "noise_update")
UNATTRIBUTED_MAX = 0.02
SLOWEST = 3
TOP_KINDS = 6  # op kinds logged per phase


@dataclasses.dataclass
class StepTrace:
    program: str  # the step program's name, e.g. `jit_step_fn`
    steps: int  # `bench.step` spans inside the window
    busy_s: float  # mean over devices of the busy union inside the window
    between_runs_s: float  # mean over devices: idle outside every run
    runs: list  # device 0: [(start_ns, end_ns)] of the step program's runs
    gaps: list  # device 0: [(start_ns, end_ns)] idle between runs
    idle_by_span: dict  # device 0: innermost host span -> seconds of gaps
    clock_offset_ns: tuple | None  # (low, high) bounds, `clock_offset`
    idle_by_span_aligned: dict  # idle_by_span with the device's times moved
    #   by the middle of those bounds
    op_s: dict  # op name -> device seconds inside the step program's runs,
    #   summed over devices
    devices: int
    module: bytes | None  # the step program's HloModuleProto, from the trace
    phase_s: dict | None = None  # phase -> device seconds, mean over devices
    unattributed_s: float = 0.0  # ops with no phase
    missing_s: float = 0.0  # op names the map does not hold
    leaf_s: float = 0.0  # non-container op time, mean over devices
    by_kind: dict = dataclasses.field(default_factory=dict)  # phase ->
    #   {op base name: device seconds, mean over devices}


# --- the HLO the trace carries ---------------------------------------------


def _varint(buf: bytes, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes):
    """(field number, value) of a serialized protocol buffer message: an int
    for a varint, bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def hlo_protos(path: str) -> dict:
    """{program run name, e.g. `jit_step_fn(123)`: serialized HloModuleProto}
    from the trace's metadata plane.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entries of
    key 1 and value 2); XEventMetadata.name = 2, .stats = 5; XStat.bytes_value
    = 6 holds an HloProto, whose hlo_module = 1."""
    with open(path, "rb") as fh:
        space = fh.read()
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        parts: dict = {}
        for f, v in _fields(plane):
            if f in (2, 4):
                parts.setdefault(f, []).append(v)
        if parts.get(2, [b""])[0].decode() != METADATA_PLANE:
            continue
        for entry in parts.get(4, []):
            meta = dict(_fields(entry)).get(2, b"")
            name, module = "", None
            for f, v in _fields(meta):
                if f == 2:
                    name = v.decode()
                elif f == 5:
                    blob = dict(_fields(v)).get(6)
                    if isinstance(blob, bytes):
                        module = dict(_fields(blob)).get(1)
            if name and module:
                out[name] = module
    return out


def hlo_text(module: bytes) -> str:
    """A serialized HloModuleProto as HLO text with each op's metadata."""
    from jax._src.lib import _jax

    opts = _jax.HloPrintOptions()
    opts.print_metadata = True
    return _jax.HloModule.from_serialized_hlo_module_proto(module).to_string(
        opts)


# --- the reduction ---------------------------------------------------------


def split_by_span(lo: float, hi: float, spans: list) -> dict:
    """{innermost host span over [lo, hi): ns}, by overlap; `none` where
    no span but the window holds the time."""
    inner = [s for s in spans if s.name != T.WINDOW_SPAN
             and s.end_ns > lo and s.start_ns < hi]
    cuts = sorted({lo, hi} | {x for s in inner for x in (s.start_ns, s.end_ns)
                              if lo < x < hi})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        holding = [s for s in inner if s.start_ns <= mid <= s.end_ns]
        name = (min(holding, key=lambda s: s.dur_ns).name if holding
                else "none")
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def clock_offset(runs: list, spans: list, lo: float, hi: float
                 ) -> tuple | None:
    """(low, high) bounds in ns on what to add to the device's times to put
    them on the host's clock: each run starts after its `bench.dispatch`
    span starts and ends before its `bench.fetch` span ends. None where the
    window's runs and spans do not pair one to one."""
    def inside(name):
        return [s for s in spans if s.name == name
                and s.start_ns >= lo and s.end_ns <= hi]

    dispatch, fetch = inside("bench.dispatch"), inside("bench.fetch")
    if not runs or not len(runs) == len(dispatch) == len(fetch):
        return None
    return (max(d.start_ns - r[0] for d, r in zip(dispatch, runs)),
            min(f.end_ns - r[1] for f, r in zip(fetch, runs)))


def _idle_by_span(gaps: list, spans: list, shift: float = 0.0) -> dict:
    out: dict = {}
    for a, b in gaps:
        for name, t in split_by_span(a + shift, b + shift, spans).items():
            out[name] = out.get(name, 0.0) + t * 1e-9
    return out


def reduce(trace: T.Trace, hlo: dict) -> StepTrace | None:
    """The step program's runs, phases' inputs and between-run idle; None
    where the trace holds no program run in the window."""
    lo, hi = T.window_of(trace)
    ns = 1e-9
    time_by_prog: dict = {}
    for dev in trace.modules:
        for e in dev:
            if e.end_ns > lo and e.start_ns < hi:
                time_by_prog[e.name] = time_by_prog.get(e.name, 0.0) + (
                    min(e.end_ns, hi) - max(e.start_ns, lo))
    if not time_by_prog:
        return None
    program = max(time_by_prog, key=time_by_prog.get)
    busy, between, op_s = [], [], {}
    runs0 = gaps0 = None
    for d, (dev_ops, dev_mods) in enumerate(zip(trace.ops, trace.modules)):
        every = T.merge(((e.start_ns, e.end_ns) for e in dev_mods), lo, hi)
        ops = [(e.start_ns, e.end_ns) for e in dev_ops
               if e.end_ns > lo and e.start_ns < hi]
        busy.append(sum(e - s for s, e in T.merge(ops, lo, hi)) * ns)
        # idle outside every run: the window less the union of runs and ops
        taken = T.merge(ops + [tuple(iv) for iv in every], lo, hi)
        between.append(((hi - lo) - sum(e - s for s, e in taken)) * ns)
        runs = sorted((max(e.start_ns, lo), min(e.end_ns, hi))
                      for e in dev_mods if e.name == program
                      and e.end_ns > lo and e.start_ns < hi)
        starts = [s for s, _ in runs]
        for e in dev_ops:
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k < 0 or e.start_ns >= runs[k][1]:
                continue
            dur = (min(e.end_ns, hi) - max(e.start_ns, lo)) * ns
            if dur > 0:
                op_s[e.name] = op_s.get(e.name, 0.0) + dur
        if d == 0:
            runs0 = runs
            edges = [lo] + [x for iv in taken for x in iv] + [hi]
            gaps0 = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    offset = clock_offset(runs0, trace.spans, lo, hi)
    shift = 0.5 * (offset[0] + offset[1]) if offset else 0.0
    module = next((m for name, m in hlo.items()
                   if T.program_name(name) == program), None)
    n = max(len(trace.ops), 1)
    steps = sum(1 for s in trace.spans if s.name == "bench.step"
                and s.start_ns >= lo and s.end_ns <= hi)
    return StepTrace(
        program=program, steps=steps, busy_s=sum(busy) / n,
        between_runs_s=sum(between) / n, runs=runs0 or [], gaps=gaps0 or [],
        idle_by_span=_idle_by_span(gaps0 or [], trace.spans),
        clock_offset_ns=offset,
        idle_by_span_aligned=_idle_by_span(gaps0 or [], trace.spans, shift),
        op_s=op_s, devices=n, module=module)


def attribute(st: StepTrace, phases: dict, containers: set) -> None:
    """Fill `st.phase_s` from an {op: phase} map; left None where ops with
    no phase or op names missing from the map take more than
    UNATTRIBUTED_MAX of the non-container time."""
    out = dict.fromkeys(PHASES, 0.0)
    none = missing = 0.0
    n = st.devices
    for name, sec in st.op_s.items():
        if name in containers:
            continue
        if name not in phases:
            missing += sec
        elif phases[name] is None:
            none += sec
        else:
            out[phases[name]] += sec
            kinds = st.by_kind.setdefault(phases[name], {})
            base = T.base_name(name)
            kinds[base] = kinds.get(base, 0.0) + sec / n
    st.leaf_s = (sum(out.values()) + none + missing) / n
    st.unattributed_s, st.missing_s = none / n, missing / n
    limit = UNATTRIBUTED_MAX * st.leaf_s
    if st.leaf_s > 0 and max(st.unattributed_s, st.missing_s) <= limit:
        st.phase_s = {k: v / n for k, v in out.items()}


def latest_xplane(run_dir: str = RUN_DIR) -> str | None:
    """The newest trace a traced window left under `run_dir`."""
    found = glob.glob(os.path.join(run_dir, "trace-*", "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _say(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr)


def log(st: StepTrace) -> None:
    """Log the phase split, the between-run idle by host span, and the
    SLOWEST longest runs of the step program with the gaps around them."""
    per = 1e3 / max(st.steps, 1)
    if st.phase_s is not None or st.leaf_s:
        split = ", ".join(f"{k} {v * per:.3f}" for k, v in
                          (st.phase_s or {}).items())
        _say(f"step phases, ms per step: {split or 'not attributed'}; "
             f"no phase {st.unattributed_s * per:.3f}, not in the map "
             f"{st.missing_s * per:.3f}, non-container ops "
             f"{st.leaf_s * per:.3f}, busy {st.busy_s * per:.3f}")
    for phase, kinds in st.by_kind.items():
        top = sorted(kinds.items(), key=lambda kv: -kv[1])[:TOP_KINDS]
        _say(f"{phase}, ms per step: " + ", ".join(
            f"{k} {v * per:.3f}" for k, v in top))
    _say(f"idle between runs of {st.program}, ms per step: "
         f"{st.between_runs_s * per:.3f}; by host span: "
         + _by_span(st.idle_by_span, per))
    if st.clock_offset_ns is not None:
        low, high = st.clock_offset_ns
        _say(f"device clock to host clock: add {low * 1e-6:.3f} to "
             f"{high * 1e-6:.3f} ms (runs inside their dispatch and fetch "
             f"spans); by host span at the middle: "
             + _by_span(st.idle_by_span_aligned, per))
    for k in sorted(range(len(st.runs)),
                    key=lambda i: st.runs[i][0] - st.runs[i][1])[:SLOWEST]:
        s, e = st.runs[k]
        before = s - st.runs[k - 1][1] if k else None
        after = st.runs[k + 1][0] - e if k + 1 < len(st.runs) else None
        _say(f"slow run {k + 1} of {len(st.runs)}: {(e - s) * 1e-6:.3f} "
             f"ms; gap before {_ms(before)}, after {_ms(after)}")


def _by_span(seconds: dict, per: float) -> str:
    return ", ".join(f"{k} {v * per:.3f}" for k, v in sorted(
        seconds.items(), key=lambda kv: -kv[1]))


def _ms(ns_or_none) -> str:
    return "-" if ns_or_none is None else f"{ns_or_none * 1e-6:.3f} ms"


def of(run, path: str | None = None) -> StepTrace | None:
    """The run's StepTrace, reduced once and kept on `run` for every
    reader; None where no traced window left a trace with a program run."""
    if not hasattr(run, "step_trace"):
        path = path or latest_xplane()
        st = None
        if path is not None:
            st = reduce(T.load(path), hlo_protos(path))
        if st is not None and st.module is not None:
            try:
                from repro.analysis.hlo import container_ops, op_phases
            except ImportError:  # a program without the phase map
                pass
            else:
                text = hlo_text(st.module)
                attribute(st, op_phases(text), container_ops(text))
        if st is not None:
            log(st)
        run.step_trace = st
    return run.step_trace


def phase_ms(run, phase: str) -> float | None:
    """Device ms per `bench.step` in one phase of the step."""
    st = of(run)
    if st is None or not st.steps or st.phase_s is None:
        return None
    return 1e3 * st.phase_s[phase] / st.steps


def step_gap_ms(run) -> float | None:
    """Device ms per `bench.step` idle between program runs."""
    st = of(run)
    if st is None or not st.steps or not st.runs:
        return None
    return 1e3 * st.between_runs_s / st.steps

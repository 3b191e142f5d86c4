"""Reduce a JAX profiler trace to device busy time, device time per op and
per program, and the longest idle gaps with what the host was doing.

The trace is the `.xplane.pb` that `jax.profiler.trace` writes. On a TPU
each chip is a plane `/device:TPU:<n>` whose line `XLA Ops` holds one event
per executed HLO instruction (named by the instruction's HLO text, e.g.
`%ghost_norm.1 = f32[4,8,128] custom-call(...)`) and whose line
`XLA Modules` holds one event per program run (`jit_step(<hash>)`). The
harness's own host spans are `jax.profiler.TraceAnnotation`s named
`bench.<what>` on the host plane, on the same clock.

Busy time is the union of the `XLA Ops` intervals inside the window; the
window is the host span `bench.window` (or, where there is none, the extent
of all `bench.*` spans).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: list  # per device: [Event] from `XLA Ops`
    modules: list  # per device: [Event] from `XLA Modules`
    spans: list  # [Event] host spans named bench.*


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices of the busy union inside the window
    op_s: dict  # exact op name -> device seconds (summed over devices)
    kernel_s: dict  # op base name (no `.N` suffix) -> device seconds
    kernel_n: dict  # op base name -> number of events
    program_s: dict  # program name -> device seconds
    program_n: dict  # program name -> runs (summed over devices)
    span_n: dict  # host span name -> count inside the window
    idle_gaps: list  # [[host span name, seconds]], longest first
    device_ops: list  # [[op name, seconds]], most time first

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops[:TOP],
                "idle_gaps": self.idle_gaps[:TOP]}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(hlo_text: str) -> str:
    """`%ghost_norm.1 = f32[...] custom-call(...)` -> `ghost_norm.1`."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def base_name(name: str) -> str:
    """`ghost_norm.1` -> `ghost_norm`; `fusion.12` -> `fusion`."""
    return re.sub(r"(\.\d+)+$", "", name)


def program_name(module_event_name: str) -> str:
    """`jit_step(5718131871217815470)` -> `jit_step`."""
    return module_event_name.split("(", 1)[0]


def load(path: str) -> Trace:
    """Read one `.xplane.pb` into device op, program and host span events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    devices = sorted((p for p in data.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in devices:
        dev_ops, dev_mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                dev_ops = [Event(op_name(e.name), float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            elif line.name == "XLA Modules":
                dev_mods = [Event(program_name(e.name), float(e.start_ns),
                                  float(e.duration_ns)) for e in line.events]
        ops.append(dev_ops)
        modules.append(dev_mods)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    spans.sort(key=lambda e: e.start_ns)
    return Trace(ops=ops, modules=modules, spans=spans)


def merge(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(trace: Trace) -> tuple:
    marked = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if marked:
        return marked[0].start_ns, marked[0].end_ns
    if trace.spans:
        return (min(s.start_ns for s in trace.spans),
                max(s.end_ns for s in trace.spans))
    every = [e for dev in trace.ops for e in dev]
    if not every:
        raise ValueError("the trace holds no device operation and no span")
    return (min(e.start_ns for e in every), max(e.end_ns for e in every))


def _label(gap, spans) -> str:
    """The innermost host span holding an idle gap's midpoint: what the
    host was doing while the device waited."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [s for s in spans if s.name != WINDOW_SPAN
              and s.start_ns <= mid <= s.end_ns]
    return min(inside, key=lambda s: s.dur_ns).name if inside else "none"


def summarize(trace: Trace) -> Summary:
    lo, hi = window_of(trace)
    ns = 1e-9
    busy, op_s, kernel_s, kernel_n = [], {}, {}, {}
    gaps: list = []
    for d, dev_ops in enumerate(trace.ops):
        inside = [e for e in dev_ops if e.end_ns > lo and e.start_ns < hi]
        merged = merge(((e.start_ns, e.end_ns) for e in inside), lo, hi)
        busy.append(sum(e - s for s, e in merged) * ns)
        for e in inside:
            dur = (min(e.end_ns, hi) - max(e.start_ns, lo)) * ns
            op_s[e.name] = op_s.get(e.name, 0.0) + dur
            b = base_name(e.name)
            kernel_s[b] = kernel_s.get(b, 0.0) + dur
            kernel_n[b] = kernel_n.get(b, 0) + 1
        if d == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    program_s, program_n = {}, {}
    for dev_mods in trace.modules:
        for e in dev_mods:
            if e.end_ns > lo and e.start_ns < hi:
                dur = (min(e.end_ns, hi) - max(e.start_ns, lo)) * ns
                program_s[e.name] = program_s.get(e.name, 0.0) + dur
                program_n[e.name] = program_n.get(e.name, 0) + 1
    span_n = {}
    for s in trace.spans:
        if s.start_ns >= lo and s.end_ns <= hi:
            span_n[s.name] = span_n.get(s.name, 0) + 1
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(g, trace.spans), (g[1] - g[0]) * ns] for g in gaps[:TOP]]
    device_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * ns,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        op_s=op_s, kernel_s=kernel_s, kernel_n=kernel_n,
        program_s=program_s, program_n=program_n,
        span_n=span_n, idle_gaps=idle,
        device_ops=[[k, v] for k, v in device_ops])


def summarize_dir(log_dir: str) -> Summary:
    return summarize(load(find_xplane(log_dir)))

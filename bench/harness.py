"""What every cell's run shares: finding a cell's files by name, the chip
check, host spans, the compile clock, the traced window and the result line.

Everything a cell needs is found by the names in `BENCHMARK.json`:
  bench/configs/<config>.json    the model configuration as it is run
  bench/traffic/<traffic>.json   the traffic mix; its `driver` names
  bench/drivers/<driver>.py      the program entry the window drives
  bench/metrics/<metric>.py      one reader per per-layer metric
  bench/limits/<workload>.json   the limits of the numbers `correct` compares
  bench/reference/<family>.py    the plain reference of the config's family
  bench/flops/<family>.py        its operations and bytes
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class CellError(Exception):
    """The cell cannot run here: no chip, too few chips, a missing file."""


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc; else since import)."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(root: str, kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: str
    bench: dict
    workload: dict
    cfg: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, section: str) -> list:
        """The cell's metrics of one section of BENCHMARK.json."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]


def find_cell(root: str, workload: str) -> Cell:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = found[0]
    cfg_entry = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    cfg = read_json(os.path.join(root, cfg_entry["file"]))
    traffic = read_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return Cell(root, bench, w, cfg, traffic)


def limits_of(cell: Cell) -> dict:
    path = os.path.join(cell.root, "bench", "limits", cell.name + ".json")
    return read_json(path) if os.path.isfile(path) else {}


def check_devices(chips: int, *, allow_cpu: bool = False) -> list:
    """The cell's devices; refuses a run without a TPU or with too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise CellError(f"JAX finds no TPU (platform "
                        f"{devices[0].platform!r}): this benchmark measures "
                        "the chip and never falls back to the CPU")
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chip(s), JAX sees "
                        f"{len(devices)}")
    return devices[:chips]


def peak_of(device_kind: str) -> dict:
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise CellError(f"device kind {device_kind!r} is not in "
                        "bench/peaks.json")
    return table[device_kind]


class CompileClock:
    """JAX's own compile events: seconds, and how many fell in a window."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    EVENTS = (TRACE, "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total_s = 0.0
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total_s += duration
            if event == self.TRACE:
                self.traces += 1
            elif event == self.EVENTS[2]:
                self.compiles += 1


class Spans:
    """Host spans of the harness: seconds and counts per name, and, while
    the profiler runs, `bench.<name>` annotations in its trace."""

    def __init__(self):
        self.seconds: dict = {}
        self.counts: dict = {}
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self.seconds[name] = self.seconds.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's options and the harness's
    instruments. A driver calls `window()` around its measured loop."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    spans: Spans
    clock: CompileClock
    trace_dir: str
    window_start_age: float | None = None
    compiles_in_window: int = 0
    traces_in_window: int = 0
    memory_peak_bytes: int = 0

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def log(self, msg: str) -> None:
        print(f"# {msg}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends here, the spans start afresh,
        and with --trace 1 the profiler records it."""
        import jax
        self.spans.reset()
        c0, t0 = self.clock.compiles, self.clock.traces
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
            self.spans.tracing = True
        self.window_start_age = process_age_s()
        try:
            with self.spans("window"):
                yield
        finally:
            if self.trace:
                self.spans.tracing = False
                jax.profiler.stop_trace()
            self.compiles_in_window = self.clock.compiles - c0
            self.traces_in_window = self.clock.traces - t0

    def read_memory(self) -> int:
        """Peak bytes in use on the fullest chip; read before the reference
        runs, since a process's peak never falls."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peaks)
        return self.memory_peak_bytes


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)

"""The phase split and the between-run wait (bench/phases.py) against two
traces recorded on one TPU v5 lite, with numbers checked by hand from
their events: `probe.xplane.pb` (bench/testdata/record_probe.py) and a
tiny private training step, `phases.xplane.pb.gz` with its compiled HLO
(bench/testdata/record_phases.py)."""
import gzip
import os
import shutil
import types

import pytest

from bench import phases as P
from bench import trace as T
from repro.analysis import hlo

DATA = os.path.join(os.path.dirname(__file__), "..", "testdata")
PROBE = os.path.join(DATA, "probe.xplane.pb")
STEP = os.path.join(DATA, "phases.xplane.pb.gz")
STEP_HLO = os.path.join(DATA, "phases.hlo.txt.gz")


@pytest.fixture(scope="module")
def probe():
    return P.reduce(T.load(PROBE), {})


def test_between_run_idle_counts_gaps_outside_runs_only(probe):
    # runs of jit_prog (XLA Modules): start + duration
    runs = [(44_886_085, 44_886_085 + 174_789),
            (49_105_770, 49_105_770 + 174_222),
            (52_534_464, 52_534_464 + 174_733)]
    assert probe.program == "jit_prog" and probe.runs == runs
    # the gap after the first run's last op (45,060,873 ns; the run ends
    # 1 ns later) to the second run's first op (49,106,041 ns) counts up to
    # the second run's start: its first 271 ns are idle inside the run
    assert probe.gaps[1] == (45_060_874, 49_105_770)
    # the window (11,693,399 ns from the first span's start) less the runs
    assert probe.between_runs_s == pytest.approx(
        (11_693_399 - 174_789 - 174_222 - 174_733) * 1e-9, abs=1e-12)
    # the 835 ns of idle inside the runs (runs less busy) are not counted
    busy = 174_511 + 173_946 + 174_452
    assert probe.busy_s == pytest.approx(busy * 1e-9, abs=1e-12)
    assert probe.between_runs_s + probe.busy_s + 835e-9 == pytest.approx(
        11_693_399e-9, abs=1e-12)


def test_idle_is_split_by_host_span_by_overlap(probe):
    spans = T.load(PROBE).spans
    # the gap of the first test: sample 1 until 45,696,955, a hole to
    # dispatch 1 at 45,747,506, dispatch 1 to 46,094,526, a hole to fetch 1
    # at 46,097,126, fetch 1 to 47,164,306, a hole to sample 2 at
    # 47,178,815, sample 2 to the gap's end
    assert P.split_by_span(45_060_874, 49_105_770, spans) == {
        "bench.sample": (45_696_955 - 45_060_874)
        + (49_105_770 - 47_178_815),
        "none": (45_747_506 - 45_696_955) + (46_097_126 - 46_094_526)
        + (47_178_815 - 47_164_306),
        "bench.dispatch": 46_094_526 - 45_747_506,
        "bench.fetch": 47_164_306 - 46_097_126}
    assert sum(probe.idle_by_span.values()) == pytest.approx(
        probe.between_runs_s, abs=1e-12)


def test_slowest_runs_log_with_the_gaps_around_them(probe, capsys):
    P.log(probe)
    err = capsys.readouterr().err.splitlines()
    slow = [line for line in err if line.startswith("# slow run")]
    assert slow[0] == ("# slow run 1 of 3: 0.175 ms; gap before -, "
                       "after 4.045 ms")
    assert len(slow) == P.SLOWEST


def test_a_trace_without_the_phase_map_gives_no_phase(tmp_path):
    # the probe's program has no phase scopes: no phase reads, the wait
    # between runs still does
    d = tmp_path / "trace-cell" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(PROBE, d / "probe.xplane.pb")
    run = types.SimpleNamespace()
    path = P.latest_xplane(str(tmp_path))
    assert P.of(run, path).phase_s is None
    assert P.phase_ms(run, "forward") is None
    assert P.step_gap_ms(run) is None  # no bench.step span in the probe
    assert P.latest_xplane(str(tmp_path / "none")) is None


def test_clock_offset_bounds_from_dispatch_and_fetch(probe):
    # each run starts after its dispatch starts (the second run binds:
    # 50,057,815 - 49,105,770) and ends before its fetch ends (the third:
    # 54,643,055 - 52,709,197): the device's clock reads about 1-2 ms early
    assert probe.clock_offset_ns == (952_045, 1_933_858)
    # moved by the middle of the bounds, the device waited on the 2 ms
    # sleep in `bench.sample` more than on the fetch
    aligned = probe.idle_by_span_aligned
    assert aligned["bench.sample"] > 2 * aligned["bench.fetch"]
    assert sum(aligned.values()) == pytest.approx(probe.between_runs_s,
                                                  abs=1e-12)


@pytest.fixture(scope="module")
def step(tmp_path_factory):
    """The recorded tiny step: its trace, reduction and compiled HLO."""
    path = tmp_path_factory.mktemp("step") / "phases.xplane.pb"
    with gzip.open(STEP) as src, open(path, "wb") as fh:
        shutil.copyfileobj(src, fh)
    with gzip.open(STEP_HLO, "rt") as fh:
        text = fh.read()
    run = types.SimpleNamespace()
    return T.load(str(path)), P.of(run, str(path)), text, run


def test_the_trace_carries_the_compiled_step(step):
    trace, st, text, _ = step
    # the only program of the window; its HloProto from the metadata plane
    # maps as the compiled executable's own text does
    assert st.program == "jit_step_fn" and st.steps == 4
    assert hlo.op_phases(P.hlo_text(st.module)) == hlo.op_phases(text)


def test_every_op_of_the_recorded_step_is_in_the_map(step):
    trace, _, text, _ = step
    phases, containers = hlo.op_phases(text), hlo.container_ops(text)
    names = [e.name for e in trace.ops[0]]
    assert set(names) <= set(phases) | containers
    assert all(phases[n] is not None for n in names if n not in containers)
    kernels = [n for n in names if "ghost_norm" in n or "clip_reduce" in n]
    # 8 layer linears and the LM head, a norm and a clipped sum each, per
    # step, all in the backward
    assert len(kernels) == 4 * 2 * 9
    assert {phases[n] for n in kernels} == {hlo.BACKWARD}


def test_phase_sums_match_a_hand_count(step):
    _, st, _, run = step
    # device ns of the non-container `XLA Ops` events of the four runs, by
    # the phase of their instruction (500, 1,220 and 424 events)
    assert st.phase_s == pytest.approx({
        "forward": 84_758e-9, "backward": 276_444e-9,
        "noise_update": 74_244e-9}, abs=1e-12)
    # the containers' events (while.13: 230,291 ns, while.14: 38,118 ns)
    # span their bodies' events and are left out
    assert st.op_s["while.13"] == pytest.approx(230_291e-9, abs=1e-12)
    assert st.leaf_s == pytest.approx(435_446e-9, abs=1e-12)
    assert st.unattributed_s == 0 and st.missing_s == 0
    assert P.phase_ms(run, "backward") == pytest.approx(276_444e-6 / 4)
    # the window (25,963,160 ns) less the four runs (156,098 + 156,401 +
    # 156,958 + 156,344 ns), over four steps
    assert P.step_gap_ms(run) == pytest.approx(
        (25_963_160 - 625_801) * 1e-6 / 4)

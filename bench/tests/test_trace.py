"""The trace reduction against a small trace recorded on one TPU v5 lite
(bench/testdata/record_probe.py), with numbers checked by hand from the
trace's events."""
import os

import pytest

from bench import trace as T

PROBE = os.path.join(os.path.dirname(__file__), "..", "testdata",
                     "probe.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    return T.load(PROBE)


def test_events_are_read_from_device_and_host_planes(probe):
    assert len(probe.ops) == 1 and len(probe.ops[0]) == 15
    assert [m.name for m in probe.modules[0]] == ["jit_prog"] * 3
    assert [s.name for s in probe.spans] == [
        "bench.sample", "bench.dispatch", "bench.fetch"] * 3
    assert probe.ops[0][1].name == "ghost_norm.1"


def test_busy_window_and_kernel_time_match_hand_counts(probe):
    s = T.summarize(probe)
    # window: first span start 42,949,656 ns to last span end
    # 53,766,885 + 876,170 ns
    assert s.window_s == pytest.approx(11_693_399e-9, abs=1e-12)
    # busy: per step the union of copy-start, ghost_norm, slice fusion,
    # copy-done and the matmul fusion (they do not overlap)
    steps = [13 + 83_160 + 362 + 3 + 90_973,
             13 + 82_816 + 363 + 3 + 90_751,
             13 + 83_047 + 362 + 3 + 91_027]
    assert s.busy_s == pytest.approx(sum(steps) * 1e-9, abs=1e-12)
    assert s.kernel_s["ghost_norm"] == pytest.approx(
        (83_160 + 82_816 + 83_047) * 1e-9, abs=1e-12)
    assert s.kernel_n["ghost_norm"] == 3
    assert s.program_n == {"jit_prog": 3}
    assert s.program_s["jit_prog"] == pytest.approx(
        (174_789 + 174_222 + 174_733) * 1e-9, abs=1e-12)
    assert s.span_n == {"bench.sample": 3, "bench.dispatch": 3,
                        "bench.fetch": 3}


def test_breakdown_names_the_longest_gaps_and_ops(probe):
    b = T.summarize(probe).breakdown()
    assert [name for name, _ in b["device_ops"][:2]] == ["fusion",
                                                         "ghost_norm.1"]
    # the longest idle gap runs from the first program's last op end
    # (45,060,873 ns) to the second program's first op (49,106,041 ns); at
    # its midpoint, 47,083,457 ns, the host was inside the first
    # bench.fetch (46,097,126 + 1,067,180 ns)
    name, sec = b["idle_gaps"][0]
    assert name == "bench.fetch"
    assert sec == pytest.approx((49_106_041 - 45_060_873) * 1e-9, abs=1e-12)
    assert len(b["idle_gaps"]) <= T.TOP and len(b["device_ops"]) <= T.TOP


@pytest.mark.parametrize("text,name,base", [
    ("%ghost_norm.1 = f32[4,8,128] custom-call(x)", "ghost_norm.1",
     "ghost_norm"),
    ("%fusion.12 = bf16[] fusion(a)", "fusion.12", "fusion"),
    ("%copy-start = (bf16[2]) copy-start(w)", "copy-start", "copy-start"),
])
def test_op_names(text, name, base):
    assert T.op_name(text) == name
    assert T.base_name(name) == base


def test_merge_clips_and_joins_intervals():
    assert T.merge([(5, 9), (0, 3), (2, 4), (8, 20)], 1, 15) == [[1, 4],
                                                                 [5, 15]]

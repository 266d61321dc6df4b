"""The trace reduction (bench/trace_reduce.py) against hand counts: a
small profiler trace recorded on the CPU (three calls of a jitted
matmul+tanh inside ``bench.step`` spans and three of a reduction inside
``bench.submit`` spans, all under ``bench.window``), and synthetic
intervals."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402

TRACE = os.path.join(ROOT, "tests", "bench", "data", "cpu_trace.xplane.pb")

# Read off the recorded trace by hand (nanoseconds).
DOT_NS = [147416, 69391, 33714]
ALL_NS = [147416, 12342, 37594, 13026, 1519,
          69391, 7372, 33325, 12504, 955,
          33714, 7281, 34872, 11911, 789]
WINDOW_NS = 1128195


@pytest.fixture(scope="module")
def trace():
    return tr.read(TRACE)


def test_union_and_overlap_hand_counts():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.union([(0, 1), (1, 2)]) == 2
    assert tr.union([]) == 0
    merged = tr.union_list([(0, 2), (4, 6)])
    assert tr.overlap(1, 5, merged) == 2


def test_window_ops_and_busy(trace):
    assert len(trace.devices) == 1
    assert trace.window_s == pytest.approx(WINDOW_NS * 1e-9, rel=1e-9)
    assert len(trace.devices[0]) == len(ALL_NS)
    # the ops run one after another: busy is their summed duration
    assert trace.busy_s() == pytest.approx(sum(ALL_NS) * 1e-9, rel=1e-6)
    idle = 1 - trace.busy_s() / trace.window_s
    assert idle == pytest.approx(1 - sum(ALL_NS) / WINDOW_NS, rel=1e-6)


def test_kernel_time_by_name(trace):
    assert trace.time_of("dot_general") == pytest.approx(
        sum(DOT_NS) * 1e-9, rel=1e-6)
    runs = trace.programs_with("dot_general")
    assert list(runs.values()) == [3]          # one program, three calls
    (prog,) = runs
    assert trace.time_of("dot_general", program={prog}) == pytest.approx(
        sum(DOT_NS) * 1e-9, rel=1e-6)
    assert trace.time_of("dot_general", program={-1}) == 0


def test_breakdown(trace):
    b = tr.breakdown(trace)
    assert b["device_ops"][0][0] == "dot_general.1"
    assert b["device_ops"][0][1] == pytest.approx(sum(DOT_NS) * 1e-9,
                                                  rel=1e-6)
    assert len(b["idle_gaps"]) <= 10
    assert {g[0] for g in b["idle_gaps"]} <= {"bench.step", "bench.submit"}
    gaps = sum(g[1] for g in b["idle_gaps"])
    assert gaps <= trace.window_s - trace.busy_s() + 1e-12


def test_exposed_collective_time():
    ops = [tr.Op("all-to-all.1", 0.0, 4.0), tr.Op("fusion.2", 1.0, 2.0),
           tr.Op("fusion.3", 3.0, 5.0)]
    t = tr.Trace(devices=[ops], spans=[], window=(0.0, 6.0))
    assert t.exposed("all-to-all") == pytest.approx(2.0)
    assert t.time_of("all-to-all") == pytest.approx(4.0)
    assert t.busy_s() == pytest.approx(5.0)


def test_op_kinds_and_program_attribution():
    ops = [tr.Op("gmm.20", 1.0, 2.0), tr.Op("_combine_jit.8", 2.0, 2.5),
           tr.Op("while.13", 0.5, 3.0), tr.Op("gmm.20", 6.0, 7.0),
           tr.Op("fusion", 9.0, 9.5)]
    assert [o.kind for o in ops] == ["gmm", "_combine_jit", "while", "gmm",
                                     "fusion"]
    mods = [(0.4, 3.1, "jit__lambda(11)", 1), (5.9, 7.2, "jit__lambda(22)", 2)]
    tr._attribute(ops, mods)
    assert [o.program for o in ops] == ["jit__lambda(11)"] * 3 + [
        "jit__lambda(22)", None]
    t = tr.Trace(devices=[ops], spans=[], window=(0.0, 10.0))
    assert t.programs_with("gmm") == {"jit__lambda(11)": 1,
                                      "jit__lambda(22)": 1}
    assert t.time_of("gmm", program={"jit__lambda(22)"}) == 1.0
    # a while loop holds its body's ops: left out of the breakdown
    names = [n for n, _ in tr.breakdown(t)["device_ops"]]
    assert "while.13" not in names and names[0] == "gmm.20"

"""The reduction from trace to metrics, on hand-made events and on a
small trace recorded on one TPU v5e (two arrivals of the arrivals cell:
two `_alloc_all_jit` executions)."""
import glob
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000


def events():
    spans = [["bench.window", 0, 100 * MS],
             ["bench.departure", 0, 5 * MS],
             ["bench.arrival", 5 * MS, 35 * MS],
             ["bench.departure", 35 * MS, 75 * MS],
             ["bench.arrival", 75 * MS, 100 * MS]]
    ops = [["%while.4 = (f32[8]) while(...)", 20 * MS, 30 * MS],
           ["%fusion.1 = f32[8] fusion(...)", 25 * MS, 35 * MS],
           ["%while.4 = (f32[8]) while(...)", 80 * MS, 90 * MS]]
    modules = [["jit__alloc_all_jit(123)", 20 * MS, 35 * MS],
               ["jit__alloc_all_jit(123)", 80 * MS, 90 * MS]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": spans}


def test_busy_union_modules_and_idle_gaps():
    s = tr.reduce(events())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.025)          # 20-35 and 80-90 ms
    assert s.idle_share == pytest.approx(0.75)
    assert s.modules["_alloc_all_jit"] == (pytest.approx(0.025), 2)
    assert s.ops["%while.4"] == pytest.approx(0.02)
    assert s.ops["%fusion.1"] == pytest.approx(0.01)
    # each gap goes to the span that covers most of it: 0-20 ms to an
    # arrival (15 of 20), 35-80 to a departure (40 of 45), 90-100 to an
    # arrival
    assert s.idle_by_span == {"arrival": pytest.approx(0.03),
                              "departure": pytest.approx(0.045)}
    assert [round(g, 6) for _, g in s.gaps] == [0.045, 0.02, 0.01]
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["%while.4", pytest.approx(0.02)]
    assert b["idle_gaps"] == [["departure:total", pytest.approx(0.045)],
                              ["arrival:total", pytest.approx(0.03)],
                              ["departure", pytest.approx(0.045)],
                              ["arrival", pytest.approx(0.02)],
                              ["arrival", pytest.approx(0.01)]]


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_names():
    assert tr.module_name("jit__tables_jit(99)") == "_tables_jit"
    assert tr.op_name("%copy-start.3 = (f64[2]) copy-start(...)") == \
        "%copy-start.3"


def test_recorded_chip_trace():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    assert paths, "the recorded trace is missing"
    s = tr.reduce(tr.extract(paths[0]))
    assert s.n_devices == 1
    seconds, runs = s.modules["_alloc_all_jit"]
    assert runs == 2 and 0 < seconds <= s.busy_s < s.window_s
    assert set(s.idle_by_span) <= {"arrival", "departure", "outside"}
    assert s.idle_by_span["arrival"] > 0
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)

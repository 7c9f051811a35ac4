"""The program's spans in a trace: self times, window clipping and idle
by innermost span on hand-made events; every new per-layer metric of
each cell read from a traced run at m=30 on the CPU; nothing read, and
nothing raised, from a trace of a program without the spans."""
import glob
import os
import time

import pytest

from bench import harness as H
from bench import program_spans as ps
from bench.tests.test_harness import small_cell

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000
NEW = {"igniter-m1000.provision": {
           "alloc_host_ms.provision", "alloc_fetch_ms.provision",
           "alloc_iters.provision", "prepare_s.provision",
           "alg1_place_s.provision"},
       "igniter-m1000.arrivals": {
           "alloc_host_ms.place", "alloc_fetch_ms.place",
           "alloc_iters.place", "cluster_build_ms.place"},
       "igniter-m1000.validate": {
           "sim_setup_ms", "sim_passes_ms", "tables_host_ms"}}


def events():
    """A 100 ms window (10-110 ms): one arrival step holding
    add_workload (20-90) with a cluster build (25-45) and a grant-loop
    call (50-80) whose fetch (60-70) overlaps the device (58-72); a
    span that starts before the window is clipped to it."""
    return {"window": [10 * MS, 110 * MS],
            "steps": [[15 * MS, 95 * MS]],
            "busy": [[58 * MS, 72 * MS], [0, 12 * MS]],
            "spans": [["igniter.remove_workload", 5 * MS, 15 * MS, {}],
                      ["igniter.add_workload", 20 * MS, 90 * MS, {}],
                      ["igniter.cluster_build", 25 * MS, 45 * MS, {}],
                      ["igniter.alloc_all", 50 * MS, 80 * MS,
                       {"iters": 3}],
                      ["igniter.alloc_all.fetch", 60 * MS, 70 * MS, {}]]}


def test_self_times_clipping_and_idle_by_innermost_span():
    sp = ps.build(events())
    assert sp.count("igniter.remove_workload") == 1
    assert sp.total("igniter.remove_workload") == pytest.approx(0.005)
    assert sp.self_times() == {
        "igniter.remove_workload": pytest.approx(0.005),
        "igniter.add_workload": pytest.approx(0.020),
        "igniter.cluster_build": pytest.approx(0.020),
        "igniter.alloc_all": pytest.approx(0.020),
        "igniter.alloc_all.fetch": pytest.approx(0.010)}
    assert sp.counter_mean("igniter.alloc_all", "iters") == 3.0
    # idle: 12-58 and 72-110 ms; 10-12 is busy before the window ends
    assert sp.idle_by_span() == {
        "igniter.remove_workload": pytest.approx(0.003),
        "outside": pytest.approx(0.005 + 0.020),
        "igniter.add_workload": pytest.approx(0.005 + 0.005 + 0.010),
        "igniter.cluster_build": pytest.approx(0.020),
        "igniter.alloc_all": pytest.approx(0.008 + 0.008)}
    # inside the step (15-95 ms) 66 ms idle, of which the leaves hold the
    # cluster build's 20 ms
    assert sp.leaf_idle_share() == pytest.approx(20 / 66)
    assert sum(sp.idle_by_span().values()) == pytest.approx(
        0.1 - 0.016)


def test_a_trace_without_program_spans_reads_nothing(monkeypatch):
    """The recorded chip trace predates the program's spans: every new
    reader returns None and none raises."""
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    sp = ps.build(ps.extract(path))
    assert sp.events == [] and sp.steps and sp.busy
    monkeypatch.setattr(ps, "load", lambda path=None: sp)
    bench = H.load_benchmark()
    facts = {"steps": 2, "window_s": 1.0, "simulated_s": 10.0}
    for names in NEW.values():
        for name in names:
            path = H.metric_path(name)
            read = H._load_module(path, "m_" + name.replace(".", "_")).read
            assert read(None, facts) is None, name
    assert {m["name"] for m in bench["per_layer"]} >= set().union(
        *NEW.values())


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_run_reads_every_new_metric_at_m30(name, monkeypatch,
                                                  tmp_path):
    monkeypatch.setattr(H, "TRACE_DIR", str(tmp_path / "trace"))
    cell = small_cell(H.load_benchmark(), name)
    if "validate" in name:
        cell.traffic["horizon_s"] = 1.0
    cell.traffic["trace_steps"] = min(cell.traffic["trace_steps"], 3)
    res = H.run(cell, 2 ** 31 + 17, 0.3, True, time.perf_counter())
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert NEW[name] <= set(got)
    assert all(got[k] > 0 for k in NEW[name]), got
    assert not os.path.exists(H.TRACE_DIR)


def test_device_readers_key_on_jitted_names_that_exist():
    """`alloc_device_ms` and `tables_device_ms` find their programs by
    the jitted functions' names."""
    from repro.core import perf_model_jax as pmj
    from repro.serving import physics_jax
    for metric, fn in (("alloc_device_ms", pmj._alloc_all_jit),
                       ("tables_device_ms", physics_jax._tables_jit)):
        mod = H._load_module(H.metric_path(metric), "m_" + metric)
        assert fn.__name__ == mod.MODULE

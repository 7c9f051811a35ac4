"""The ``rows_sent`` counter of the grant loop as per-layer metrics:
pinned in ``BENCHMARK.json``, read from a traced run at m=30 on the CPU
(a provision copies the whole state only when a capacity grows, an
arrival's fresh cluster always), and nothing read, nothing raised, from
a trace of a program without the counter."""
import glob
import os
import time

import pytest

from bench import harness as H
from bench import program_spans as ps
from bench.tests.test_harness import small_cell

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = {"igniter-m1000.provision": "alloc_rows_sent.provision",
       "igniter-m1000.arrivals": "alloc_rows_sent.place"}


def _reader(name):
    return H._load_module(H.metric_path(name),
                          "m_" + name.replace(".", "_")).read


def test_metrics_are_pinned_in_their_cells():
    by_name = {m["name"]: m for m in H.load_benchmark()["per_layer"]}
    for cell, name in NEW.items():
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["layer"] == "Alg. 2 grant loop"


def test_a_trace_without_the_counter_reads_nothing(monkeypatch):
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    sp = ps.build(ps.extract(path))
    monkeypatch.setattr(ps, "load", lambda path=None: sp)
    for name in NEW.values():
        assert _reader(name)(None, {}) is None, name
    sp.events.append(ps.Event("igniter.alloc_all", 0, 1, {"iters": 3}))
    for name in NEW.values():
        assert _reader(name)(None, {}) is None, name


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_run_reads_rows_sent_at_m30(name, monkeypatch, tmp_path):
    monkeypatch.setattr(H, "TRACE_DIR", str(tmp_path / "trace"))
    cell = small_cell(H.load_benchmark(), name)
    cell.traffic["trace_steps"] = min(cell.traffic["trace_steps"], 3)
    res = H.run(cell, 2 ** 31 + 17, 0.3, True, time.perf_counter())
    assert res["correct"], res["checks"]
    got = res["metrics"][NEW[name]]["value"]
    if "arrivals" in name:
        assert got >= 32               # each arrival builds a new cluster
    else:
        assert 1 <= got <= 8

"""The harness finds every file by name, and each cell's check passes on
the system and fails on a broken one, at m=30 on the CPU."""
import json
import os
import re
import shutil
import time

import pytest

from bench import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return H.load_benchmark()


def test_benchmark_json_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1] == "bench/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            cell = H.Cell(bench, w)
            assert m["moves"] in [x["name"] for x in cell.end_to_end]
    for w in bench["workloads"]:
        cell = H.Cell(bench, w["name"])
        assert w["chips"] == 1
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_every_cell_resolves_by_name(bench):
    for w in bench["workloads"]:
        cell = H.Cell(bench, w["name"])
        assert os.path.exists(cell.config_path)
        assert cell.traffic_path.endswith(f"traffic/{w['traffic']}.json")
        assert os.path.exists(cell.driver_path)
        for name in cell.readers:
            assert callable(cell.reader(name))


def test_added_files_are_found_without_editing_the_harness(tmp_path, bench):
    root = tmp_path / "tree"
    shutil.copytree(os.path.join(H.ROOT, "bench"), root / "bench")
    cfg = json.load(open(root / "bench/configs/igniter-m1000.json"))
    cfg["m"] = 500
    json.dump(cfg, open(root / "bench/configs/igniter-m500.json", "w"))
    traffic = json.load(open(root / "bench/traffic/arrivals.json"))
    traffic["warmup_steps"] = 1
    json.dump(traffic, open(root / "bench/traffic/churn1.json", "w"))
    (root / "bench/metrics/edits_per_s.py").write_text(
        "def read(summary, facts):\n"
        "    return facts['steps'] / facts['window_s']\n")
    b = json.loads(json.dumps(bench))
    b["configs"].append(dict(b["configs"][0], name="igniter-m500",
                             file="bench/configs/igniter-m500.json"))
    b["workloads"].append({"name": "igniter-m500.churn1",
                           "config": "igniter-m500", "traffic": "churn1",
                           "chips": 1, "why": "added as files"})
    b["end_to_end"][1]["workloads"].append("igniter-m500.churn1")
    b["per_layer"].append({"name": "edits_per_s", "unit": "1/s",
                           "better": "higher", "source": "program_counter",
                           "layer": "device", "moves": "place_ms_p95",
                           "workloads": ["igniter-m500.churn1"]})
    cell = H.Cell(b, "igniter-m500.churn1", root=str(root))
    assert cell.config["m"] == 500
    assert cell.traffic["warmup_steps"] == 1
    assert cell.driver_path == str(root / "bench/drivers/arrivals.py")
    assert callable(cell.module().control)
    assert cell.reader("edits_per_s")(None, {"steps": 6,
                                             "window_s": 2.0}) == 3.0
    assert H.metric_path("device_idle.new_cell", str(root / "bench")) \
        .endswith("metrics/device_idle.py")


def small_cell(bench, name, m=30):
    cell = H.Cell(bench, name)
    cell.config["m"] = m
    return cell


def run_small(cell, seconds=0.5, seed=2 ** 31 + 11):
    return H.run(cell, seed, seconds, False, time.perf_counter())


@pytest.mark.parametrize("name", ["igniter-m1000.provision",
                                  "igniter-m1000.arrivals",
                                  "igniter-m1000.validate"])
def test_cell_step_and_check_pass_at_m30(bench, name):
    cell = small_cell(bench, name)
    if "validate" in name:
        cell.traffic["horizon_s"] = 1.0
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_cli_refuses_a_cpu(capsys):
    assert H.main(["--workload", "igniter-m1000.provision", "--seed", "1",
                   "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "accelerator" in out.err

"""The control, the plain reference in float32 in the program's place,
run through the harness and judged by its own comparison, here at m=30
on three seeds.

It comes out not correct where the timed path's answer carries the
arithmetic: the simulator's latencies.  The planner's answer is a set of
grant decisions with 1e-9 epsilons, which float32 arithmetic leaves as
they are: those cells' control reads every planner number at 0 (PERF.md
records the readings at the cells' own size)."""
import pytest

from bench import control
from bench import harness as H

SEEDS = (1, 2, 2 ** 31 + 3)


def small(name):
    cell = H.Cell(H.load_benchmark(), name)
    cell.config["m"] = 30
    if "validate" in name:
        cell.traffic["horizon_s"] = 1.0
    return cell


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_control_fails_the_simulator_check(seed):
    res = control.run(small("igniter-m1000.validate"), seed, 0.3)
    assert not res["correct"]
    c = res["checks"]["latency_rel_off"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("name", ["igniter-m1000.provision",
                                  "igniter-m1000.arrivals"])
@pytest.mark.parametrize("seed", SEEDS)
def test_float32_control_keeps_the_planner_decisions(name, seed):
    res = control.run(small(name), seed, 0.3)
    assert res["attempted"] >= 1
    assert all(c["value"] == 0 for k, c in res["checks"].items()), \
        res["checks"]


def test_control_restores_the_program():
    from repro.core import provisioner
    real = provisioner.provision_cheapest
    control.run(small("igniter-m1000.provision"), 1, 0.1)
    assert provisioner.provision_cheapest is real

"""The timed path broken underneath: each fault makes ``correct`` false.

The faults are planted where the answer is produced: one extra
``+r_unit`` grant in the jitted Alg. 2 grant loop's result, and a
latency table one part in a million off in the simulator's jitted
table build."""
import time

import numpy as np
import pytest

from bench import harness as H
from bench.tests.test_harness import small_cell


@pytest.fixture
def one_extra_grant(monkeypatch):
    """The jax grant loop hands back one grant too many, on the newcomer
    of the first feasible device of every call."""
    from repro.core import perf_model_jax as pmj
    real = pmj.alloc_all_jax

    def broken(cl, spec, coeffs, batch, r_lower):
        feasible, rr, rn, r_inter = real(cl, spec, coeffs, batch, r_lower)
        rows = np.flatnonzero(feasible)
        if rows.size:
            rn = rn.copy()
            rn[rows[0]] = np.round(rn[rows[0]] + cl.hw.r_unit, 10)
        return feasible, rr, rn, r_inter
    monkeypatch.setattr(pmj, "alloc_all_jax", broken)


@pytest.mark.parametrize("name", ["igniter-m1000.provision",
                                  "igniter-m1000.arrivals"])
def test_perturbed_grant_fails_the_check(one_extra_grant, name):
    cell = small_cell(H.load_benchmark(), name)
    res = H.run(cell, 7, 0.3, False, time.perf_counter())
    assert not res["correct"]
    assert res["failed"] >= 1


def test_altered_latency_table_fails_the_check(monkeypatch):
    from repro.serving import physics_jax
    real = physics_jax.table_values

    def broken(*args, **kwargs):
        t_load, t_sch, t_act, t_fb, freq = real(*args, **kwargs)
        return t_load, t_sch, t_act * (1 + 1e-6), t_fb, freq
    monkeypatch.setattr(physics_jax, "table_values", broken)
    cell = small_cell(H.load_benchmark(), "igniter-m1000.validate")
    cell.traffic["horizon_s"] = 1.0
    res = H.run(cell, 7, 0.3, False, time.perf_counter())
    assert not res["correct"]
    assert res["checks"]["latency_rel_off"]["value"] > \
        res["checks"]["latency_rel_off"]["limit"]

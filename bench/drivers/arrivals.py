"""Online churn on a standing plan: ``remove_workload`` + ``add_workload``.

Set-up provisions the deployment's workloads (in the seed's order) with
the numpy planner, the standing plan.  Each step is one departure, a
resident drawn by the seed, then one arrival, a fresh workload from the
same jittered App-table mix, placed with the deployment's planner
configuration: one Alg. 2 grant-loop call over every open device.  The
number of workloads stays at ``m``.  Only the arrival is timed for
``place_ms_p95``.  The check replays the same edits on the plain
reference, from its own standing plan, and compares the whole plan after
every arrival.  `control` puts the reference in the program's place.
"""
from __future__ import annotations

import time

import numpy as np

from bench import deployment as dep
from bench.reference import planner as ref


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.failed = 0

    def setup(self) -> None:
        from repro.core import provisioner as prov
        self.prov = prov
        i = self.inputs = dep.ProgramInputs(self.cfg)
        self.workloads = dep.run_workloads(self.cfg, self.seed)
        plan, hw = prov.provision_cheapest(
            i.specs(self.workloads), i.profiles_by_hw, i.hardware,
            config=i.config.replace(backend="numpy"))
        self.plan0, self.hw = plan, hw
        self.profiles = i.profiles_by_hw[hw.name]

    def _reset(self, stream: int) -> None:
        self.plan = self.plan0
        self.churn = dep.Churn(self.cfg, [w[0] for w in self.workloads],
                               self.seed, stream)

    def warmup(self) -> None:
        self._reset(2)
        for _ in range(int(self.traffic["warmup_steps"])):
            self._edit()
        self._reset(1)

    def _edit(self):
        gone, w = self.churn.next()
        spec = self.inputs.specs([w])[0]
        with self.span("departure"):
            self.plan = self.prov.remove_workload(self.plan, gone)
        with self.span("arrival"):
            t0 = time.perf_counter()
            self.plan = self.prov.add_workload(self.plan, spec, self.profiles,
                                               self.hw,
                                               config=self.inputs.config)
            wall = time.perf_counter() - t0
        return wall, gone, w, self.plan

    def step(self):
        return self._edit()

    def end_to_end(self, records, window_s: float) -> dict:
        walls = np.array([r[0] for r in records]) * 1e3
        return {"place_ms_p95": float(np.percentile(walls, 95))}

    def check(self, records) -> dict:
        fleets = {f.name: f for f in dep.reference_fleets(self.cfg)}
        plan, fleet, _ = ref.provision_cheapest(self.workloads,
                                                list(fleets.values()))
        standing = dep.rows_off(dep.plan_key(self.plan0), dep.ref_key(plan))
        if fleet.name != self.hw.name:
            standing = max(len(plan), len(self.plan0.placements))
        known = {w[0]: w for w in self.workloads}
        off = 0
        for _, gone, w, p in records:
            plan = ref.remove_workload(plan, gone)
            known[w[0]] = w
            plan = ref.add_workload(plan, w, fleet, known)
            bad = dep.rows_off(dep.plan_key(p), dep.ref_key(plan))
            self.failed += bool(bad)
            off += bool(bad)
        return {"standing_plan_off": (standing, 0),
                "arrivals_off": (off, 0)}


def control(cfg: dict, dtype) -> dict:
    """The program's attributes that the plain reference in ``dtype``
    replaces, as ``{(module, name): replacement}``."""
    from repro.core import provisioner as prov
    fleets = {f.name: f for f in dep.reference_fleets(cfg, dtype)}

    def add_workload(plan, spec, profiles, hw, config=None):
        specs = {p.workload.name: p.workload for p in plan.placements}
        specs[spec.name] = spec
        known = {n: dep.workload_of(s) for n, s in specs.items()}
        out = ref.add_workload(dep.from_program(plan), known[spec.name],
                               fleets[hw.name], known)
        return dep.to_program(out, specs, hw)
    return {(prov, "provision_cheapest"): dep.reference_provision(cfg, dtype),
            (prov, "add_workload"): add_workload}

"""Whole provisions: ``provision_cheapest`` on the deployment's workloads.

Each step provisions the run's workload set from scratch on every fleet
and keeps the cheapest plan (paper Sec. 5.4).  The set is the
deployment's ``m`` workloads in an order shuffled by the seed, so every
seed offers the same sizes.  The check compares each plan with the plain
reference's: hardware, every placement ``(name, gpu, r, batch)``, $/h
and the set of predicted violations.  `control` puts the reference in
the program's place.
"""
from __future__ import annotations

import time

from bench import deployment as dep
from bench.reference import planner as ref


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.failed = 0

    def setup(self) -> None:
        from repro.core import provisioner as prov
        self.prov = prov
        self.inputs = dep.ProgramInputs(self.cfg)
        self.workloads = dep.run_workloads(self.cfg, self.seed)
        self.specs = self.inputs.specs(self.workloads)

    def _provision(self):
        i = self.inputs
        return self.prov.provision_cheapest(self.specs, i.profiles_by_hw,
                                            i.hardware, config=i.config)

    def warmup(self) -> None:
        self._provision()

    def step(self):
        with self.span("provision"):
            t0 = time.perf_counter()
            plan, hw = self._provision()
            wall = time.perf_counter() - t0
        return wall, plan, hw

    def end_to_end(self, records, window_s: float) -> dict:
        return {"provision_s": sum(r[0] for r in records) / len(records)}

    def check(self, records) -> dict:
        fleets = dep.reference_fleets(self.cfg)
        plan, fleet, cost = ref.provision_cheapest(self.workloads, fleets)
        key = dep.ref_key(plan)
        by_name = {w[0]: w for w in self.workloads}
        viol = set(ref.predicted_violations(plan, fleet, by_name))
        off = cost_off = viol_off = 0
        for _, p, hw in records:
            got = dep.plan_key(p)
            bad = dep.rows_off(got, key)
            if hw.name != fleet.name:
                bad = max(len(got), len(key))
            pv = set(self.prov.predicted_violations(
                p, self.inputs.profiles_by_hw[hw.name], hw,
                config=self.inputs.config))
            c = abs(p.cost_per_hour() - cost)
            v = len(pv ^ viol)
            self.failed += bool(bad or c or v)
            off, cost_off, viol_off = max(off, bad), max(cost_off, c), \
                max(viol_off, v)
        return {"placements_off": (off, 0), "cost_off": (cost_off, 0.0),
                "pred_violations_off": (viol_off, 0)}


def control(cfg: dict, dtype) -> dict:
    """The program's attributes that the plain reference in ``dtype``
    replaces, as ``{(module, name): replacement}``."""
    from repro.core import provisioner as prov
    return {(prov, "provision_cheapest"): dep.reference_provision(cfg, dtype)}

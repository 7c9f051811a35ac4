"""Plan validation: ``simulate_full`` over every device of a standing plan.

Set-up provisions the deployment's workloads (in the seed's order) with
the numpy planner.  Each step simulates the whole plan for the mix's
``horizon_s`` simulated seconds with constant-rate arrivals, a fresh
simulation seed per step; no grant-loop call is made.  The check draws
``check_steps`` of the window's steps from the seed and runs the plain
reference simulator on each, over the reference planner's own standing
plan (itself compared with the program's): per-request latencies,
request counts and violation sets.  `control` puts the reference in the
program's place.
"""
from __future__ import annotations

import time

import numpy as np

from bench import deployment as dep
from bench.reference import planner as ref
from bench.reference import simulator as ref_sim


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.horizon_s = float(traffic["horizon_s"])
        self.failed = 0
        self.k = 0

    def setup(self) -> None:
        from repro.core import provisioner as prov
        from repro.profiling.metrics import ServedModelDesc
        from repro.serving.simulator import simulate_full
        self.simulate_full = simulate_full
        i = self.inputs = dep.ProgramInputs(self.cfg)
        self.workloads = dep.run_workloads(self.cfg, self.seed)
        self.plan, self.hw = prov.provision_cheapest(
            i.specs(self.workloads), i.profiles_by_hw, i.hardware,
            config=i.config.replace(backend="numpy"))
        self.models = {k: ServedModelDesc(**v)
                       for k, v in self.cfg["models"].items()}

    def _simulate(self, sim_seed: int):
        return self.simulate_full(self.plan, self.models, self.hw,
                                  duration_s=self.horizon_s, seed=sim_seed,
                                  backend=self.traffic["backend"])

    def warmup(self) -> None:
        self._simulate(self._sim_seed(-1))

    def _sim_seed(self, k: int) -> int:
        return int(np.random.default_rng([self.seed, 3, k + 1])
                   .integers(2 ** 62))

    def step(self):
        s = self._sim_seed(self.k)
        self.k += 1
        with self.span("simulate"):
            t0 = time.perf_counter()
            res = self._simulate(s)
            wall = time.perf_counter() - t0
        return wall, s, res

    def end_to_end(self, records, window_s: float) -> dict:
        n = sum(int(r[2].stats["n_requests"]) for r in records)
        return {"sim_requests_per_s": n / sum(r[0] for r in records)}

    def facts(self, records) -> dict:
        return {"simulated_s": self.horizon_s * len(records)}

    def check(self, records) -> dict:
        rng = np.random.default_rng([self.seed, 4])
        k = min(len(records), int(self.traffic["check_steps"]))
        picked = sorted(rng.choice(len(records), size=k, replace=False))
        by_name = {w[0]: w for w in self.workloads}
        plan, fleet, _ = ref.provision_cheapest(
            self.workloads, dep.reference_fleets(self.cfg))
        standing = dep.rows_off(dep.plan_key(self.plan), dep.ref_key(plan))
        if fleet.name != self.hw.name:
            standing = max(len(plan), len(self.plan.placements))
        hw = next(h for h in self.cfg["hardware"] if h["name"] == fleet.name)
        specs = {s.name: s for s in self.inputs.specs(self.workloads)}
        rtol = float(self.traffic["latency_rtol"])
        rel = 0.0
        counts = viol = 0
        for j in picked:
            _, s, res = records[j]
            want = ref_sim.simulate(plan, by_name, self.cfg["models"], hw,
                                    self.cfg["physics"],
                                    self.horizon_s * 1e3, s)
            got = res.request_latencies
            bad_counts = sum(got.get(n, np.empty(0)).shape != x.shape
                             for n, x in want.items()) \
                + len(set(got) ^ set(want))
            rel_j = 0.0
            for n, x in want.items():
                y = got.get(n)
                if y is not None and y.shape == x.shape and x.size:
                    rel_j = max(rel_j,
                                float(np.max(np.abs(y - x) / np.abs(x))))
            v = len(set(res.violations(specs))
                    ^ set(ref_sim.violations(want, by_name, self.horizon_s)))
            self.failed += bool(bad_counts or v or rel_j > rtol)
            rel = max(rel, rel_j)
            counts, viol = max(counts, bad_counts), max(viol, v)
        return {"standing_plan_off": (standing, 0),
                "latency_rel_off": (rel, rtol),
                "request_counts_off": (counts, 0),
                "violations_off": (viol, 0)}


class _ReferenceResult:
    """The parts of the simulator's result that the driver reads."""

    def __init__(self, lat: dict, duration_s: float):
        self.request_latencies = lat
        self.duration_s = duration_s
        self.stats = {"n_requests": sum(x.size for x in lat.values())}

    def violations(self, specs: dict):
        return ref_sim.violations(
            self.request_latencies,
            {n: dep.workload_of(s) for n, s in specs.items()},
            self.duration_s)


def control(cfg: dict, dtype) -> dict:
    """The program's attributes that the plain reference in ``dtype``
    replaces, as ``{(module, name): replacement}``."""
    from repro.core import provisioner as prov
    from repro.serving import simulator

    def simulate_full(plan, models, hw, *, duration_s, seed, **kwargs):
        known = {p.workload.name: dep.workload_of(p.workload)
                 for p in plan.placements}
        hwd = next(h for h in cfg["hardware"] if h["name"] == hw.name)
        lat = ref_sim.simulate(dep.from_program(plan), known, cfg["models"],
                               hwd, cfg["physics"], duration_s * 1e3, seed,
                               dtype)
        return _ReferenceResult(lat, duration_s)
    return {(prov, "provision_cheapest"): dep.reference_provision(cfg, dtype),
            (simulator, "simulate_full"): simulate_full}

"""A deployment file turned into inputs: fleets, coefficients, workloads.

The workload generator draws jittered rows of the deployment's App
table from ``--seed`` (the same draws as the repository's
``synthetic_workloads``).  Workloads come out as plain tuples
``(name, model, slo_ms, rate_rps)``; `ProgramInputs` turns them and the
fleets into the objects the system under test takes, and `to_program`
and `from_program` carry a plan between the reference's tuples and the
system's types.
"""
from __future__ import annotations

import json
from typing import Dict, List, Tuple

import numpy as np

Workload = Tuple[str, str, float, float]


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def workloads(cfg: dict, m: int, rng: np.random.Generator,
              prefix: str = "S", start: int = 0) -> List[Workload]:
    """``m`` jittered App-table rows: SLO x U[lo, hi), rate x U[lo, hi)."""
    table = cfg["app_table"]
    (s_lo, s_hi), (r_lo, r_hi) = cfg["jitter"]["slo"], cfg["jitter"]["rate"]
    nd = cfg["jitter"]["round"]
    out = []
    for i in range(start, start + m):
        model, slo, rate = table[int(rng.integers(len(table)))]
        out.append((f"{prefix}{i}", model,
                    round(float(slo * rng.uniform(s_lo, s_hi)), nd),
                    round(float(rate * rng.uniform(r_lo, r_hi)), nd)))
    return out


def run_workloads(cfg: dict, seed: int) -> List[Workload]:
    """The deployment's ``m`` workloads (drawn from its own
    ``workload_seed``), in an order shuffled by the run's seed: every
    seed offers the same sizes."""
    ws = workloads(cfg, cfg["m"], np.random.default_rng(cfg["workload_seed"]))
    perm = np.random.default_rng([seed, 0]).permutation(len(ws))
    return [ws[i] for i in perm]


class Churn:
    """Departures and arrivals drawn from a seed: each edit removes a
    resident chosen uniformly and brings a fresh workload from the
    deployment's mix, named ``A<k>``."""

    def __init__(self, cfg: dict, residents: List[str], seed: int,
                 stream: int):
        self.cfg = cfg
        self.residents = list(residents)
        self.rng = np.random.default_rng([seed, stream])
        self.k = 0

    def next(self) -> Tuple[str, Workload]:
        gone = self.residents.pop(int(self.rng.integers(len(self.residents))))
        w = workloads(self.cfg, 1, self.rng, prefix="A", start=self.k)[0]
        self.k += 1
        self.residents.append(w[0])
        return gone, w


def rows_off(got: List[tuple], want: List[tuple]) -> int:
    """Rows that differ between two plans, the longer one's extra rows
    included."""
    return (sum(a != b for a, b in zip(got, want))
            + abs(len(got) - len(want)))


def reference_fleets(cfg: dict, dtype=np.float64):
    from bench.reference.planner import Fleet
    bud = cfg["budget_model"]
    return [Fleet(hw, cfg["profiles"][hw["name"]], bud, dtype=dtype)
            for hw in cfg["hardware"]]



class ProgramInputs:
    """The system's own types, built from the deployment file."""

    def __init__(self, cfg: dict):
        from repro.core.types import (HardwareSpec, PlannerConfig,
                                      WorkloadCoefficients)
        self.hardware = [HardwareSpec(**hw) for hw in cfg["hardware"]]
        self.profiles_by_hw: Dict[str, dict] = {
            hw: {m: WorkloadCoefficients(**c) for m, c in prof.items()}
            for hw, prof in cfg["profiles"].items()}
        p = cfg["planner"]
        self.config = PlannerConfig(backend=p["backend"], engine=p["engine"],
                                    budget=p["budget"], batch=p["batch"],
                                    replicate=p["replicate"])

    @staticmethod
    def specs(ws: List[Workload]):
        from repro.core.types import WorkloadSpec
        return [WorkloadSpec(name=n, model=m, slo_ms=s, rate_rps=r)
                for n, m, s, r in ws]

    def hw(self, name: str):
        return next(h for h in self.hardware if h.name == name)


def reference_provision(cfg: dict, dtype):
    """``provision_cheapest`` answered by the plain reference in
    ``dtype``: the standing plans' part of each driver's control."""
    from bench.reference import planner as ref
    inputs = ProgramInputs(cfg)

    def provision_cheapest(specs, profiles_by_hw, hardware, config=None):
        plan, fleet, _ = ref.provision_cheapest(
            [workload_of(s) for s in specs], reference_fleets(cfg, dtype))
        hw = inputs.hw(fleet.name)
        return to_program(plan, {s.name: s for s in specs}, hw), hw
    return provision_cheapest


def workload_of(spec) -> Workload:
    return (spec.name, spec.model, spec.slo_ms, spec.rate_rps)


def to_program(plan, specs: Dict[str, object], hw):
    """A reference plan as the system's ``ProvisioningPlan``."""
    from repro.core.types import Placement, ProvisioningPlan
    out = ProvisioningPlan(hardware=hw)
    out.placements = [Placement(workload=specs[n], gpu=g, r=r, batch=b)
                      for n, g, r, b in plan]
    out.n_gpus = len({p[1] for p in plan})
    return out


def from_program(plan) -> List[tuple]:
    """A ``ProvisioningPlan`` as the reference's placement tuples."""
    return [(p.workload.name, p.gpu, p.r, p.batch) for p in plan.placements]


def plan_key(plan) -> List[tuple]:
    """A program plan as ``(name, gpu, round(r, 9), batch)`` rows."""
    return [(p.workload.name, p.gpu, round(p.r, 9), p.batch)
            for p in plan.placements]


def ref_key(plan) -> List[tuple]:
    return [(n, g, round(r, 9), b) for n, g, r, b in plan]


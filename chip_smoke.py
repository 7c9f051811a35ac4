"""On-chip smoke check: the `backend="jax"` planner, simulator and
controller at the paper's deployment scale, held to the numpy oracle.

Three phases, each run once with ``backend="numpy"`` (the pinned oracle,
on the host) and once with ``backend="jax"`` (jitted, on the
accelerator), at m=1000 synthetic workloads over the v5e and v4 fleets:

  A  provisioning  `provision_cheapest` — same hardware, placements
                   ``(name, gpu, round(r, 9), batch)``, $/h and
                   predicted-violation set;
  B  simulation    `simulate_full` over every device of that plan —
                   same violation set and per-workload request counts,
                   request latencies within TOL;
  C  control       a controlled ``diurnal`` run (as in
                   `benchmarks.dynamic_sweep`) — same n_reconfigs, final
                   placements and violation set.

Every wall printed is a first run in this process, so the jax walls
include compilation (and a persistent-cache lookup; see
`benchmarks/compile_cache.py`).  Any mismatch raises, and the script
exits non-zero without printing a result.  It also exits non-zero, naming
the platform it found, when JAX's default backend is not a TPU: there is
no CPU fallback.  The last line of stdout is one JSON object naming the
device.

Run:  python chip_smoke.py        (one process; it holds the chip)
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

M = 1000
SEED = 0
SIM_S = 3.0          # simulated seconds of the full-cluster run (phase B)
CTL_S = 4.0          # simulated seconds of the controlled run (phase C)
TOL = dict(rtol=1e-6, atol=1e-9)    # tests/test_perf_model_jax.py TOL
BACKENDS = ("numpy", "jax")


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _plan_key(plan):
    return [(p.workload.name, p.gpu, round(p.r, 9), p.batch)
            for p in plan.placements]


def _first_diff(a, b):
    """Index and pair of the first differing entries of two sequences."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i, x, y
    return min(len(a), len(b)), len(a), len(b)


def phase_provision(m: int):
    """A: numpy vs jax `provision_cheapest`.  Returns the (identical)
    plan, its hardware and the facts of the phase."""
    from benchmarks.scale_sweep import _context
    from repro.core import provisioner as prov
    from repro.core.types import PlannerConfig
    from repro.serving.workload import synthetic_workloads

    profiles_by_hw, hardware = _context()
    specs = synthetic_workloads(m, seed=SEED)
    out = {}
    for be in BACKENDS:
        t0 = time.perf_counter()
        plan, hw = prov.provision_cheapest(
            specs, profiles_by_hw, hardware,
            config=PlannerConfig(backend=be))
        wall = time.perf_counter() - t0
        viol = prov.predicted_violations(plan, profiles_by_hw[hw.name], hw)
        out[be] = (plan, hw, wall, set(viol))
    (pn, hn, wn, vn), (pj, hj, wj, vj) = out["numpy"], out["jax"]
    _check(hj.name == hn.name, f"hardware: jax {hj.name} != numpy {hn.name}")
    kn, kj = _plan_key(pn), _plan_key(pj)
    _check(kj == kn, "placements differ; first (index, numpy, jax): "
           f"{_first_diff(kn, kj)}")
    _check(pj.cost_per_hour() == pn.cost_per_hour(),
           f"$/h: jax {pj.cost_per_hour()} != numpy {pn.cost_per_hour()}")
    _check(vj == vn, f"predicted violations differ: {sorted(vj ^ vn)}")
    facts = {"phase": "A_provision", "m": m, "hardware": hn.name,
             "n_devices": pn.n_gpus, "cost_per_hour": pn.cost_per_hour(),
             "predicted_violations": len(vn), "numpy_wall_s": wn,
             "jax_first_run_wall_s_incl_compile": wj}
    return pn, hn, facts


def phase_simulate(m: int, plan, hw):
    """B: numpy vs jax `simulate_full` over every device of ``plan``."""
    import numpy as np
    from repro.serving.simulator import simulate_full
    from repro.serving.workload import models, synthetic_workloads

    sb = {s.name: s for s in synthetic_workloads(m, seed=SEED)}
    mods = models()
    res, walls = {}, {}
    for be in BACKENDS:
        t0 = time.perf_counter()
        res[be] = simulate_full(plan, mods, hw, duration_s=SIM_S,
                                seed=SEED, backend=be)
        walls[be] = time.perf_counter() - t0
    rn, rj = res["numpy"], res["jax"]
    vn, vj = set(rn.violations(sb)), set(rj.violations(sb))
    _check(vj == vn, f"simulated violations differ: {sorted(vj ^ vn)}")
    _check(set(rj.request_latencies) == set(rn.request_latencies),
           "simulated workload sets differ")
    n_req, max_rel = 0, 0.0
    for name, ln in rn.request_latencies.items():
        lj = rj.request_latencies[name]
        _check(lj.shape == ln.shape, f"{name}: request count jax "
               f"{lj.shape[0]} != numpy {ln.shape[0]}")
        np.testing.assert_allclose(lj, ln, err_msg=name, **TOL)
        n_req += ln.size
        if ln.size:
            max_rel = max(max_rel, float(np.max(np.abs(lj - ln)
                                                / np.abs(ln))))
    return {"phase": "B_simulate", "m": m, "sim_devices": plan.n_gpus,
            "sim_s": SIM_S, "requests": n_req, "violations": len(vn),
            "max_rel_latency_diff": max_rel,
            "numpy_wall_s": walls["numpy"],
            "jax_first_run_wall_s_incl_compile": walls["jax"]}


def phase_control(m: int, plan, hw):
    """C: numpy vs jax controlled ``diurnal`` run from ``plan``."""
    from benchmarks.dynamic_sweep import _make_trace, _violations
    from benchmarks.scale_sweep import _context
    from repro.core.types import PlannerConfig
    from repro.serving.controller import Controller
    from repro.serving.simulator import simulate_full
    from repro.serving.workload import models, synthetic_workloads

    profiles_by_hw, _ = _context()
    specs = synthetic_workloads(m, seed=SEED)
    horizon_ms = CTL_S * 1000.0
    tr, poisson = _make_trace("diurnal", [s.name for s in specs],
                              horizon_ms, SEED)
    mods = models()
    out = {}
    for be in BACKENDS:
        ctl = Controller(plan, profiles_by_hw[hw.name], hw,
                         config=PlannerConfig(backend=be).replace(
                             batch="joint"))
        t0 = time.perf_counter()
        res = simulate_full(plan, mods, hw, duration_s=CTL_S, seed=SEED,
                            poisson=poisson, trace=tr, adjust_fn=ctl,
                            adjust_scope="cluster", adjust_period_s=1.0,
                            backend=be)
        wall = time.perf_counter() - t0
        out[be] = (int(res.stats["n_reconfigs"]), _plan_key(ctl.plan),
                   set(_violations(res, specs, tr, horizon_ms)), wall,
                   len(ctl.edits))
    (rn, kn, vn, wn, en), (rj, kj, vj, wj, _) = out["numpy"], out["jax"]
    _check(rj == rn, f"n_reconfigs: jax {rj} != numpy {rn}")
    _check(kj == kn, "final placements differ; first (index, numpy, jax): "
           f"{_first_diff(kn, kj)}")
    _check(vj == vn, f"controlled violations differ: {sorted(vj ^ vn)}")
    return {"phase": "C_control", "m": m, "scenario": "diurnal",
            "sim_s": CTL_S, "n_reconfigs": rn, "n_edits": en,
            "final_placements": len(kn), "violations": len(vn),
            "numpy_wall_s": wn, "jax_first_run_wall_s_incl_compile": wj}


def main() -> int:
    from benchmarks.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({len(devices)} device(s))",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)

    def report(facts):
        print(" ".join(f"{k}={v}" for k, v in facts.items())
              + f" device_count={len(devices)} PASS", flush=True)

    plan, hw, facts = phase_provision(M)
    report(facts)
    report(phase_simulate(M, plan, hw))
    report(phase_control(M, plan, hw))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

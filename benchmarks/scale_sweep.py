"""Sec. 5.4 scalability sweep: the paper claims Algorithm 1 provisions
m = 1000 workloads in 4.61 s (the interference model is called O(m^2)
times).  This benchmark tracks that bound against the vectorized engine,
and — since the simulator is vectorized too — closes the loop against
ground truth at FULL cluster scale:

  * m in {10, 100, 500, 1000} synthetic workloads (jittered App-table
    mixes) provisioned over heterogeneous hardware (TPU v5e + v4) via
    `provision_cheapest`,
  * reported per m: provisioning wall-clock, devices used, chosen
    hardware, plan cost, and the model-predicted SLO-violation count,
  * for small m: the scalar-oracle wall-clock and a plan-identity check,
  * a FULL-cluster discrete-event simulation (`simulate_full`: every
    device, >= 10 simulated seconds) reporting *simulated* SLO
    violations next to the predicted ones, plus events/sec throughput
    so simulator perf regressions are visible per PR,
  * the predicted-vs-simulated violation GAP for BOTH budget splits:
    the queueing-aware default (`budget="queueing"`, the headline row)
    and the paper-faithful `budget="half"` comparison whose zero-slack
    split is what produced the historical 5-predicted-vs-178-simulated
    gap at m=1000 (`half_*` fields),
  * the replica-group plan (`provision(..., replicate=True)`, `repl_*`
    fields): workloads infeasible even solo at r = 1.0 are split into
    rate-share replicas (`w#0..w#k-1`) instead of clamped, so the
    honest full-device residual becomes servable — replica counts and
    the remaining residual are tracked per m (docs/provisioning.md).

The jitted backend (`--backend jax`, `PlannerConfig(backend="jax")`)
extends the sweep to m = 10,000 (~8k devices): provisioning runs
through `perf_model_jax.alloc_all_jax` and the simulator's latency
tables through the bulk `physics_jax` twin, with numpy staying the
pinned oracle (plans are checked identical at m <= 1000 by the jax
test suite).  Above `CMP_MAX_M` the half-split and replica comparison
plans are skipped — they would triple the simulation cost of the
informational m=10k tier without adding coverage the m=1000 row
doesn't already pin.

Run:  PYTHONPATH=src python -m benchmarks.scale_sweep [--quick] [--check]
      --quick        m <= 100 only (CI per-PR smoke; uploads artifact)
      --backend B    "numpy" (default) or "jax": planner + simulator
                     hot-path backend for every plan in the sweep
      --check        exit non-zero if any swept m in TARGETS exceeds its
                     (provision, full-simulation) wall-clock targets, or
                     if its simulated violations exceed 2x the predicted
                     count
      --sim-floor N  exit non-zero if any full simulation ran below N
                     simulated events per wall-clock second
      --gap-budget N exit non-zero if, for any m, the queueing-aware
                     plan's simulated violations exceed predicted + N
                     (negative disables; CI enforces this per PR)

Writes a JSON row dump (default benchmarks/out/scale_sweep_results.json
— gitignored; CI uploads it as an artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SIZES_FULL = (10, 100, 500, 1000)
SIZES_QUICK = (10, 100)
TARGET_S = 10.0          # CI bound for m=1000 provisioning (paper: 4.61 s)
SIM_TARGET_S = 60.0      # CI bound for the m=1000 FULL-cluster simulation
# per-m (provision, full-simulation) wall-clock targets --check enforces;
# m=10,000 rides the informational jax-tier job (single-digit minutes)
TARGETS = {1000: (TARGET_S, SIM_TARGET_S), 10000: (240.0, 300.0)}
CMP_MAX_M = 1000         # half-split / replica comparison plans up to here
DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out",
                           "scale_sweep_results.json")


def _context():
    from repro.core.experiments import fitted_context
    ctx5 = fitted_context("tpu-v5e")
    ctx4 = fitted_context("tpu-v4")
    profiles_by_hw = {ctx5.hw.name: ctx5.profiles,
                      ctx4.hw.name: ctx4.profiles}
    return profiles_by_hw, [ctx5.hw, ctx4.hw]


def sweep(sizes, *, seed: int = 0, oracle_max_m: int = 100,
          sim_duration_s: float = 10.0, backend: str = "numpy"):
    from repro.core import provisioner as prov
    from repro.core.types import PlannerConfig
    from repro.serving.simulator import simulate_full
    from repro.serving.workload import models, synthetic_workloads

    cfg = PlannerConfig(backend=backend)
    profiles_by_hw, hardware = _context()
    mods = models()
    rows = []
    for m in sizes:
        specs = synthetic_workloads(m, seed)
        sb = {s.name: s for s in specs}
        t0 = time.perf_counter()
        plan, hw = prov.provision_cheapest(specs, profiles_by_hw, hardware,
                                           config=cfg)
        wall = time.perf_counter() - t0
        viol = prov.predicted_violations(plan, profiles_by_hw[hw.name], hw)
        row = {
            "bench": "scale_sweep", "m": m,
            "budget": "queueing", "backend": backend,
            "wall_s": round(wall, 3),
            "n_devices": plan.n_gpus,
            "hardware": hw.name,
            "cost_per_hour": round(plan.cost_per_hour(), 2),
            "predicted_violations": len(viol),
            "target_s": TARGETS[m][0] if m in TARGETS else None,
        }
        if m <= oracle_max_m:
            t0 = time.perf_counter()
            oracle, hw_o = prov.provision_cheapest(
                specs, profiles_by_hw, hardware, engine="scalar")
            row["scalar_wall_s"] = round(time.perf_counter() - t0, 3)
            row["matches_scalar_oracle"] = (
                hw_o.name == hw.name
                and [(p.workload.name, p.gpu, round(p.r, 9), p.batch)
                     for p in oracle.placements]
                == [(p.workload.name, p.gpu, round(p.r, 9), p.batch)
                    for p in plan.placements])
        # full-cluster ground truth: EVERY device, simulated violations
        # reported next to the model-predicted count
        t0 = time.perf_counter()
        res = simulate_full(plan, mods, hw, duration_s=sim_duration_s,
                            seed=seed, backend=backend)
        sim_wall = time.perf_counter() - t0
        row.update({
            "sim_devices": plan.n_gpus,
            "sim_workloads": m,
            "sim_duration_s": sim_duration_s,
            "sim_wall_s": round(sim_wall, 3),
            "sim_violations": len(res.violations(sb)),
            "sim_requests": int(res.stats["n_requests"]),
            "sim_passes": int(res.stats["n_passes"]),
            "sim_events_per_s": round(res.stats["events_per_s"]),
            "sim_wait_mean_ms": round(res.stats["wait_mean_ms"], 3),
            "sim_wait_p99_ms": round(res.stats["wait_p99_ms"], 3),
            "sim_target_s": TARGETS[m][1] if m in TARGETS else None,
        })
        row["gap"] = row["sim_violations"] - row["predicted_violations"]
        if m <= CMP_MAX_M:
            _comparison_plans(row, specs, sb, profiles_by_hw, hardware,
                              mods, cfg, sim_duration_s, seed)
        rows.append(row)
        print(",".join(f"{k}={v}" for k, v in row.items() if v is not None),
              flush=True)
    return rows


def _comparison_plans(row, specs, sb, profiles_by_hw, hardware, mods, cfg,
                      sim_duration_s, seed):
    """Half-split + replica-group comparison rows (m <= CMP_MAX_M)."""
    from repro.core import provisioner as prov
    from repro.core import replication
    from repro.serving.simulator import simulate_full

    # the paper-faithful half split, same workloads: the historical
    # 5-vs-178 gap stays visible next to the queueing-aware numbers
    plan_h, hw_h = prov.provision_cheapest(specs, profiles_by_hw, hardware,
                                           config=cfg.replace(budget="half"))
    viol_h = prov.predicted_violations(plan_h, profiles_by_hw[hw_h.name],
                                       hw_h, budget="half")
    res_h = simulate_full(plan_h, mods, hw_h, duration_s=sim_duration_s,
                          seed=seed, backend=cfg.backend)
    row.update({
        "half_n_devices": plan_h.n_gpus,
        "half_cost_per_hour": round(plan_h.cost_per_hour(), 2),
        "half_predicted_violations": len(viol_h),
        "half_sim_violations": len(res_h.violations(sb)),
    })
    row["half_gap"] = (row["half_sim_violations"]
                       - row["half_predicted_violations"])
    # replica groups (replicate=True): workloads infeasible even
    # solo at r = 1.0 are split into rate-share replicas instead of
    # clamped — the honest full-device residual becomes servable
    plan_r, hw_r = prov.provision_cheapest(specs, profiles_by_hw, hardware,
                                           config=cfg.replace(replicate=True))
    viol_r = prov.predicted_violations(plan_r,
                                       profiles_by_hw[hw_r.name], hw_r)
    res_r = simulate_full(plan_r, mods, hw_r,
                          duration_s=sim_duration_s, seed=seed,
                          backend=cfg.backend)
    groups = replication.group_placements(plan_r.placements)
    row.update({
        "repl_n_devices": plan_r.n_gpus,
        "repl_cost_per_hour": round(plan_r.cost_per_hour(), 2),
        "repl_predicted_violations": len(viol_r),
        "repl_sim_violations": len(res_r.violations(sb)),
        "repl_split_workloads": sum(1 for g in groups.values()
                                    if len(g) > 1),
        "repl_n_replicas": sum(len(g) for g in groups.values()
                               if len(g) > 1),
    })
    row["repl_gap"] = (row["repl_sim_violations"]
                       - row["repl_predicted_violations"])
    # replica groups under the paper-faithful half split: the half
    # budget clamps MORE workloads to r = 1.0 than the queueing split,
    # so replication has more residual to recover — this is the pairing
    # that shows whether the 5-vs-178 gap is a budget artifact or a
    # single-instance ceiling artifact
    plan_hr, hw_hr = prov.provision_cheapest(
        specs, profiles_by_hw, hardware,
        config=cfg.replace(budget="half", replicate=True))
    viol_hr = prov.predicted_violations(plan_hr,
                                        profiles_by_hw[hw_hr.name], hw_hr,
                                        budget="half")
    res_hr = simulate_full(plan_hr, mods, hw_hr, duration_s=sim_duration_s,
                           seed=seed, backend=cfg.backend)
    groups_hr = replication.group_placements(plan_hr.placements)
    row.update({
        "half_repl_n_devices": plan_hr.n_gpus,
        "half_repl_cost_per_hour": round(plan_hr.cost_per_hour(), 2),
        "half_repl_predicted_violations": len(viol_hr),
        "half_repl_sim_violations": len(res_hr.violations(sb)),
        "half_repl_split_workloads": sum(1 for g in groups_hr.values()
                                         if len(g) > 1),
        "half_repl_n_replicas": sum(len(g) for g in groups_hr.values()
                                    if len(g) > 1),
    })
    row["half_repl_gap"] = (row["half_repl_sim_violations"]
                            - row["half_repl_predicted_violations"])


def run():
    """benchmarks.run integration: the quick tier only."""
    return sweep(SIZES_QUICK)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="m <= 100 only (per-PR CI smoke)")
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated m values (overrides --quick)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sim-duration", type=float, default=10.0,
                    help="simulated seconds for the full-cluster run")
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="planner + simulator hot-path backend")
    ap.add_argument("--out", type=str, default=DEFAULT_OUT)
    ap.add_argument("--check", action="store_true",
                    help="fail if any swept m in TARGETS exceeds its "
                         "(provision, full-simulation) wall-clock targets "
                         "(m=1000: %.0f s / %.0f s) or if its simulated "
                         "violations exceed 2x the predicted count"
                         % (TARGET_S, SIM_TARGET_S))
    ap.add_argument("--sim-floor", type=float, default=0.0,
                    help="fail if any full simulation ran below this many "
                         "events/sec (0 = off)")
    ap.add_argument("--gap-budget", type=int, default=-1,
                    help="fail if, for any m, the queueing-aware plan's "
                         "simulated violations exceed predicted + this "
                         "budget (negative = off)")
    args = ap.parse_args(argv)
    from benchmarks.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
    else:
        sizes = SIZES_QUICK if args.quick else SIZES_FULL
    if args.check and not any(m in TARGETS for m in sizes):
        print("error: --check requires a target size "
              f"({sorted(TARGETS)}) in the sweep (selected: {sizes})",
              file=sys.stderr)
        return 2
    rows = sweep(sizes, seed=args.seed, sim_duration_s=args.sim_duration,
                 backend=args.backend)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {args.out} ({len(rows)} rows)")

    status = 0
    for row in rows:
        if args.sim_floor and row["sim_events_per_s"] < args.sim_floor:
            print(f"# m={row['m']} simulator throughput "
                  f"{row['sim_events_per_s']:.0f} events/s < "
                  f"{args.sim_floor:.0f} floor (FAIL)")
            status = 1
        if args.gap_budget >= 0:
            gap_ok = (row["sim_violations"]
                      <= row["predicted_violations"] + args.gap_budget)
            half = ("; half split: "
                    f"{row['half_predicted_violations']} predicted / "
                    f"{row['half_sim_violations']} simulated"
                    if "half_sim_violations" in row else "")
            print(f"# m={row['m']} violation gap: "
                  f"predicted={row['predicted_violations']} "
                  f"simulated={row['sim_violations']} "
                  f"(budget +{args.gap_budget}, "
                  f"{'PASS' if gap_ok else 'FAIL'}{half})")
            if not gap_ok:
                status = 1
        if row["m"] in TARGETS:
            m = row["m"]
            target_s, sim_target_s = TARGETS[m]
            ok = row["wall_s"] < target_s
            print(f"# m={m} provisioning {row['wall_s']:.2f}s "
                  f"{'<' if ok else '>='} {target_s:.0f}s target "
                  f"({'PASS' if ok else 'FAIL'}"
                  f"{'; paper reports 4.61s' if m == 1000 else ''})")
            sim_ok = row["sim_wall_s"] < sim_target_s
            half = (f" (half split: {row['half_predicted_violations']}/"
                    f"{row['half_sim_violations']})"
                    if "half_sim_violations" in row else "")
            print(f"# m={m} full-cluster sim ({row['sim_devices']} devices, "
                  f"{row['sim_duration_s']:.0f}s sim) {row['sim_wall_s']:.2f}s "
                  f"{'<' if sim_ok else '>='} {sim_target_s:.0f}s target "
                  f"({'PASS' if sim_ok else 'FAIL'}); "
                  f"violations predicted={row['predicted_violations']} "
                  f"simulated={row['sim_violations']}{half}")
            # acceptance bound: simulated within 2x of predicted (the
            # half split sat at ~36x: 5 predicted vs 178 simulated)
            two_ok = (row["sim_violations"]
                      <= 2 * max(row["predicted_violations"], 1))
            print(f"# m={m} simulated/predicted "
                  f"{row['sim_violations']}/{row['predicted_violations']} "
                  f"within 2x bound ({'PASS' if two_ok else 'FAIL'})")
            if "repl_n_replicas" in row:
                print(f"# m={m} replica groups: "
                      f"{row['repl_split_workloads']} workloads split into "
                      f"{row['repl_n_replicas']} replicas; violations "
                      f"predicted={row['repl_predicted_violations']} "
                      f"simulated={row['repl_sim_violations']} "
                      f"({row['repl_n_devices']} devices, "
                      f"${row['repl_cost_per_hour']}/h)")
            if "half_repl_n_replicas" in row:
                print(f"# m={m} half-budget replica groups: "
                      f"{row['half_repl_split_workloads']} workloads split "
                      f"into {row['half_repl_n_replicas']} replicas; "
                      f"violations "
                      f"predicted={row['half_repl_predicted_violations']} "
                      f"simulated={row['half_repl_sim_violations']} "
                      f"({row['half_repl_n_devices']} devices, "
                      f"${row['half_repl_cost_per_hour']}/h)")
            if args.check and not (ok and sim_ok and two_ok):
                status = 1
    return status


if __name__ == "__main__":
    sys.exit(main())

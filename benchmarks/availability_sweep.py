"""Availability sweep: SLO violations and recovery time vs device fault
rate, controller-on vs controller-off (the robustness half of the
predictability story — docs/simulator.md, docs/control-plane.md).

The paper provisions for clean hardware; this sweep measures what its
plans are worth when hardware misbehaves.  For each cluster size m the
static queueing-aware plan is simulated under seeded fault schedules
(`repro.serving.faults`) twice per scenario — once uncontrolled (the
plan just eats the outage: backlog piles up, drains after restart) and
once with the closed-loop controller's health layer detecting failures
/ stragglers from live telemetry, quarantining the device, and
migrating victims to healthy homes.  Rows report whole-run per-request
violation rates, the simulator's downtime / lost-request / recovery
accounting (``SimResult.stats``), and the controller's health edit
counts (``migrate`` / ``readmit``).

Scenarios:
  fail-R     Poisson device failures at R per device-minute, fixed MTTR
             (`faults.random_failures`, one row per swept rate).  The
             availability gate: at EVERY positive rate the controlled
             run must beat the uncontrolled one on BOTH the mean
             per-request violation rate and mean recovery time —
             strictly, unless the seeded schedule happened to produce
             zero in-window failures (noted, skipped).
  straggler  a seeded fraction of devices serve every pass at a
             multiplier the performance model never sees
             (`faults.stragglers`).  The gate: the controller detects
             the stragglers from measured-vs-predicted residuals,
             migrates >= 1 victim off them, and every victim's tail
             (last TAIL_WINDOW_S of 1 s monitor windows) is back under
             its SLO.
  clean      no faults — the health layer must be a perfect no-op
             (zero reconfigurations, plan bit-identical), enforced by
             --check.  Guards against health false-positives rotting
             the no-drift guarantee.

Run:  PYTHONPATH=src python -m benchmarks.availability_sweep [--quick]
      --quick        m <= 100 only (CI per-PR smoke; uploads artifact)
      --sizes M,...  explicit cluster sizes
      --rates R,...  failure rates per device-minute (default 0.5,1,2)
      --seed N       fault-schedule / simulator seed
      --backend B    "numpy" (default) or "jax" simulator backend
      --sim-duration secs of simulated serving per run
      --check        exit non-zero if any gate above fails
      --telemetry    attach a `Telemetry` recorder to every controlled
                     run (results are byte-identical by contract —
                     docs/observability.md); writes per-scenario JSONL +
                     HTML artifacts next to --out, rows gain
                     ``telemetry_*`` columns (drift rows are the
                     straggler-detection signal), and --check gates the
                     event-log-vs-n_reconfigs reconciliation
      --out F        JSON row dump (default
                     benchmarks/out/availability_sweep_results.json)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SIZES_FULL = (100, 1000)
SIZES_QUICK = (100,)
RATES = (0.5, 1.0, 2.0)     # device failures per device-minute
MTTR_MS = 4000.0            # fixed repair time: > the ~3 s detection
                            # latency (1 s control period x fail_ticks),
                            # so an undetected outage is never shorter
                            # than a detected-and-migrated one
FAULT_HORIZON_FRAC = 0.6    # failures only in the first 60% of the run:
                            # every restart (+MTTR) lands in-window, so
                            # the uncontrolled recovery time is measured,
                            # not censored by the horizon
STRAGGLER_FRAC = 0.1
STRAGGLER_MULT = 2.5        # comfortably past the fleet-relative
                            # detection bar (health_straggler_factor)
TAIL_WINDOW_S = 3.0         # straggler gate: victim p99 over the last
                            # 3 s of 1 s monitor windows must meet SLO
DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out",
                           "availability_sweep_results.json")


def _mean_violation_rate(res, specs) -> float:
    import numpy as np
    rates = res.violation_rates({s.name: s for s in specs})
    return float(np.mean(list(rates.values())))


def _fault_stats(res) -> dict:
    """Fault accounting keys (absent from faults-off runs: zeros)."""
    return {k: res.stats.get(k, 0) for k in
            ("n_failures", "downtime_ms", "lost_requests",
             "n_recoveries", "recovery_mean_ms")}


def _victim_tail_ok(res, plan, specs, slow_gpus, horizon_s) -> tuple:
    """(ok, worst) — every straggler-victim base workload's monitor
    windows inside the last TAIL_WINDOW_S must sit at/below its SLO."""
    from repro.core import replication
    victims = {replication.base_name(p.workload.name)
               for p in plan.placements if p.gpu in slow_gpus}
    slo = {s.name: s.slo_ms for s in specs}
    ok, worst = True, 0.0
    for row in res.timeline:
        base = replication.base_name(row["workload"])
        if base not in victims or row["t_s"] < horizon_s - TAIL_WINDOW_S:
            continue
        if row["rps_1s"] <= 0.0:
            continue
        margin = row["p99_1s"] / slo[base]
        worst = max(worst, margin)
        if row["p99_1s"] > slo[base] + 1e-9:
            ok = False
    return ok, worst


def sweep(sizes, *, rates=RATES, seed: int = 0,
          sim_duration_s: float = 12.0, backend: str = "numpy",
          telemetry: bool = False, artifact_dir: str = None):
    from repro.core import provisioner as prov
    from repro.core.experiments import fitted_context
    from repro.core.types import PlannerConfig
    from repro.serving import faults
    from repro.serving.controller import Controller
    from repro.serving.simulator import simulate_full
    from repro.serving.telemetry import Telemetry
    from repro.serving.workload import models, synthetic_workloads

    from benchmarks import telemetry_report

    if telemetry:
        artifact_dir = artifact_dir or os.path.dirname(DEFAULT_OUT)
        os.makedirs(artifact_dir, exist_ok=True)

    cfg = PlannerConfig(backend=backend)
    ctx5 = fitted_context("tpu-v5e")
    ctx4 = fitted_context("tpu-v4")
    profiles_by_hw = {ctx5.hw.name: ctx5.profiles,
                      ctx4.hw.name: ctx4.profiles}
    hardware = [ctx5.hw, ctx4.hw]
    mods = models()
    horizon_ms = sim_duration_s * 1000.0

    rows = []
    for m in sizes:
        specs = synthetic_workloads(m, seed)
        plan, hw = prov.provision_cheapest(specs, profiles_by_hw, hardware,
                                           config=cfg)
        profiles = profiles_by_hw[hw.name]
        scenarios = [("clean", None)]
        scenarios += [
            (f"fail-{r:g}", faults.random_failures(
                plan.n_gpus, horizon_ms * FAULT_HORIZON_FRAC,
                rate_per_min=r, mttr_ms=MTTR_MS, seed=seed))
            for r in rates]
        scenarios.append(("straggler", faults.stragglers(
            plan.n_gpus, frac=STRAGGLER_FRAC, multiplier=STRAGGLER_MULT,
            seed=seed)))
        for scenario, fs in scenarios:
            kw = dict(duration_s=sim_duration_s, seed=seed, faults=fs,
                      backend=backend, record_timeline=True)
            t0 = time.perf_counter()
            res_u = simulate_full(plan, mods, hw, **kw)
            off_wall = time.perf_counter() - t0
            tel = Telemetry() if telemetry else None
            ctl = Controller(plan, profiles, hw,
                             config=cfg.replace(batch="joint"),
                             telemetry=tel)
            t0 = time.perf_counter()
            res_c = simulate_full(plan, mods, hw, adjust_fn=ctl,
                                  adjust_scope="cluster",
                                  adjust_period_s=1.0, telemetry=tel,
                                  **kw)
            on_wall = time.perf_counter() - t0
            row = {
                "bench": "availability_sweep", "m": m,
                "scenario": scenario, "backend": backend,
                "hardware": hw.name, "n_devices": plan.n_gpus,
                "n_failures": int(res_u.stats.get("n_failures", 0)),
                "off_violation_rate":
                    round(_mean_violation_rate(res_u, specs), 4),
                "on_violation_rate":
                    round(_mean_violation_rate(res_c, specs), 4),
                "off": {k: round(float(v), 2)
                        for k, v in _fault_stats(res_u).items()},
                "on": {k: round(float(v), 2)
                       for k, v in _fault_stats(res_c).items()},
                "n_reconfigs": int(res_c.stats["n_reconfigs"]),
                "n_migrations": sum(1 for e in ctl.edits
                                    if e.action == "migrate"),
                "n_readmits": sum(1 for e in ctl.edits
                                  if e.action == "readmit"),
                "n_edits": len(ctl.edits),
                "plan_identical": ctl.plan is plan,
                "off_sim_wall_s": round(off_wall, 3),
                "on_sim_wall_s": round(on_wall, 3),
                "sim_duration_s": sim_duration_s,
            }
            if scenario == "straggler":
                slow_gpus = set(fs.slow)
                ok, worst = _victim_tail_ok(res_c, plan, specs, slow_gpus,
                                            sim_duration_s)
                row["n_stragglers"] = len(slow_gpus)
                row["victim_tail_ok"] = ok
                row["victim_tail_worst"] = round(worst, 3)
            if tel is not None:
                stem = os.path.join(artifact_dir,
                                    f"telemetry_m{m}_{scenario}")
                tel.to_jsonl(stem + ".jsonl")
                with open(stem + ".html", "w") as f:
                    f.write(telemetry_report.render_html(
                        telemetry_report.load(stem + ".jsonl")))
                row.update({
                    "telemetry_events": tel.events.total,
                    "telemetry_drift_rows": tel.drift.total,
                    "telemetry_reconfig_ok":
                        tel.counters.get("reconfig_events", 0)
                        == int(res_c.stats["n_reconfigs"]),
                    "telemetry_log": stem + ".jsonl",
                })
            rows.append(row)
            print(",".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return rows


def run():
    """benchmarks.run integration: the quick tier only."""
    return sweep(SIZES_QUICK)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="m <= 100 only (per-PR CI smoke)")
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated m values (overrides --quick)")
    ap.add_argument("--rates", type=str, default=None,
                    help="comma-separated failure rates per device-minute "
                         f"(default: {','.join(str(r) for r in RATES)})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="simulator backend (default: numpy)")
    ap.add_argument("--sim-duration", type=float, default=12.0)
    ap.add_argument("--telemetry", action="store_true",
                    help="attach a Telemetry recorder to every "
                         "controlled run; writes per-scenario JSONL + "
                         "HTML artifacts next to --out")
    ap.add_argument("--out", type=str, default=DEFAULT_OUT)
    ap.add_argument("--check", action="store_true",
                    help="fail unless controller-on strictly beats "
                         "controller-off on violations AND recovery at "
                         "every positive fault rate, the straggler gate "
                         "holds, and the clean run is a no-op")
    args = ap.parse_args(argv)
    from benchmarks.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
    else:
        sizes = SIZES_QUICK if args.quick else SIZES_FULL
    rates = (tuple(float(r) for r in args.rates.split(","))
             if args.rates else RATES)
    rows = sweep(sizes, rates=rates, seed=args.seed,
                 sim_duration_s=args.sim_duration, backend=args.backend,
                 telemetry=args.telemetry,
                 artifact_dir=os.path.dirname(os.path.abspath(args.out)))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {args.out} ({len(rows)} rows)")

    status = 0
    for row in rows:
        tag = f"m={row['m']} {row['scenario']}"
        if "telemetry_events" in row:
            ok_rec = row["telemetry_reconfig_ok"]
            print(f"# {tag}: telemetry {row['telemetry_events']} events, "
                  f"{row['telemetry_drift_rows']} drift rows, event-log "
                  f"reconciliation {'PASS' if ok_rec else 'FAIL'}")
            if args.check and not ok_rec:
                status = 1
        if row["scenario"] == "clean":
            noop = (row["n_reconfigs"] == 0 and row["n_edits"] == 0
                    and row["plan_identical"])
            print(f"# {tag}: health no-op check "
                  f"({'PASS' if noop else 'FAIL'}: "
                  f"{row['n_reconfigs']} reconfigs, {row['n_edits']} "
                  f"edits, plan_identical={row['plan_identical']})")
            if args.check and not noop:
                status = 1
        elif row["scenario"].startswith("fail-"):
            if row["n_failures"] == 0:
                print(f"# {tag}: no in-window failures at this seed — "
                      f"dominance gate skipped")
                continue
            ok = (row["on_violation_rate"] < row["off_violation_rate"]
                  and row["on"]["recovery_mean_ms"]
                  < row["off"]["recovery_mean_ms"])
            print(f"# {tag}: {row['n_failures']} failures; violation "
                  f"rate {row['off_violation_rate']:.4f} -> "
                  f"{row['on_violation_rate']:.4f}, recovery "
                  f"{row['off']['recovery_mean_ms']:.0f}ms -> "
                  f"{row['on']['recovery_mean_ms']:.0f}ms, "
                  f"{row['n_migrations']} migrations "
                  f"({'PASS' if ok else 'FAIL'})")
            if args.check and not ok:
                status = 1
        elif row["scenario"] == "straggler":
            ok = row["n_migrations"] >= 1 and row["victim_tail_ok"]
            print(f"# {tag}: {row['n_stragglers']} stragglers; "
                  f"{row['n_migrations']} migrations, victim tail "
                  f"p99/SLO worst {row['victim_tail_worst']:.2f} "
                  f"({'PASS' if ok else 'FAIL'})")
            if args.check and not ok:
                status = 1
    return status


if __name__ == "__main__":
    sys.exit(main())

"""Dynamic-load sweep: static plan vs closed-loop controller over the
trace suite (the runtime half of the paper, Sec. 4.2/4.4).

For each cluster size m, the static queueing-aware plan is simulated
under every trace scenario twice — once as-is and once with the online
controller (`repro.serving.controller.Controller`) driving the
simulator's cluster-scoped ``adjust_fn`` hook — and the rows report
simulated SLO violations (rate targets corrected by each trace's
time-weighted mean scale), reconfiguration counts, controller wall-clock
overhead (``reconfig_latency_ms``, the paper's Sec. 5.5 number), final
plan cost, and simulator throughput.  Since the controller gained
replica scale-out (``split_workload``/``merge_workload`` reconciliation,
docs/control-plane.md) the rows also report ``n_splits`` / ``n_merges``
edit counts and the final plan's replica footprint
(``split_workloads`` / ``n_replicas``) — the r = 1.0 ceiling that used
to cap every diurnal workload at one device's throughput is gone, which
is what the controlled-violations column measures.

Scenarios:
  no_drift   constant-rate control case — the controller must do NOTHING
             (zero reconfigurations, plan bit-identical); enforced by
             --check.
  diurnal    2x smooth ramp over the horizon (deterministic arrivals) —
             the headline closed-loop case: the static plan degrades,
             the controlled plan must violate strictly less.
  spike      2.5x flash crowd for 2 s mid-run (Poisson arrivals) — a
             reactive controller cannot un-blow a short spike's p99, but
             must never be WORSE and drains the backlog faster (the
             per-request violation-rate column shows the win).
  churn      10% of workloads depart / 10% arrive mid-run — exercises
             remove_workload / add_workload reconciliation.
  overload   demand ramps to ~2x an immovable fleet: the plan is
             provisioned normally, then the controller runs with
             ``max_devices`` frozen at that fleet size while the low
             tier (3 of every 4 workloads, priority 0) ramps to
             OVERLOAD_PEAK_LO and the high tier (priority 1) to
             OVERLOAD_PEAK_HI.  The admission layer must degrade
             gracefully: preempt/brownout/shed the low tier, keep the
             high tier's whole-run p99 inside its SLO.  --check gates
             zero high-tier violations plus bounded low-tier shed-rate
             and brownout depth (both reported in the JSON artifact).

The reconciler's Theorem-1 probes are memoized across edits
(`provisioner.ProbeCache`): repeat (spec, budget) probes — the dominant
cost of a reconciliation burst at large m — are O(1) after their first
miss.  Rows report the cache's ``probe_hits`` / ``probe_misses``, and
--check enforces the m=1000 diurnal edit-overhead bound
(``EDIT_TARGET_MS``) the cache is responsible for.  ``--backend jax``
threads the jitted planner + simulator hot paths through the run
(m=10,000 rides the informational CI tier this way).

Run:  PYTHONPATH=src python -m benchmarks.dynamic_sweep [--quick] [--check]
      --quick        m <= 100 only (CI per-PR smoke; uploads artifact)
      --sizes M,...  explicit cluster sizes
      --scenarios s, explicit scenario subset (default: all four)
      --backend B    "numpy" (default) or "jax" planner/simulator backend
      --check        exit non-zero if any scenario's controlled
                     violations exceed the static plan's, if a no-drift
                     run reconfigures at all (or its plan is not
                     bit-identical), if an overload run violates a
                     high-tier SLO or exceeds the low-tier shed/brownout
                     bounds, if an m=1000 controlled sim exceeds the
                     scale_sweep wall-clock bound, or if the m=1000
                     diurnal controller overhead exceeds EDIT_TARGET_MS
      --sim-floor N  exit non-zero if any sim ran below N events/s

--telemetry re-runs each controlled scenario with a `Telemetry`
recorder attached (`repro.serving.telemetry`, docs/observability.md) to
a FRESH controller — the primary controlled run stays telemetry-off so
its wall clock remains the no-observability baseline.  Per scenario it
writes a JSONL event/timeline log plus a self-contained HTML report
(rendered via `benchmarks.telemetry_report`) next to --out, and the
row gains ``telemetry_*`` columns.  Under --check the telemetry run
must (a) reconcile its overflow-immune ``reconfig_events`` counter
against the sim's ``n_reconfigs`` stat — every placement mutation
appears exactly once in the event log — and (b) at m=1000 keep the
telemetry-on wall within TELEMETRY_OVERHEAD_CAP (10%) of the
telemetry-off controlled run.

Writes a JSON row dump (default benchmarks/out/dynamic_sweep_results.json
— gitignored; CI uploads it as an artifact).
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
SCENARIOS = ("no_drift", "diurnal", "spike", "churn", "overload")
OVERLOAD_HI_EVERY = 4     # every 4th workload is priority 1 (high tier)
OVERLOAD_PEAK_LO = 3.0    # low-tier diurnal peak ...
OVERLOAD_PEAK_HI = 1.3    # ... high-tier peak: aggregate demand ~2x fleet
                          # (the low tier drives the overload; the high
                          # tier's gentle ramp is what the admission layer
                          # must keep whole)
OVERLOAD_SHED_CAP = 0.6   # --check: low-tier shed-rate must stay below
OVERLOAD_BROWNOUT_FRAC = 1.0  # --check: max brownout depth / low-tier count
OVERLOAD_RESERVE = 1.4    # high-tier capacity reservation factor at
                          # provisioning time (> OVERLOAD_PEAK_HI): near
                          # the r = 1.0 ceiling the planner's queueing
                          # model understates rho -> 1 delay, so the
                          # reservation must push ceiling placements into
                          # configurations with real simulated headroom
SIM_TARGET_S = 60.0      # same bound as scale_sweep's m=1000 full sim
EDIT_TARGET_MS = 10000.0  # m=1000 diurnal controller overhead bound:
                          # ~13 s before PR 6 (ProbeCache + vectorized
                          # probe path), ~7 s after
TELEMETRY_OVERHEAD_CAP = 0.10  # --check: m=1000 telemetry-on wall may
                               # exceed telemetry-off by at most 10%
DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out",
                           "dynamic_sweep_results.json")


def _make_trace(scenario: str, names, horizon_ms: float, seed: int):
    from repro.serving import traces
    if scenario == "no_drift":
        return traces.constant(names, horizon_ms), False
    if scenario == "diurnal":
        return traces.diurnal(names, horizon_ms, peak=2.0), False
    if scenario == "spike":
        return traces.step_spike(names, horizon_ms,
                                 at_ms=0.4 * horizon_ms,
                                 duration_ms=0.2 * horizon_ms,
                                 scale=2.5), True
    if scenario == "churn":
        return traces.random_churn(names, horizon_ms, depart_frac=0.1,
                                   arrive_frac=0.1, seed=seed), False
    if scenario == "overload":
        # Priority-split ramp: aggregate demand peaks at ~2x the capped
        # fleet, but the high tier only ramps to OVERLOAD_PEAK_HI so the
        # admission layer can keep it whole by degrading the low tier.
        hi = [n for i, n in enumerate(names) if i % OVERLOAD_HI_EVERY == 0]
        lo = [n for i, n in enumerate(names) if i % OVERLOAD_HI_EVERY != 0]
        t_lo = traces.diurnal(lo, horizon_ms, peak=OVERLOAD_PEAK_LO)
        t_hi = traces.diurnal(hi, horizon_ms, peak=OVERLOAD_PEAK_HI)
        return traces.Trace(edges=t_lo.edges,
                            scales={**t_lo.scales, **t_hi.scales}), False
    raise ValueError(f"unknown scenario {scenario!r}")


def _overload_specs(specs):
    """Same workloads with every OVERLOAD_HI_EVERY-th marked priority 1
    (matching `_make_trace`'s tier split); the rest stay priority 0."""
    import dataclasses
    return [dataclasses.replace(s, priority=1)
            if i % OVERLOAD_HI_EVERY == 0 else s
            for i, s in enumerate(specs)]


def _overload_plan(o_specs, profiles_by_hw, hardware, cfg):
    """Provision the overload fleet with the high tier's rate inflated
    by OVERLOAD_RESERVE (its capacity reservation — what a priority
    tier buys), then rewrite the placements' spec rates back to the
    true base rates so arrivals and controller targets see real
    demand.  The fleet is then frozen at this size: the low tier's
    ramp must be absorbed by admission control, and the gate checks it
    never steals the high tier's reserved headroom (zero whole-run p99
    violations there).  The fleet is pinned to the FIRST (commodity)
    hardware tier: a roomier accelerator would leave enough slack that
    the cap never binds and the scenario measures nothing."""
    import dataclasses
    from repro.core import provisioner as prov
    prov_specs = [dataclasses.replace(s, rate_rps=s.rate_rps
                                      * OVERLOAD_RESERVE)
                  if s.priority > 0 else s for s in o_specs]
    plan, hw = prov.provision_cheapest(prov_specs, profiles_by_hw,
                                       hardware[:1],
                                       config=cfg.replace(replicate=True))
    placements = [
        dataclasses.replace(p, workload=dataclasses.replace(
            p.workload, rate_rps=p.workload.rate_rps / OVERLOAD_RESERVE))
        if p.workload.priority > 0 else p
        for p in plan.placements]
    return dataclasses.replace(plan, placements=placements), hw


def _scaled_specs(specs, tr, horizon_ms):
    """Specs with each rate replaced by its trace-mean expectation, so
    `SimResult.violations`' 95%-of-target rate check measures against
    what the trace actually offered (one violation definition, reused)."""
    import dataclasses
    return {s.name: dataclasses.replace(
        s, rate_rps=s.rate_rps * tr.mean_scale(s.name, horizon_ms))
        for s in specs}


def _violations(res, specs, tr, horizon_ms):
    return res.violations(_scaled_specs(specs, tr, horizon_ms))


def _mean_violation_rate(res, specs) -> float:
    import numpy as np
    rates = res.violation_rates({s.name: s for s in specs})
    return float(np.mean(list(rates.values())))


def sweep(sizes, scenarios, *, seed: int = 0, sim_duration_s: float = 10.0,
          backend: str = "numpy", telemetry: bool = False,
          artifact_dir: str = None):
    from repro.core import provisioner as prov
    from repro.core.experiments import fitted_context
    from repro.core.types import PlannerConfig
    from repro.serving.controller import Controller, ControllerConfig
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
        names = [s.name for s in specs]
        t0 = time.perf_counter()
        plan, hw = prov.provision_cheapest(specs, profiles_by_hw, hardware,
                                           config=cfg)
        prov_wall = time.perf_counter() - t0
        profiles = profiles_by_hw[hw.name]
        for scenario in scenarios:
            o_specs, o_plan, o_hw = specs, plan, hw
            o_profiles, o_prov_wall, ctl_cfg = profiles, prov_wall, None
            if scenario == "overload":
                # Re-provision with priority annotations, then FREEZE the
                # fleet at the provisioned size: the controller may not
                # buy its way out of the 2x ramp.
                o_specs = _overload_specs(specs)
                t0 = time.perf_counter()
                o_plan, o_hw = _overload_plan(o_specs, profiles_by_hw,
                                              hardware, cfg)
                o_prov_wall = time.perf_counter() - t0
                o_profiles = profiles_by_hw[o_hw.name]
                # aggressive resize headroom: under overload the
                # controller should ask EARLY for the capacity it must
                # claw back from the low tier (demand never exceeds the
                # high tier's reservation, so a refused edit is safe)
                ctl_cfg = ControllerConfig(max_devices=o_plan.n_gpus,
                                           headroom=0.35)
            tr, poisson = _make_trace(scenario, names, horizon_ms, seed)
            t0 = time.perf_counter()
            res_s = simulate_full(o_plan, mods, o_hw,
                                  duration_s=sim_duration_s,
                                  seed=seed, poisson=poisson, trace=tr,
                                  backend=backend)
            static_wall = time.perf_counter() - t0
            ctl = Controller(o_plan, o_profiles, o_hw,
                             config=cfg.replace(batch="joint"),
                             cfg=ctl_cfg)
            t0 = time.perf_counter()
            res_c = simulate_full(o_plan, mods, o_hw,
                                  duration_s=sim_duration_s,
                                  seed=seed, poisson=poisson, trace=tr,
                                  adjust_fn=ctl, adjust_scope="cluster",
                                  adjust_period_s=1.0, backend=backend)
            ctl_wall = time.perf_counter() - t0
            from repro.core import replication
            groups = replication.group_placements(ctl.plan.placements)
            row = {
                "bench": "dynamic_sweep", "m": m, "scenario": scenario,
                "backend": backend,
                "hardware": o_hw.name, "n_devices": o_plan.n_gpus,
                "provision_wall_s": round(o_prov_wall, 3),
                "static_violations": len(_violations(res_s, o_specs, tr,
                                                     horizon_ms)),
                "controlled_violations": len(_violations(res_c, o_specs, tr,
                                                         horizon_ms)),
                "static_violation_rate":
                    round(_mean_violation_rate(res_s, o_specs), 4),
                "controlled_violation_rate":
                    round(_mean_violation_rate(res_c, o_specs), 4),
                "n_reconfigs": int(res_c.stats["n_reconfigs"]),
                "n_edits": len(ctl.edits),
                "n_splits": sum(1 for e in ctl.edits
                                if e.action == "split"),
                "n_merges": sum(1 for e in ctl.edits
                                if e.action == "merge"),
                "split_workloads": sum(1 for g in groups.values()
                                       if len(g) > 1),
                "n_replicas": sum(len(g) for g in groups.values()
                                  if len(g) > 1),
                "reconfig_latency_ms":
                    round(res_c.stats["reconfig_latency_ms"], 1),
                "probe_hits": ctl.reconciler.probes.hits,
                "probe_misses": ctl.reconciler.probes.misses,
                "plan_identical": ctl.plan is o_plan,
                "static_cost_per_hour": round(o_plan.cost_per_hour(), 2),
                "final_cost_per_hour":
                    round(ctl.plan.cost_per_hour(), 2),
                "mean_cost_per_hour": round(
                    sum(c for _, c in ctl.costs)
                    / max(len(ctl.costs), 1), 2),
                "static_sim_wall_s": round(static_wall, 3),
                "controlled_sim_wall_s": round(ctl_wall, 3),
                "sim_events_per_s": round(res_c.stats["events_per_s"]),
                "sim_duration_s": sim_duration_s,
            }
            if scenario == "overload":
                viol = set(_violations(res_c, o_specs, tr, horizon_ms))
                hi = {s.name for s in o_specs if s.priority > 0}
                st = res_c.stats
                row.update({
                    "max_devices": o_plan.n_gpus,
                    "hi_workloads": len(hi),
                    "lo_workloads": len(o_specs) - len(hi),
                    "hi_violations": len(viol & hi),
                    "lo_violations": len(viol - hi),
                    "shed_requests": int(st.get("shed_requests", 0)),
                    "lo_shed_rate": round(st.get("class0_shed_rate",
                                                 0.0), 4),
                    "hi_shed_rate": round(st.get("class1_shed_rate",
                                                 0.0), 4),
                    "hi_violation_rate":
                        round(st.get("class1_violation_rate", 0.0), 4),
                    "brownout_depth_max":
                        int(st.get("brownout_depth_max", 0)),
                    "brownout_ticks": int(st.get("brownout_ticks", 0)),
                    "admission_preemptions":
                        int(st.get("admission_preemptions", 0)),
                    "admission_shed_workloads":
                        int(st.get("admission_shed_workloads", 0)),
                    "admission_readmits":
                        int(st.get("admission_readmits", 0)),
                })
            if scenario in ("no_drift", "spike"):
                # Third run: same plan/trace with the predictive tier on
                # (forecast-armed Sec. 4.2 shadows, docs/control-plane.md).
                # The spike gate wants the forecast-on whole-run violation
                # rate strictly below the reactive controller's — the
                # reactive loop can only drain a 2 s flash crowd's backlog
                # after the fact, while the forecaster pre-sizes and arms
                # standby r before the step lands.  no_drift must stay a
                # no-op: constant-rate Poisson noise never fires the
                # forecaster (zero forecast/shadow_arm events, plan
                # bit-identical).
                import dataclasses
                fc_cfg = (dataclasses.replace(ctl_cfg, forecast=True)
                          if ctl_cfg is not None
                          else ControllerConfig(forecast=True))
                ctl_f = Controller(o_plan, o_profiles, o_hw,
                                   config=cfg.replace(batch="joint"),
                                   cfg=fc_cfg)
                t0 = time.perf_counter()
                res_f = simulate_full(o_plan, mods, o_hw,
                                      duration_s=sim_duration_s,
                                      seed=seed, poisson=poisson, trace=tr,
                                      adjust_fn=ctl_f,
                                      adjust_scope="cluster",
                                      adjust_period_s=1.0, backend=backend)
                fc_wall = time.perf_counter() - t0
                row.update({
                    "forecast_violations": len(_violations(res_f, o_specs,
                                                           tr, horizon_ms)),
                    "forecast_violation_rate":
                        round(_mean_violation_rate(res_f, o_specs), 4),
                    "forecast_n_reconfigs": int(res_f.stats["n_reconfigs"]),
                    "n_forecast_events": sum(1 for e in ctl_f.edits
                                             if e.action == "forecast"),
                    "n_shadow_arms": sum(1 for e in ctl_f.edits
                                         if e.action == "shadow_arm"),
                    "forecast_plan_identical": ctl_f.plan is o_plan,
                    "forecast_sim_wall_s": round(fc_wall, 3),
                })
            if telemetry:
                # Fresh controller + recorder: the primary controlled
                # run above stays telemetry-off, so ctl_wall is the
                # baseline the overhead gate compares against.
                tel = Telemetry()
                ctl_t = Controller(o_plan, o_profiles, o_hw,
                                   config=cfg.replace(batch="joint"),
                                   cfg=ctl_cfg, telemetry=tel)
                t0 = time.perf_counter()
                res_t = simulate_full(o_plan, mods, o_hw,
                                      duration_s=sim_duration_s,
                                      seed=seed, poisson=poisson, trace=tr,
                                      adjust_fn=ctl_t,
                                      adjust_scope="cluster",
                                      adjust_period_s=1.0, backend=backend,
                                      telemetry=tel)
                tel_wall = time.perf_counter() - t0
                stem = os.path.join(artifact_dir,
                                    f"telemetry_m{m}_{scenario}")
                tel.to_jsonl(stem + ".jsonl")
                with open(stem + ".html", "w") as f:
                    f.write(telemetry_report.render_html(
                        telemetry_report.load(stem + ".jsonl")))
                row.update({
                    "telemetry_wall_s": round(tel_wall, 3),
                    "telemetry_overhead": round(
                        (tel_wall - ctl_wall) / max(ctl_wall, 1e-9), 4),
                    "telemetry_events": tel.events.total,
                    "telemetry_reconfig_ok":
                        tel.counters.get("reconfig_events", 0)
                        == int(res_t.stats["n_reconfigs"]),
                    "telemetry_log": stem + ".jsonl",
                })
            rows.append(row)
            print(",".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return rows


def run():
    """benchmarks.run integration: the quick tier only."""
    return sweep(SIZES_QUICK, SCENARIOS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="m <= 100 only (per-PR CI smoke)")
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated m values (overrides --quick)")
    ap.add_argument("--scenarios", type=str, default=None,
                    help="comma-separated scenario subset "
                         f"(default: {','.join(SCENARIOS)})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="planner/simulator backend (default: numpy)")
    ap.add_argument("--sim-duration", type=float, default=10.0)
    ap.add_argument("--out", type=str, default=DEFAULT_OUT)
    ap.add_argument("--telemetry", action="store_true",
                    help="re-run each controlled scenario with a "
                         "Telemetry recorder attached; writes per-"
                         "scenario JSONL + HTML artifacts next to --out "
                         "and (with --check) gates the event-log "
                         "reconciliation and the m=1000 overhead cap")
    ap.add_argument("--check", action="store_true",
                    help="fail on controlled > static violations, on any "
                         "no-drift reconfiguration, or on an m=1000 "
                         f"controlled sim over {SIM_TARGET_S:.0f} s")
    ap.add_argument("--sim-floor", type=float, default=0.0,
                    help="fail if any sim ran below this many events/s "
                         "(0 = off)")
    args = ap.parse_args(argv)
    from benchmarks.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
    else:
        sizes = SIZES_QUICK if args.quick else SIZES_FULL
    scenarios = (tuple(args.scenarios.split(",")) if args.scenarios
                 else SCENARIOS)
    rows = sweep(sizes, scenarios, seed=args.seed,
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
        ok = row["controlled_violations"] <= row["static_violations"]
        print(f"# {tag}: static={row['static_violations']} "
              f"controlled={row['controlled_violations']} "
              f"(rates {row['static_violation_rate']:.3f} -> "
              f"{row['controlled_violation_rate']:.3f}; "
              f"{row['n_reconfigs']} reconfigs, "
              f"{row['n_splits']} splits/{row['n_merges']} merges -> "
              f"{row['n_replicas']} replicas, "
              f"{row['reconfig_latency_ms']:.0f} ms overhead; "
              f"{'PASS' if ok else 'FAIL'})")
        if args.check and not ok:
            status = 1
        if row["scenario"] == "no_drift":
            noop = row["n_reconfigs"] == 0 and row["plan_identical"]
            print(f"# {tag}: no-op check "
                  f"({'PASS' if noop else 'FAIL'}: "
                  f"{row['n_reconfigs']} reconfigs, plan_identical="
                  f"{row['plan_identical']})")
            if args.check and not noop:
                status = 1
        if row["scenario"] == "overload":
            bo_cap = OVERLOAD_BROWNOUT_FRAC * row["lo_workloads"]
            ok_hi = row["hi_violations"] == 0
            ok_shed = row["lo_shed_rate"] <= OVERLOAD_SHED_CAP
            ok_bo = row["brownout_depth_max"] <= bo_cap
            print(f"# {tag}: overload gates hi_violations="
                  f"{row['hi_violations']} (want 0), lo_shed_rate="
                  f"{row['lo_shed_rate']:.3f} (cap {OVERLOAD_SHED_CAP}), "
                  f"brownout_depth_max={row['brownout_depth_max']} "
                  f"(cap {bo_cap:.0f}); {row['shed_requests']} shed, "
                  f"{row['admission_preemptions']} preemptions, "
                  f"{row['admission_readmits']} readmits "
                  f"({'PASS' if ok_hi and ok_shed and ok_bo else 'FAIL'})")
            if args.check and not (ok_hi and ok_shed and ok_bo):
                status = 1
        if "forecast_violations" in row:
            if row["scenario"] == "spike":
                ok_f = (row["forecast_violation_rate"]
                        < row["controlled_violation_rate"]
                        and row["forecast_violations"]
                        <= row["controlled_violations"])
                print(f"# {tag}: forecast gate rate "
                      f"{row['forecast_violation_rate']:.3f} "
                      f"{'<' if ok_f else '!<'} reactive "
                      f"{row['controlled_violation_rate']:.3f} "
                      f"(violations {row['controlled_violations']} -> "
                      f"{row['forecast_violations']}; "
                      f"{row['n_forecast_events']} forecast edits, "
                      f"{row['n_shadow_arms']} shadow arms; "
                      f"{'PASS' if ok_f else 'FAIL'})")
            else:  # no_drift: the forecaster must not fire on Poisson noise
                ok_f = (row["forecast_n_reconfigs"] == 0
                        and row["forecast_plan_identical"]
                        and row["n_forecast_events"] == 0
                        and row["n_shadow_arms"] == 0)
                print(f"# {tag}: forecast no-op check "
                      f"({'PASS' if ok_f else 'FAIL'}: "
                      f"{row['forecast_n_reconfigs']} reconfigs, "
                      f"{row['n_forecast_events']} forecast edits, "
                      f"plan_identical={row['forecast_plan_identical']})")
            if args.check and not ok_f:
                status = 1
        if "telemetry_events" in row:
            ok_rec = row["telemetry_reconfig_ok"]
            print(f"# {tag}: telemetry {row['telemetry_events']} events, "
                  f"wall {row['telemetry_wall_s']:.2f}s "
                  f"({row['telemetry_overhead']:+.1%} vs off), event-log "
                  f"reconciliation {'PASS' if ok_rec else 'FAIL'}")
            if args.check and not ok_rec:
                status = 1
            if row["m"] == 1000:
                ok_ovh = row["telemetry_overhead"] <= TELEMETRY_OVERHEAD_CAP
                print(f"# {tag}: telemetry overhead "
                      f"{row['telemetry_overhead']:.1%} "
                      f"{'<=' if ok_ovh else '>'} "
                      f"{TELEMETRY_OVERHEAD_CAP:.0%} cap "
                      f"({'PASS' if ok_ovh else 'FAIL'})")
                if args.check and not ok_ovh:
                    status = 1
        if row["m"] == 1000:
            fast = row["controlled_sim_wall_s"] < SIM_TARGET_S
            print(f"# {tag}: controlled full sim "
                  f"{row['controlled_sim_wall_s']:.2f}s "
                  f"{'<' if fast else '>='} {SIM_TARGET_S:.0f}s "
                  f"({'PASS' if fast else 'FAIL'})")
            if args.check and not fast:
                status = 1
            if row["scenario"] == "diurnal":
                cheap = row["reconfig_latency_ms"] < EDIT_TARGET_MS
                print(f"# {tag}: controller edit overhead "
                      f"{row['reconfig_latency_ms']:.0f}ms "
                      f"{'<' if cheap else '>='} {EDIT_TARGET_MS:.0f}ms "
                      f"(probe cache {row['probe_hits']} hits / "
                      f"{row['probe_misses']} misses; "
                      f"{'PASS' if cheap else 'FAIL'})")
                if args.check and not cheap:
                    status = 1
        if args.sim_floor and row["sim_events_per_s"] < args.sim_floor:
            print(f"# {tag}: throughput {row['sim_events_per_s']:.0f} "
                  f"events/s < {args.sim_floor:.0f} floor (FAIL)")
            status = 1
    return status


if __name__ == "__main__":
    sys.exit(main())

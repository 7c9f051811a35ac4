"""Online control plane: estimators, hysteresis, reconciliation, and the
closed loop against the simulator.

Property tests run under hypothesis when available and skip cleanly on
bare environments (`tests._hypothesis_stub`), mirroring
`test_queueing.py`; the unit tests alongside always run.

The closed-loop tier pins the PR's acceptance behavior at m=100:

  * no-drift runs (deterministic AND Poisson noise-only) perform ZERO
    reconfigurations and leave the plan bit-identical — the controlled
    simulation's latency streams equal the uncontrolled run's exactly;
  * under a 2x diurnal ramp the controlled plan's simulated violations
    come in strictly below the static queueing plan's;
  * a reconfiguring controlled run is byte-identical across simulator
    engines (fresh controllers per engine), including `n_reconfigs`.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:      # bare env: property tests skip, unit tests run
    from tests._hypothesis_stub import given, settings, st

from repro.core import provisioner as prov
from repro.core.experiments import fitted_context
from repro.core.queueing import QUEUEING
from repro.serving import traces
from repro.serving.controller import (ArrivalEstimator, Controller,
                                      ControllerConfig, Reconciler)
from repro.serving.simulator import simulate_full, simulate_plan
from repro.serving.workload import models, synthetic_workloads, \
    twelve_workloads

WINDOW_MS = 1000.0


def _poisson_window(rng, rate_rps, window_ms=WINDOW_MS, t0=0.0):
    n = rng.poisson(rate_rps * window_ms / 1000.0)
    return t0 + np.sort(rng.uniform(0.0, window_ms, size=n))


def _det_window(rate_rps, window_ms=WINDOW_MS, t0=0.0):
    period = 1000.0 / rate_rps
    return t0 + np.arange(period / 2.0, window_ms, period)


# ---------------------------------------------------------------------------
# Estimators
# ---------------------------------------------------------------------------

def test_ewma_rate_converges_on_constant_trace():
    est = ArrivalEstimator(50.0)           # prior far from truth
    for k in range(20):
        est.observe(_det_window(120.0, t0=k * WINDOW_MS), WINDOW_MS)
    assert est.rate_rps == pytest.approx(120.0, rel=0.02)
    assert abs(est.trend_rps) < 2.0
    # burstiness of an evenly spaced stream ~ 0
    assert est.cv2 < 0.05


def test_burstiness_poisson_near_one():
    rng = np.random.default_rng(0)
    est = ArrivalEstimator(200.0)
    for k in range(30):
        est.observe(_poisson_window(rng, 200.0, t0=k * WINDOW_MS),
                    WINDOW_MS)
    assert 0.5 < est.cv2 < 1.8
    assert est.rate_rps == pytest.approx(200.0, rel=0.15)


def test_burstiness_spike_train_much_greater_than_one():
    """Bursts of back-to-back arrivals separated by long silences: the
    CV^2 estimator must see the inter-burst gaps (chained across
    windows) and report >> 1."""
    est = ArrivalEstimator(40.0)
    for k in range(12):
        t0 = k * WINDOW_MS
        burst = t0 + 100.0 + np.arange(40) * 1.0      # 40 reqs in 40 ms
        est.observe(burst, WINDOW_MS)
    assert est.cv2 > 4.0


def test_burstiness_accumulates_for_low_rate_workloads():
    """A 3 rps workload yields fewer than min_gap_obs gaps per window;
    gaps must buffer across windows so cv2 still updates eventually."""
    est = ArrivalEstimator(3.0)
    for k in range(10):
        est.observe(_det_window(3.0, t0=k * WINDOW_MS), WINDOW_MS)
    assert est.n_gaps > 0
    assert est.cv2 < 0.1          # evenly spaced: near-deterministic


def test_estimator_empty_windows_accumulate():
    est = ArrivalEstimator(80.0)
    est.observe(_det_window(80.0), WINDOW_MS)
    assert est.empty_ms == 0.0
    for _ in range(3):
        est.observe(np.empty(0), WINDOW_MS)
    assert est.empty_ms == pytest.approx(3 * WINDOW_MS)
    est.observe(_det_window(80.0, t0=4 * WINDOW_MS), WINDOW_MS)
    assert est.empty_ms == 0.0


@settings(max_examples=30, deadline=None)
@given(rate=st.floats(10.0, 400.0), prior=st.floats(5.0, 500.0))
def test_ewma_convergence_randomized(rate, prior):
    est = ArrivalEstimator(prior)
    for k in range(25):
        est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
    assert est.rate_rps == pytest.approx(rate, rel=0.05)
    assert est.cv2 < 0.1


@settings(max_examples=20, deadline=None)
@given(rate=st.floats(30.0, 300.0), seed=st.integers(0, 50))
def test_burstiness_poisson_randomized(rate, seed):
    rng = np.random.default_rng(seed)
    est = ArrivalEstimator(rate)
    for k in range(30):
        est.observe(_poisson_window(rng, rate, t0=k * WINDOW_MS), WINDOW_MS)
    assert 0.3 < est.cv2 < 2.5


# ---------------------------------------------------------------------------
# Hysteresis / reconciler (no simulator involved)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ctx12():
    ctx = fitted_context()
    plan = prov.provision(twelve_workloads(), ctx.profiles, ctx.hw)
    return ctx, plan


def _estimators(plan, cfg=None):
    return {p.workload.name: ArrivalEstimator(p.workload.rate_rps, cfg)
            for p in plan.placements}


def test_hysteresis_quiet_on_noise_only_input(ctx12):
    """Poisson windows at the provisioned rates, many ticks, several
    seeds: the reconciler must never fire (oscillation prevention)."""
    ctx, plan = ctx12
    for seed in range(3):
        rng = np.random.default_rng(seed)
        rec = Reconciler(plan, ctx.profiles, ctx.hw)
        ests = _estimators(plan)
        for k in range(25):
            for name, est in ests.items():
                rate = rec.targets[name].rate_rps
                est.observe(_poisson_window(rng, rate, t0=k * WINDOW_MS),
                            WINDOW_MS)
            assert not rec.reconcile((k + 1.0), ests)
        assert rec.edits == [] and rec.plan is plan


def test_reconciler_fires_on_sustained_updrift(ctx12):
    """A sustained up-drift reconfigures: either a same-count resize or
    — when the drifted rate is infeasible even solo on a full device —
    a replica split (the allocated rate shares then sum to the new
    target instead of clamping at r = 1.0)."""
    from repro.core import replication
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw)
    ests = _estimators(plan)
    name = plan.placements[0].workload.name
    base = rec.targets[name].rate_rps
    changed = False
    for k in range(6):
        for n, est in ests.items():
            rate = rec.targets[n].rate_rps * (1.6 if n == name else 1.0)
            est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
        changed |= rec.reconcile(k + 1.0, ests)
    assert changed
    acts = [e for e in rec.edits if e.workload == name]
    assert acts and acts[0].action in ("resize", "split")
    assert rec.targets[name].rate_rps > base * 1.3
    group = replication.group_placements(rec.plan.placements)[name]
    assert len(group) == acts[-1].replicas
    assert sum(p.workload.rate_rps for p in group) == \
        pytest.approx(rec.targets[name].rate_rps)


def test_reconciler_departure_and_rearrival(ctx12):
    ctx, plan = ctx12
    cfg = ControllerConfig()
    rec = Reconciler(plan, ctx.profiles, ctx.hw, cfg=cfg)
    ests = _estimators(plan, cfg)
    name = plan.placements[0].workload.name
    # one active window first (a NEVER-active workload is "not started
    # yet", not departed), then silence long enough to miss >=
    # depart_missed expected arrivals
    for k in range(7):
        for n, est in ests.items():
            if n == name and k > 0:
                est.observe(np.empty(0), WINDOW_MS)
            else:
                est.observe(_det_window(rec.targets[n].rate_rps,
                                        t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
    assert name in rec.departed
    assert all(p.workload.name != name for p in rec.plan.placements)
    assert any(e.action == "remove" and e.workload == name
               for e in rec.edits)
    # traffic resumes: the workload is re-added
    orig_rate = rec.departed[name].rate_rps
    for k in range(7, 13):
        for n, est in ests.items():
            rate = orig_rate if n == name else rec.targets[n].rate_rps
            est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
    assert name not in rec.departed
    # re-added possibly as a replica group (w#0..w#k-1) when the
    # recovered rate + headroom is infeasible for a single instance
    from repro.core import replication
    group = replication.group_placements(rec.plan.placements).get(name)
    assert group, f"{name} not re-added"
    assert sum(p.workload.rate_rps for p in group) == \
        pytest.approx(rec.targets[name].rate_rps)
    assert any(e.action == "add" and e.workload == name for e in rec.edits)


def test_never_active_workload_left_alone(ctx12):
    """A workload with zero traffic FROM THE START keeps its provisioned
    allocation (reclaiming it would manufacture a cold start when the
    traffic begins); silence only counts as departure after activity."""
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw)
    ests = _estimators(plan)
    name = plan.placements[0].workload.name
    for k in range(10):
        for n, est in ests.items():
            if n == name:
                est.observe(np.empty(0), WINDOW_MS)
            else:
                est.observe(_det_window(rec.targets[n].rate_rps,
                                        t0=k * WINDOW_MS), WINDOW_MS)
        assert not rec.reconcile(k + 1.0, ests)
    assert name not in rec.departed
    assert rec.plan is plan


def test_planstate_matches_sequential_provisioner_ops(ctx12):
    """The persistent VecCluster hot path (PlanState) produces the same
    per-workload allocations as applying the plan-in/plan-out
    provisioner ops one by one (entry order inside a device differs —
    irrelevant to the model's symmetric sums — and PlanState may reuse
    an emptied device where the ops would open a fresh one)."""
    import dataclasses
    ctx, plan = ctx12
    state = prov.PlanState(plan, ctx.profiles, ctx.hw)
    seq = plan
    specs = {p.workload.name: p.workload for p in plan.placements}
    edits = [("resize", "W5", 1.3), ("remove", "W2", None),
             ("resize", "W9", 0.6), ("resize", "W5", 1.1),
             ("add", "W2", 1.2), ("resize", "W11", 1.4)]
    for action, name, factor in edits:
        if action == "remove":
            state.remove(name)
            seq = prov.remove_workload(seq, name)
            continue
        new = dataclasses.replace(specs[name],
                                  rate_rps=specs[name].rate_rps * factor)
        specs[name] = new
        if action == "resize":
            state.resize(new, batch="eq17")
            seq = prov.resize_workload(seq, new, ctx.profiles, ctx.hw)
        else:
            state.add(new, batch="eq17")
            seq = prov.add_workload(seq, new, ctx.profiles, ctx.hw)
    got = {p.workload.name: (round(p.r, 9), p.batch)
           for p in state.to_plan().placements}
    want = {p.workload.name: (round(p.r, 9), p.batch)
            for p in seq.placements}
    assert got == want
    assert state.to_plan().n_gpus <= seq.n_gpus


def test_online_burstiness_floored_at_base(ctx12):
    """A deterministic trace's cv2 ~ 0 must not loosen the budget below
    the provisioned model; a bursty trace tightens it."""
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw)
    ests = _estimators(plan)
    name = plan.placements[0].workload.name
    for k in range(6):
        for n, est in ests.items():
            rate = rec.targets[n].rate_rps * (1.6 if n == name else 1.0)
            est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
    assert rec.edits                      # it did reconfigure
    assert rec.bm.burstiness >= QUEUEING.burstiness - 1e-12
    # synthetic bursty estimates push it up, clamped at the ceiling
    for est in ests.values():
        est.cv2 = 6.0
        est.n_gaps = 1000
    for k in range(6, 12):
        for n, est in ests.items():
            rate = rec.targets[n].rate_rps * (1.6 if n == name else 1.0)
            est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
    assert rec.bm.burstiness > 2.0


# ---------------------------------------------------------------------------
# Closed loop against the simulator (m=100 acceptance tier)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def m100():
    ctx = fitted_context()
    specs = synthetic_workloads(100, 0)
    plan = prov.provision(specs, ctx.profiles, ctx.hw)
    return ctx, specs, plan, models()


def _violations(res, specs, tr, horizon_ms):
    """SimResult.violations with each spec's rate target replaced by its
    trace-mean expectation (reuses the one violation definition)."""
    import dataclasses
    scaled = {s.name: dataclasses.replace(
        s, rate_rps=s.rate_rps * tr.mean_scale(s.name, horizon_ms))
        for s in specs}
    return res.violations(scaled)


@pytest.mark.parametrize("poisson", [False, True], ids=["det", "poisson"])
def test_no_drift_closed_loop_is_a_noop(m100, poisson):
    """Zero reconfigurations, bit-identical plan, and latency streams
    equal to the uncontrolled run — under both arrival processes."""
    ctx, specs, plan, mods = m100
    tr = traces.constant([s.name for s in specs], 10_000.0)
    ctl = Controller(plan, ctx.profiles, ctx.hw)
    res_c = simulate_full(plan, mods, ctx.hw, duration_s=10.0, trace=tr,
                          poisson=poisson, adjust_fn=ctl,
                          adjust_scope="cluster", adjust_period_s=1.0)
    assert res_c.stats["n_reconfigs"] == 0
    assert ctl.edits == []
    assert ctl.plan is plan               # bit-identical: never replaced
    res_0 = simulate_full(plan, mods, ctx.hw, duration_s=10.0, trace=tr,
                          poisson=poisson)
    for w in res_0.request_latencies:
        assert np.array_equal(res_c.request_latencies[w],
                              res_0.request_latencies[w]), w


def test_diurnal_controlled_beats_static(m100):
    """The PR's headline acceptance: under a 2x diurnal ramp the
    controlled plan's simulated violations come in strictly below the
    static queueing plan's (which degrades badly)."""
    ctx, specs, plan, mods = m100
    H = 10_000.0
    tr = traces.diurnal([s.name for s in specs], H, peak=2.0)
    res_s = simulate_full(plan, mods, ctx.hw, duration_s=10.0, trace=tr)
    ctl = Controller(plan, ctx.profiles, ctx.hw)
    res_c = simulate_full(plan, mods, ctx.hw, duration_s=10.0, trace=tr,
                          adjust_fn=ctl, adjust_scope="cluster",
                          adjust_period_s=1.0)
    v_s = _violations(res_s, specs, tr, H)
    v_c = _violations(res_c, specs, tr, H)
    assert len(v_s) >= 60                 # the static plan degrades
    assert len(v_c) < len(v_s) * 0.75     # the controller recovers most
    assert res_c.stats["n_reconfigs"] > 0
    assert res_c.stats["reconfig_latency_ms"] > 0.0
    # the controller buys capacity: more devices at peak, tracked cost
    assert ctl.plan.n_gpus >= plan.n_gpus


def test_controlled_run_engine_identical(ctx12):
    """A RECONFIGURING controlled run is byte-identical across engines
    (fresh controller per engine; wall-clock stat excluded)."""
    ctx, plan = ctx12
    mods = models()
    names = [s.name for s in twelve_workloads()]
    tr = traces.diurnal(names, 6000.0, peak=2.0)
    results = {}
    for engine in ("scalar", "vec"):
        ctl = Controller(plan, ctx.profiles, ctx.hw)
        results[engine] = (ctl, simulate_plan(
            plan, mods, ctx.hw, duration_s=6.0, trace=tr, adjust_fn=ctl,
            adjust_scope="cluster", adjust_period_s=1.0, engine=engine))
    (ctl_a, a), (ctl_b, b) = results["scalar"], results["vec"]
    assert a.stats["n_reconfigs"] == b.stats["n_reconfigs"] > 0
    for w in a.request_latencies:
        assert np.array_equal(a.request_latencies[w],
                              b.request_latencies[w]), w
        assert np.array_equal(a.request_waits[w], b.request_waits[w]), w
    assert a.per_workload == b.per_workload
    assert len(ctl_a.edits) == len(ctl_b.edits)
    for ea, eb in zip(ctl_a.edits, ctl_b.edits):
        assert (ea.t_s, ea.action, ea.workload, ea.rate_to) == \
            (eb.t_s, eb.action, eb.workload, eb.rate_to)


def test_adjust_scope_device_vs_cluster_instance_local(ctx12):
    """An instance-local callback produces identical results under both
    scopes and both engines (the unified contract)."""
    ctx, plan = ctx12
    mods = models()

    def bump(now, insts):
        for inst in insts:
            if inst.completed > 300 and inst.batch < 32:
                inst.batch += 1

    base = None
    for engine in ("scalar", "vec"):
        for scope in ("device", "cluster"):
            res = simulate_plan(plan, mods, ctx.hw, duration_s=4.0,
                                adjust_fn=bump, adjust_period_s=0.7,
                                adjust_scope=scope, engine=engine)
            sig = (res.stats["n_reconfigs"],
                   {w: res.request_latencies[w].tobytes()
                    for w in res.request_latencies})
            if base is None:
                base = sig
            else:
                assert sig == base, (engine, scope)


def test_simulate_rejects_bad_scope(ctx12):
    ctx, plan = ctx12
    with pytest.raises(ValueError):
        simulate_plan(plan, models(), ctx.hw, duration_s=1.0,
                      adjust_scope="rack")


def test_controller_rejects_device_scope(ctx12):
    """Driving the Controller under the default per-device scope would
    corrupt its estimators (zero-width windows); it must fail loudly."""
    ctx, plan = ctx12
    ctl = Controller(plan, ctx.profiles, ctx.hw)
    with pytest.raises(RuntimeError, match="cluster"):
        simulate_plan(plan, models(), ctx.hw, duration_s=3.0,
                      adjust_fn=ctl, adjust_period_s=1.0,
                      adjust_scope="device")


def test_controller_composes_with_shadow_mode(ctx12):
    """The historical Controller <-> shadow=True refusal is gone: the
    controller ADOPTS simulator-armed shadow_r reservations into its
    armed book at the first tick, so every plan edit accounts for them
    and an activation can never overcommit a device."""
    ctx, plan = ctx12
    ctl = Controller(plan, ctx.profiles, ctx.hw)
    res = simulate_plan(plan, models(), ctx.hw, duration_s=3.0,
                        shadow=True, adjust_fn=ctl, adjust_period_s=1.0,
                        adjust_scope="cluster")
    assert res.stats["n_requests"] > 0
    # every _setup-armed reservation is in the book after tick 1
    assert ctl.reconciler.armed  # twelve_workloads leaves free capacity


def test_migration_via_gpu_mutation(ctx12):
    """The adjust hook's gpu mutation (migration) is honored by both
    engines: the instance serves from the new device's co-location
    state and the streams stay engine-identical."""
    ctx, plan = ctx12
    mods = models()
    free_gpu = max(p.gpu for p in plan.placements) + 1
    moved = set()

    def make_fn():
        moved.clear()

        def fn(now, insts):
            for inst in insts:
                if inst.spec.name == "W1" and inst.spec.name not in moved:
                    inst.gpu = free_gpu
                    inst.r = 1.0
                    moved.add(inst.spec.name)
        return fn

    a = simulate_plan(plan, mods, ctx.hw, duration_s=4.0,
                      adjust_fn=make_fn(), adjust_scope="cluster",
                      adjust_period_s=1.0, engine="scalar")
    b = simulate_plan(plan, mods, ctx.hw, duration_s=4.0,
                      adjust_fn=make_fn(), adjust_scope="cluster",
                      adjust_period_s=1.0, engine="vec")
    assert a.stats["n_reconfigs"] == b.stats["n_reconfigs"] == 1
    assert a.per_workload["W1"]["r_final"] == 1.0
    for w in a.request_latencies:
        assert np.array_equal(a.request_latencies[w],
                              b.request_latencies[w]), w


def test_recent_arrivals_synced_to_adjust_window(ctx12):
    ctx, plan = ctx12
    mods = models()
    seen = []

    def probe(now, insts):
        for inst in insts:
            if inst.spec.name == "W1":
                seen.append((now, np.array(inst.recent_arrivals)))

    simulate_plan(plan, mods, ctx.hw, duration_s=3.0, adjust_fn=probe,
                  adjust_scope="cluster", adjust_period_s=1.0)
    w1 = next(s for s in twelve_workloads() if s.name == "W1")
    assert len(seen) >= 2
    for now, arr in seen:
        assert arr.size == pytest.approx(w1.rate_rps, rel=0.05)
        assert (arr > (now - 1.0) * 1000.0).all()
        assert (arr <= now * 1000.0).all()

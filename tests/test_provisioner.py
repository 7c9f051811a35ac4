"""Provisioning strategy invariants (Alg. 1 / Alg. 2) — unit + hypothesis."""
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:      # bare env: property tests skip, unit tests run
    from tests._hypothesis_stub import given, settings, st

from repro.core import baselines as B
from repro.core import perf_model as pm
from repro.core import provisioner as prov
from repro.core.types import V5E, WorkloadSpec
from tests.test_perf_model import make_coeffs


def _profiles():
    return {
        "light": make_coeffs(k1=0.002, k2=0.4, k3=0.8, k5=0.05),
        "mid": make_coeffs(k1=0.01, k2=2.0, k3=3.0),
        "heavy": make_coeffs(k1=0.02, k2=5.0, k3=8.0, k5=0.3),
    }


workload_st = st.lists(
    st.tuples(st.sampled_from(["light", "mid", "heavy"]),
              st.floats(60.0, 400.0), st.floats(5.0, 80.0)),
    min_size=1, max_size=8)


@settings(max_examples=40, deadline=None)
@given(ws=workload_st)
def test_provision_invariants(ws):
    specs = [WorkloadSpec(f"W{i}", m, slo, rate)
             for i, (m, slo, rate) in enumerate(ws)]
    profiles = _profiles()
    try:
        plan = prov.provision(specs, profiles, V5E)
    except prov.InfeasibleError:
        return
    # every workload placed exactly once (Eq. 16)
    placed = sorted(p.workload.name for p in plan.placements)
    assert placed == sorted(s.name for s in specs)
    # capacity constraint per device (Eq. 15)
    for g in range(plan.n_gpus):
        assert plan.total_allocated(g) <= 1.0 + 1e-9
    # allocations in r_unit grid, positive
    for p in plan.placements:
        assert p.r > 0
        assert abs(p.r / V5E.r_unit - round(p.r / V5E.r_unit)) < 1e-6
        assert p.batch >= 1
    # the analytical model predicts every SLO met (Constraint 14)
    for g, pls in plan.by_gpu().items():
        placed_w = [pm.PlacedWorkload(profiles[p.workload.model], p.batch, p.r)
                    for p in pls]
        pred = pm.predict_device(placed_w, V5E)
        for p, wp in zip(pls, pred.per_workload):
            assert wp.t_inf <= p.workload.slo_ms / 2.0 + 1e-6


@settings(max_examples=25, deadline=None)
@given(ws=workload_st)
def test_ffd_uses_fewer_or_equal_devices_than_singletons(ws):
    specs = [WorkloadSpec(f"W{i}", m, slo, rate)
             for i, (m, slo, rate) in enumerate(ws)]
    profiles = _profiles()
    try:
        plan = B.provision_ffd(specs, profiles, V5E)
    except prov.InfeasibleError:
        return
    assert plan.n_gpus <= len(specs)


def test_alloc_gpus_grows_on_violation():
    """Alg. 2: co-locating a heavy neighbor grants extra resources to the
    originally-placed workload when its SLO would be violated."""
    profiles = _profiles()
    hw = V5E
    s1 = WorkloadSpec("a", "mid", 100.0, 40.0)
    s2 = WorkloadSpec("b", "heavy", 150.0, 30.0)
    b1 = prov.appropriate_batch(s1, profiles["mid"], hw)
    r1 = prov.resource_lower_bound(s1, profiles["mid"], hw, b1)
    dev = prov._Dev(entries=[(s1, profiles["mid"], b1, r1)])
    b2 = prov.appropriate_batch(s2, profiles["heavy"], hw)
    r2 = prov.resource_lower_bound(s2, profiles["heavy"], hw, b2)
    r_a = prov.alloc_gpus(dev, s2, profiles["heavy"], b2, r2, hw)
    if r_a is not None:
        assert r_a[0] >= r1 - 1e-9          # never shrinks the original
        assert r_a[-1] >= r2 - 1e-9
        assert sum(r_a) <= 1.0 + 1e-9


def test_gpulets_at_most_two_per_device():
    specs = [WorkloadSpec(f"W{i}", "light", 120.0, 30.0) for i in range(7)]
    plan = B.provision_gpulets(specs, _profiles(), V5E)
    for g, pls in plan.by_gpu().items():
        assert len(pls) <= 2
        for p in pls:
            assert p.r in (0.2, 0.4, 0.5, 0.6, 0.8)


def test_heterogeneous_selection_picks_cheaper():
    from repro.core.types import V4
    specs = [WorkloadSpec("W0", "light", 150.0, 20.0),
             WorkloadSpec("W1", "mid", 200.0, 20.0)]
    profiles = {"tpu-v5e": _profiles(), "tpu-v4": _profiles()}
    plan, hw = prov.provision_cheapest(specs, profiles, [V5E, V4])
    # same coefficient surface on both -> cheaper per-device price must win
    assert hw.name == "tpu-v5e"


def test_sorted_descending_placement_order():
    """Alg. 1 line 3: larger r_lower placed first (ANYFIT constraint)."""
    profiles = _profiles()
    specs = [WorkloadSpec("small", "light", 300.0, 10.0),
             WorkloadSpec("big", "heavy", 80.0, 50.0)]
    try:
        plan = prov.provision(specs, profiles, V5E)
    except prov.InfeasibleError:
        return
    by = {p.workload.name: p for p in plan.placements}
    assert by["big"].gpu == 0     # the big workload anchored the first device


def test_online_add_workload_adjusts_originals():
    """Sec. 2.3's gpu-lets critique: iGniter must be able to grow the
    ORIGINALLY-placed workloads' allocations when a newcomer lands."""
    profiles = _profiles()
    base_specs = [WorkloadSpec("W0", "mid", 150.0, 40.0),
                  WorkloadSpec("W1", "light", 200.0, 30.0)]
    plan = prov.provision(base_specs, profiles, V5E)
    before = {p.workload.name: p.r for p in plan.placements}

    new = WorkloadSpec("W2", "heavy", 200.0, 30.0)
    plan2 = prov.add_workload(plan, new, profiles, V5E)
    names = sorted(p.workload.name for p in plan2.placements)
    assert names == ["W0", "W1", "W2"]
    # capacity + predicted SLOs still hold
    for g, pls in plan2.by_gpu().items():
        assert sum(p.r for p in pls) <= 1.0 + 1e-9
        placed = [pm.PlacedWorkload(profiles[p.workload.model], p.batch, p.r)
                  for p in pls]
        pred = pm.predict_device(placed, V5E)
        for p, wp in zip(pls, pred.per_workload):
            assert wp.t_inf <= p.workload.slo_ms / 2.0 + 1e-6
    # originals never shrink (Alg. 2 only grows)
    after = {p.workload.name: p.r for p in plan2.placements}
    for n in before:
        assert after[n] >= before[n] - 1e-9


def test_online_add_matches_batch_quality():
    """A stream of online arrivals should not use wildly more devices
    than provisioning the same set at once."""
    import numpy as np
    profiles = _profiles()
    rng = np.random.default_rng(1)
    specs = [WorkloadSpec(f"W{i}", ["light", "mid", "heavy"][i % 3],
                          float(rng.uniform(150, 350)),
                          float(rng.uniform(10, 40))) for i in range(8)]
    batch_plan = prov.provision(specs, profiles, V5E)
    online = prov.provision(specs[:1], profiles, V5E)
    for s in specs[1:]:
        online = prov.add_workload(online, s, profiles, V5E)
    assert online.n_gpus <= batch_plan.n_gpus + 2


# ---------------------------------------------------------------------------
# Fresh-device self-grant (beyond-paper fix for the Theorem-1 f/F
# throttling residual — see ROADMAP / ISSUE 2)
# ---------------------------------------------------------------------------

def test_self_grant_meets_half_slo_budget():
    """Every fresh-device anchor must satisfy Constraint 14 at its
    granted allocation (or honestly occupy the full device)."""
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    ctx = fitted_context()
    grants = 0
    for s in synthetic_workloads(30, seed=5):
        c = ctx.profiles[s.model]
        b = prov.appropriate_batch(s, c, ctx.hw)
        rl = prov.resource_lower_bound(s, c, ctx.hw, b)
        r = prov.self_grant(s, c, b, rl, ctx.hw)
        assert r >= rl - 1e-12
        assert abs(r / ctx.hw.r_unit - round(r / ctx.hw.r_unit)) < 1e-6
        pred = pm.predict_device(
            [pm.PlacedWorkload(coeffs=c, batch=b, r=r)], ctx.hw)
        assert (pred.per_workload[0].t_inf <= s.slo_ms / 2.0 + 1e-9
                or r == prov.R_MAX)
        grants += r > rl + 1e-12
    assert grants > 0     # the throttling residual is real for this mix


def test_self_grant_clears_predicted_violations_at_scale():
    """Pre-fix the m=100 synthetic sweep predicted 8 violations — all
    solo fresh-device anchors.  Post-fix the model predicts zero.
    (A half-budget regression test: the Theorem-1 throttling residual is
    defined against the paper's T_slo/2 split.)"""
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    ctx5 = fitted_context("tpu-v5e")
    ctx4 = fitted_context("tpu-v4")
    profiles = {ctx5.hw.name: ctx5.profiles, ctx4.hw.name: ctx4.profiles}
    specs = synthetic_workloads(100, 0)
    plan, hw = prov.provision_cheapest(specs, profiles, [ctx5.hw, ctx4.hw],
                                       budget="half")
    assert prov.predicted_violations(plan, profiles[hw.name], hw,
                                     budget="half") == []
    # both engines apply the identical self-grant
    oracle, hw_o = prov.provision_cheapest(specs, profiles,
                                           [ctx5.hw, ctx4.hw],
                                           engine="scalar", budget="half")
    assert hw_o.name == hw.name
    assert [(p.workload.name, p.gpu, round(p.r, 9)) for p in oracle.placements] \
        == [(p.workload.name, p.gpu, round(p.r, 9)) for p in plan.placements]


# ---------------------------------------------------------------------------
# Incremental plan edits (online control plane): resize / remove / migrate
# ---------------------------------------------------------------------------

def _mixed_plan():
    profiles = _profiles()
    specs = [WorkloadSpec(f"W{i}", m, slo, rate) for i, (m, slo, rate) in
             enumerate([("light", 80.0, 60.0), ("mid", 150.0, 40.0),
                        ("heavy", 240.0, 25.0), ("light", 120.0, 90.0),
                        ("mid", 200.0, 30.0), ("heavy", 300.0, 20.0)])]
    return specs, profiles, prov.provision(specs, profiles, V5E)


def _plan_key(plan):
    return [(p.workload.name, p.workload.rate_rps, p.gpu,
             round(p.r, 9), p.batch) for p in plan.placements]


def test_remove_workload_drops_exactly_one():
    specs, profiles, plan = _mixed_plan()
    out = prov.remove_workload(plan, "W2")
    assert len(out.placements) == len(plan.placements) - 1
    assert all(p.workload.name != "W2" for p in out.placements)
    assert out.n_gpus == len({p.gpu for p in out.placements})
    # survivors untouched (peers keep their grants)
    kept = {p.workload.name: (p.gpu, p.r, p.batch) for p in out.placements}
    for p in plan.placements:
        if p.workload.name != "W2":
            assert kept[p.workload.name] == (p.gpu, p.r, p.batch)
    with pytest.raises(KeyError):
        prov.remove_workload(plan, "nope")


@pytest.mark.parametrize("factor", [1.5, 0.5])
def test_resize_workload_engines_identical(factor):
    import dataclasses
    specs, profiles, plan = _mixed_plan()
    new = dataclasses.replace(specs[1], rate_rps=specs[1].rate_rps * factor)
    a = prov.resize_workload(plan, new, profiles, V5E, engine="vec")
    b = prov.resize_workload(plan, new, profiles, V5E, engine="scalar")
    assert _plan_key(a) == _plan_key(b)
    pa = next(p for p in a.placements if p.workload.name == new.name)
    assert pa.workload.rate_rps == new.rate_rps
    # Theorem 1 re-ran at the new rate
    bm = prov.resolve("queueing")
    assert pa.batch == prov.appropriate_batch(new, profiles["mid"], V5E,
                                              budget=bm)
    with pytest.raises(KeyError):
        prov.resize_workload(plan, dataclasses.replace(new, name="nope"),
                             profiles, V5E)


def test_resize_up_never_shrinks_peer_grants():
    import dataclasses
    specs, profiles, plan = _mixed_plan()
    cur = plan.placements[0]
    new = dataclasses.replace(cur.workload,
                              rate_rps=cur.workload.rate_rps * 1.4)
    out = prov.resize_workload(plan, new, profiles, V5E)
    before = {p.workload.name: p.r for p in plan.placements
              if p.gpu == cur.gpu}
    target = next(p for p in out.placements if p.workload.name == new.name)
    if target.gpu == cur.gpu:          # same-device fast path taken
        for p in out.placements:
            if p.gpu == cur.gpu and p.workload.name != new.name:
                assert p.r >= before[p.workload.name] - 1e-12


def test_migrate_workload_engines_identical():
    import dataclasses
    specs, profiles, plan = _mixed_plan()
    new = dataclasses.replace(specs[0], rate_rps=specs[0].rate_rps * 1.2)
    a = prov.migrate_workload(plan, new, profiles, V5E, engine="vec")
    b = prov.migrate_workload(plan, new, profiles, V5E, engine="scalar")
    assert _plan_key(a) == _plan_key(b)
    assert sum(1 for p in a.placements if p.workload.name == new.name) == 1


def test_resize_falls_back_to_migration_when_device_full():
    """Grow a workload until its current device cannot host it: the
    resize must land it elsewhere (or on a fresh device) instead of
    failing, and the result must match the scalar oracle."""
    import dataclasses
    specs, profiles, plan = _mixed_plan()
    cur = plan.placements[0]
    peers = [p for p in plan.placements if p.gpu == cur.gpu
             and p.workload.name != cur.workload.name]
    grown = None
    for f in (2.0, 3.0, 4.0, 6.0):
        new = dataclasses.replace(cur.workload,
                                  rate_rps=cur.workload.rate_rps * f)
        try:
            out = prov.resize_workload(plan, new, profiles, V5E)
        except prov.InfeasibleError:
            break
        tgt = next(p for p in out.placements
                   if p.workload.name == new.name)
        if peers and tgt.gpu != cur.gpu:
            grown = (new, out)
            break
    if grown is not None:
        new, out = grown
        oracle = prov.resize_workload(plan, new, profiles, V5E,
                                      engine="scalar")
        assert _plan_key(out) == _plan_key(oracle)


# ---------------------------------------------------------------------------
# Queueing-aware joint batch re-optimizer (batch="joint")
# ---------------------------------------------------------------------------

def test_joint_batch_never_needs_more_solo_resources():
    """For every feasible spec, r_lower at the joint batch is <= r_lower
    at Eq. 17's batch (never-worse by construction)."""
    import numpy as np
    profiles = _profiles()
    rng = np.random.default_rng(2)
    checked = 0
    for _ in range(150):
        m = str(rng.choice(["light", "mid", "heavy"]))
        s = WorkloadSpec("W", m, float(rng.uniform(60.0, 400.0)),
                         float(rng.uniform(5.0, 300.0)))
        c = profiles[m]
        try:
            b0 = prov.appropriate_batch(s, c, V5E)
            r0 = prov.resource_lower_bound(s, c, V5E, b0)
        except prov.InfeasibleError:
            continue
        b1 = prov.appropriate_batch(s, c, V5E, batch="joint")
        r1 = prov.resource_lower_bound(s, c, V5E, b1)
        assert r1 <= r0 + 1e-12, (s.slo_ms, s.rate_rps, b0, b1)
        checked += 1
    assert checked > 40


def test_joint_batch_rejects_unknown_mode():
    profiles = _profiles()
    s = WorkloadSpec("W", "mid", 150.0, 60.0)
    with pytest.raises(ValueError):
        prov.appropriate_batch(s, profiles["mid"], V5E, batch="auto")


def test_joint_batch_plan_never_worse_at_m100():
    """m=100 regression pin: the joint re-optimizer's full plan costs no
    more than the default and predicts no more violations (measured on
    this container: 72 vs 78 devices, 7 vs 13 predicted violations)."""
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    ctx = fitted_context()
    specs = synthetic_workloads(100, 0)
    dflt = prov.provision(specs, ctx.profiles, ctx.hw)
    joint = prov.provision(specs, ctx.profiles, ctx.hw, batch="joint")
    assert joint.cost_per_hour() <= dflt.cost_per_hour()
    v_d = prov.predicted_violations(dflt, ctx.profiles, ctx.hw)
    v_j = prov.predicted_violations(joint, ctx.profiles, ctx.hw)
    assert len(v_j) <= len(v_d)
    # engines agree on the joint plans too
    oracle = prov.provision(specs, ctx.profiles, ctx.hw, batch="joint",
                            engine="scalar")
    assert _plan_key(joint) == _plan_key(oracle)


# ---------------------------------------------------------------------------
# One online placement decision (`_choose_row`) behind `add_workload`'s vec
# engine and `PlanState`: each constraint binds on an m=30 standing plan
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def standing30():
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    ctx = fitted_context()
    return ctx, prov.provision(synthetic_workloads(30, seed=3),
                               ctx.profiles, ctx.hw)


def _rows(plan):
    return [(p.workload.name, p.gpu, round(p.r, 9), p.batch)
            for p in plan.placements]


def _landed(plan, name):
    return next((p.gpu, p.r) for p in plan.placements
                if p.workload.name == name)


@pytest.mark.parametrize("case", ["ban", "reserve", "cap", "pin"])
def test_online_placement_decision_engines_and_planstate_agree(standing30,
                                                               case):
    """`add_workload` with the vec and the scalar engine gives the same
    ordered plan rows (or the same error) under an exclusion, an armed
    reservation, a binding device cap and a pin; `PlanState.add` under
    the same constraint lands the newcomer on the same device with the
    same r."""
    from repro.core.types import PlannerConfig
    ctx, plan = standing30
    new = WorkloadSpec("new", "rwkv6-1.6b", 156.2, 20.8)
    g0 = _landed(prov.add_workload(plan, new, ctx.profiles, ctx.hw),
                 new.name)[0]
    assert g0 in {p.gpu for p in plan.placements}   # lands on a resident
    kw, pin = {}, None
    state = prov.PlanState(plan, ctx.profiles, ctx.hw)
    if case == "ban":
        kw["exclude_gpus"] = frozenset({g0})
        state.banned = {g0}
    elif case == "reserve":
        free = 1.0 - plan.total_allocated(g0)
        kw["reserved"] = {g0: free}
        holder = next(p.workload.name for p in plan.placements
                      if p.gpu == g0)
        state.shadow[holder] = free
    elif case == "cap":
        new = WorkloadSpec("new", "qwen2-vl-7b", 156.2, 41.6)
        uncapped = prov.add_workload(plan, new, ctx.profiles, ctx.hw)
        assert _landed(uncapped, new.name)[0] == max(plan.by_gpu()) + 1
        kw["max_devices"] = state.max_devices = plan.n_gpus
    else:
        pin = kw["pin"] = (2, 0.3)

    got = {}
    for engine in ("vec", "scalar"):
        try:
            got[engine] = _rows(prov.add_workload(
                plan, new, ctx.profiles, ctx.hw,
                config=PlannerConfig(engine=engine), **kw))
        except prov.InfeasibleError as e:
            got[engine] = type(e)
    assert got["vec"] == got["scalar"]
    try:
        state.add(new, batch="eq17", pin=pin)
        live = _landed(state.to_plan(), new.name)
    except prov.InfeasibleError as e:
        live = type(e)

    if case == "cap":
        assert got["vec"] is prov.DeviceCapError
        assert live is prov.DeviceCapError
        return
    want = next((g, r) for name, g, r, _ in got["vec"] if name == new.name)
    assert (live[0], round(live[1], 9)) == want
    if case == "pin":
        assert next(b for name, _, _, b in got["vec"]
                    if name == new.name) == pin[0]
        assert want[1] >= pin[1] - 1e-12
    else:
        assert want[0] != g0                  # the constraint binds

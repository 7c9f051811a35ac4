"""Vectorized model/provisioner vs the scalar reference oracle.

Pure numpy randomization (seeded) — deliberately no hypothesis
dependency so the tier-1 consistency gate runs on bare environments.
"""
import numpy as np
import pytest

from repro.core import baselines as B
from repro.core import perf_model as pm
from repro.core import perf_model_vec as pmv
from repro.core import provisioner as prov
from repro.core.types import (PlannerConfig, V5E, WorkloadCoefficients,
                              WorkloadSpec)
from tests.test_perf_model import make_coeffs

TOL = dict(rtol=1e-9, atol=1e-9)
# the scalar-vs-vec suites also pin the jitted backend where it plugs in
# (plan identity / grid-identical allocations); jax params ride the
# jax-marked CI job, numpy params stay in tier 1
BACKENDS = ("numpy", pytest.param("jax", marks=pytest.mark.jax))
FIELDS = ("t_load", "t_sch", "t_act", "t_gpu", "t_feedback", "t_inf",
          "throughput")


def random_coeffs(rng):
    return make_coeffs(
        k1=rng.uniform(0.001, 0.03), k2=rng.uniform(0.2, 6.0),
        k3=rng.uniform(0.5, 9.0), k4=rng.uniform(0.01, 0.5),
        k5=rng.uniform(0.01, 0.5), alpha_cache=rng.uniform(0.0, 0.6))


def random_device(rng, n=None):
    n = int(rng.integers(1, 7)) if n is None else n
    return [pm.PlacedWorkload(random_coeffs(rng), int(rng.integers(1, 33)),
                              float(rng.uniform(0.05, 1.0)))
            for _ in range(n)]


def _profiles():
    return {
        "light": make_coeffs(k1=0.002, k2=0.4, k3=0.8, k5=0.05),
        "mid": make_coeffs(k1=0.01, k2=2.0, k3=3.0),
        "heavy": make_coeffs(k1=0.02, k2=5.0, k3=8.0, k5=0.3),
    }


def random_specs(rng, max_n=9):
    names = rng.choice(["light", "mid", "heavy"],
                       size=int(rng.integers(1, max_n)))
    return [WorkloadSpec(f"W{i}", m, float(rng.uniform(60.0, 400.0)),
                         float(rng.uniform(5.0, 80.0)))
            for i, m in enumerate(names)]


def plan_key(plan):
    return ([(p.workload.name, p.gpu, round(p.r, 9), p.batch)
             for p in plan.placements], plan.n_gpus)


# ---------------------------------------------------------------------------
# Eqs. (1)-(11): batched == scalar to 1e-9
# ---------------------------------------------------------------------------

def test_predict_device_vec_matches_scalar_randomized():
    """Randomized co-location mixes: every per-workload and per-device
    quantity agrees with the scalar Eqs. (1)-(11) to <= 1e-9."""
    rng = np.random.default_rng(0)
    for _ in range(300):
        ws = random_device(rng)
        a = pm.predict_device(ws, V5E)
        b = pmv.predict_device_vec(ws, V5E)
        np.testing.assert_allclose(b.freq, a.freq, **TOL)
        np.testing.assert_allclose(b.p_demand, a.p_demand, **TOL)
        np.testing.assert_allclose(b.delta_sch, a.delta_sch, **TOL)
        assert len(a.per_workload) == len(b.per_workload)
        for wa, wb in zip(a.per_workload, b.per_workload):
            for f in FIELDS:
                np.testing.assert_allclose(getattr(wb, f), getattr(wa, f),
                                           err_msg=f, **TOL)


def test_predict_device_batch_matches_per_device():
    """Ragged device batches: one batched call == D scalar calls."""
    rng = np.random.default_rng(1)
    devices = [random_device(rng) for _ in range(12)]
    batch = pmv.predict_device_batch(devices, V5E)
    for q, ws in enumerate(devices):
        ref = pm.predict_device(ws, V5E)
        got = batch.device(q)
        np.testing.assert_allclose(got.freq, ref.freq, **TOL)
        np.testing.assert_allclose(got.p_demand, ref.p_demand, **TOL)
        for wa, wb in zip(ref.per_workload, got.per_workload):
            np.testing.assert_allclose(wb.t_inf, wa.t_inf, **TOL)
            np.testing.assert_allclose(wb.throughput, wa.throughput, **TOL)


def test_throttling_regime_matches_scalar():
    """Eq. (9) branch coverage: heavy mixes that exceed the power cap."""
    rng = np.random.default_rng(2)
    hit = 0
    for _ in range(100):
        ws = random_device(rng, n=6)
        a = pm.predict_device(ws, V5E)
        b = pmv.predict_device_vec(ws, V5E)
        hit += a.p_demand > V5E.power_cap
        np.testing.assert_allclose(b.freq, a.freq, **TOL)
        for wa, wb in zip(a.per_workload, b.per_workload):
            np.testing.assert_allclose(wb.t_inf, wa.t_inf, **TOL)
    assert hit > 0          # the sweep actually exercised the branch


# ---------------------------------------------------------------------------
# Incremental invariants (VecCluster caching)
# ---------------------------------------------------------------------------

def test_veccluster_incremental_matches_fresh():
    """After appends, grants (set_row_r) and device growth, the cached
    invariants give the same prediction as a fresh scalar evaluation."""
    rng = np.random.default_rng(3)
    profiles = _profiles()
    cl = pmv.VecCluster(V5E, cap_d=1, cap_n=1)   # force capacity growth
    devices = []
    for q in range(5):
        cl.add_device()
        devices.append([])
        for _ in range(int(rng.integers(1, 5))):
            m = str(rng.choice(["light", "mid", "heavy"]))
            s = WorkloadSpec(f"W{q}", m, 200.0, 30.0)
            b = int(rng.integers(1, 17))
            r = float(rng.choice([0.1, 0.2, 0.25, 0.4]))
            cl.add_entry(q, s, profiles[m], b, r)
            devices[q].append((profiles[m], b, r))
    # grant +r_unit to a couple of entries on device 2
    k = int(cl.n[2])
    new_r = cl.r[2, :k].copy()
    new_r[0] = round(new_r[0] + 2 * V5E.r_unit, 10)
    cl.set_row_r(2, new_r)
    devices[2][0] = (devices[2][0][0], devices[2][0][1], float(new_r[0]))
    for q in range(5):
        ref = pm.predict_device(
            [pm.PlacedWorkload(c, b, r) for (c, b, r) in devices[q]], V5E)
        got = cl.predict(q)
        np.testing.assert_allclose(got.p_demand, ref.p_demand, **TOL)
        for wa, wb in zip(ref.per_workload, got.per_workload):
            np.testing.assert_allclose(wb.t_inf, wa.t_inf, **TOL)


def random_residents(rng, n_devices, sizes):
    """``n_devices`` resident lists ``(spec, coeffs, batch, r)`` of the
    pinned profiles, row sizes drawn from ``sizes`` (each one used)."""
    profiles = _profiles()
    counts = list(sizes) + list(rng.choice(sizes,
                                           size=n_devices - len(sizes)))
    rng.shuffle(counts)
    devices = []
    for q, k in enumerate(counts):
        row = []
        for i in range(int(k)):
            m = str(rng.choice(["light", "mid", "heavy"]))
            s = WorkloadSpec(f"W{q}_{i}", m, float(rng.uniform(60, 400)),
                             float(rng.uniform(5, 300)))
            row.append((s, profiles[m], int(rng.integers(1, 33)),
                        float(rng.choice([0.05, 0.1, 0.2, 0.25, 0.4]))))
        devices.append(row)
    return devices


def loop_cluster(devices, budget, backend="numpy"):
    """The cluster built one `add_device` / `add_entry` call at a time."""
    cl = pmv.VecCluster(V5E, budget=budget, backend=backend)
    for row in devices:
        q = cl.add_device()
        for (s, c, b, r) in row:
            cl.add_entry(q, s, c, b, r)
    return cl


CLUSTER_ARRAYS = ("b", "r", "budget_ms", "mask", "n", "k_act", "power",
                  "cache", "t_io", "t_schk", "power_sum", "cache_sum")


@pytest.mark.parametrize("budget", ["half", "queueing"])
@pytest.mark.parametrize("n_devices,sizes", [
    (5, (1, 4)),              # capacities stay (8, 4)
    (12, (1, 4, 5)),          # rows cross 8, slots 4 -> 8
    (20, (1, 4, 5, 9)),       # rows cross 16, slots 8 -> 16, pairwise sums
    (17, (0, 1, 9, 12, 17)),  # empty rows, slots 16 -> 32
])
def test_from_devices_matches_add_entry_bit_for_bit(n_devices, sizes,
                                                    budget):
    """The one-pass constructor gives the incremental build's state bit
    for bit: capacities, every coefficient field and cached array
    (numpy's pairwise row sums past 8 residents included), entries,
    counts, and a fresh cluster's all-dirty rows with no device copy."""
    rng = np.random.default_rng([n_devices, len(sizes)])
    devices = random_residents(rng, n_devices, sizes)
    ref = loop_cluster(devices, budget)
    got = pmv.VecCluster.from_devices(V5E, devices, budget=budget)
    for f in pmv.COEFF_FIELDS:
        a, b = getattr(ref.ca, f), getattr(got.ca, f)
        assert a.shape == b.shape and a.tobytes() == b.tobytes(), f
    for f in CLUSTER_ARRAYS:
        a, b = getattr(ref, f), getattr(got, f)
        assert a.shape == b.shape and a.dtype == b.dtype, f
        assert a.tobytes() == b.tobytes(), f
    assert got.entries == ref.entries
    assert got.d == ref.d == n_devices
    assert got.dirty.shape == ref.dirty.shape and got.dirty.all()
    assert got.mirror is None
    assert got.mask.shape[1] >= max(sizes)


# ---------------------------------------------------------------------------
# Algorithm 2: batched == scalar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("budget", ["half", "queueing"])
def test_alloc_gpus_vec_matches_scalar_randomized(budget, backend):
    rng = np.random.default_rng(4)
    profiles = _profiles()
    checked = 0
    for _ in range(60):
        residents = []
        for i in range(int(rng.integers(0, 4))):
            m = str(rng.choice(["light", "mid", "heavy"]))
            s = WorkloadSpec(f"R{i}", m, float(rng.uniform(80, 400)), 30.0)
            residents.append((s, profiles[m], int(rng.integers(1, 17)),
                              float(rng.choice([0.1, 0.2, 0.25]))))
        m = str(rng.choice(["light", "mid", "heavy"]))
        s_new = WorkloadSpec("NEW", m, float(rng.uniform(80, 400)),
                             float(rng.uniform(5, 60)))
        try:
            b = prov.appropriate_batch(s_new, profiles[m], V5E, budget=budget)
            rl = prov.resource_lower_bound(s_new, profiles[m], V5E, b,
                                           budget=budget)
        except prov.InfeasibleError:
            continue
        dev = prov._Dev(entries=list(residents))
        ref = prov.alloc_gpus(dev, s_new, profiles[m], b, rl, V5E,
                              budget=budget)
        got = pmv.alloc_gpus_vec(residents, s_new, profiles[m], b, rl, V5E,
                                 budget=budget, backend=backend)
        assert (ref is None) == (got is None)
        if ref is not None:
            np.testing.assert_allclose(got, ref, **TOL)
            checked += 1
    assert checked > 10


# ---------------------------------------------------------------------------
# Algorithm 1: identical plans from both engines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("budget", ["half", "queueing"])
def test_provision_engines_identical_randomized(budget, backend):
    rng = np.random.default_rng(5)
    profiles = _profiles()
    compared = 0
    for _ in range(40):
        specs = random_specs(rng)
        try:
            scalar = prov.provision(specs, profiles, V5E, engine="scalar",
                                    budget=budget)
        except prov.InfeasibleError:
            continue
        vec = prov.provision(specs, profiles, V5E,
                             config=PlannerConfig(engine="vec",
                                                  budget=budget,
                                                  backend=backend))
        assert plan_key(vec) == plan_key(scalar)
        compared += 1
    assert compared > 10


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("budget", ["half", "queueing"])
def test_provision_vec_identical_on_paper_workload(budget, backend):
    """The paper's 4-model 12-workload App study: the batched provisioner
    emits a plan identical to the scalar oracle under both budget
    splits."""
    from repro.core.experiments import fitted_context
    from repro.serving.workload import twelve_workloads
    ctx = fitted_context()
    specs = twelve_workloads()
    scalar = prov.provision(specs, ctx.profiles, ctx.hw, engine="scalar",
                            budget=budget)
    vec = prov.provision(specs, ctx.profiles, ctx.hw,
                         config=PlannerConfig(engine="vec", budget=budget,
                                              backend=backend))
    assert plan_key(vec) == plan_key(scalar)
    if budget == "queueing":
        # and the defaults are: vectorized engine, queueing budget
        assert plan_key(prov.provision(specs, ctx.profiles, ctx.hw)) \
            == plan_key(scalar)


def test_budget_terms_batched_matches_scalar_in_cluster():
    """The VecCluster's cached per-entry budget thresholds equal the
    scalar `BudgetModel.budget_ms` (and the batched `budget_ms_vec`)
    to <= 1e-9 for both modes."""
    from repro.core.queueing import resolve
    rng = np.random.default_rng(7)
    profiles = _profiles()
    for budget in ("half", "queueing"):
        bm = resolve(budget)
        cl = pmv.VecCluster(V5E, budget=budget)
        entries = []
        q = cl.add_device()
        for i in range(6):
            m = str(rng.choice(["light", "mid", "heavy"]))
            s = WorkloadSpec(f"W{i}", m, float(rng.uniform(60, 400)),
                             float(rng.uniform(5, 300)))
            b = int(rng.integers(1, 33))
            cl.add_entry(q, s, profiles[m], b, 0.2)
            entries.append((s, b))
        ref = np.array([bm.budget_ms(s.slo_ms, s.rate_rps, b)
                        for (s, b) in entries])
        got = cl.budget_ms[0, :len(entries)]
        np.testing.assert_allclose(got, ref, **TOL)
        vec = bm.budget_ms_vec(
            np.array([s.slo_ms for s, _ in entries]),
            np.array([s.rate_rps for s, _ in entries]),
            np.array([float(b) for _, b in entries]))
        np.testing.assert_allclose(vec, ref, **TOL)


@pytest.mark.parametrize("backend", BACKENDS)
def test_ffd_and_online_engines_identical(backend):
    rng = np.random.default_rng(6)
    profiles = _profiles()
    cfg = PlannerConfig(engine="vec", backend=backend)
    for _ in range(15):
        specs = random_specs(rng)
        try:
            a = B.provision_ffd(specs, profiles, V5E, use_alloc_gpus=True,
                                engine="scalar")
        except prov.InfeasibleError:
            continue
        b = B.provision_ffd(specs, profiles, V5E, use_alloc_gpus=True,
                            config=cfg)
        assert plan_key(b) == plan_key(a)
        # online arrival of one extra workload
        extra = WorkloadSpec("EXTRA", "mid", 250.0, 25.0)
        base = prov.provision(specs, profiles, V5E)
        pa = prov.add_workload(base, extra, profiles, V5E, engine="scalar")
        pb = prov.add_workload(base, extra, profiles, V5E, config=cfg)
        assert sorted(plan_key(pa)[0]) == sorted(plan_key(pb)[0])


def test_predicted_violations_consistent_with_metrics():
    profiles = _profiles()
    specs = [WorkloadSpec("W0", "mid", 150.0, 40.0),
             WorkloadSpec("W1", "light", 200.0, 30.0)]
    plan = prov.provision(specs, profiles, V5E)
    # Alg. 2 guarantees the non-throttled regime meets T_slo/2
    assert prov.predicted_violations(plan, profiles, V5E) == []

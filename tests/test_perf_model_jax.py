"""JAX backend vs the numpy oracle: the grant loop and the simulator.

The numerical contract (docs/reproduction-notes.md, deviation 5): the
jitted twins agree with the numpy hot path to <= 1e-6 relative — XLA
reassociates sums and fuses multiply-adds, so bitwise equality is out of
scope — while every plan-level DECISION (placements, batches, grid-
snapped allocations, device counts) is bit-identical, because Alg. 1/2
thresholds carry 1e-9 epsilons that dwarf the float divergence.
"""
import numpy as np
import pytest

from repro.core import perf_model_vec as pmv
from repro.core import provisioner as prov
from repro.core.queueing import resolve
from repro.core.types import V5E, PlannerConfig, WorkloadSpec
from tests.test_perf_model_vec import (
    _profiles, plan_key, random_specs)

pytestmark = pytest.mark.jax   # needs the JAX toolchain (jax CI job)

TOL = dict(rtol=1e-6, atol=1e-9)


# ---------------------------------------------------------------------------
# Algorithm 2 against every open device: lax.while_loop twin
# ---------------------------------------------------------------------------

def _alloc_all_pairs():
    """(numpy, jax) `VecCluster.alloc_all` results for one newcomer on
    randomized resident mixes (trials where Theorem 1 refuses the
    newcomer are skipped)."""
    profiles = _profiles()
    rng = np.random.default_rng(2)
    for trial in range(40):
        cls = {be: pmv.VecCluster(V5E, budget="queueing", backend=be)
               for be in ("numpy", "jax")}
        for q in range(int(rng.integers(1, 5))):
            for cl in cls.values():
                cl.add_device()
            for i in range(int(rng.integers(0, 4))):
                m = str(rng.choice(["light", "mid", "heavy"]))
                s = WorkloadSpec(f"R{q}_{i}", m,
                                 float(rng.uniform(80, 400)), 30.0)
                b = int(rng.integers(1, 17))
                r = float(rng.choice([0.1, 0.2, 0.25]))
                for cl in cls.values():
                    cl.add_entry(q, s, profiles[m], b, r)
        m = str(rng.choice(["light", "mid", "heavy"]))
        s_new = WorkloadSpec("NEW", m, float(rng.uniform(80, 400)),
                             float(rng.uniform(5, 60)))
        try:
            b = prov.appropriate_batch(s_new, profiles[m], V5E)
            rl = prov.resource_lower_bound(s_new, profiles[m], V5E, b)
        except prov.InfeasibleError:
            continue
        yield (cls["numpy"].alloc_all(s_new, profiles[m], b, rl),
               cls["jax"].alloc_all(s_new, profiles[m], b, rl))


def _assert_oracle_bits(pairs):
    """Same feasibility verdicts and the oracle's exact allocation and
    Alg. 1 score bits: allocations are +r_unit grid points snapped by
    round(x, 10), and the backends must land on the SAME points."""
    checked = 0
    for (fa, rra, rna, ia), (fb, rrb, rnb, ib) in pairs:
        np.testing.assert_array_equal(fb, fa)
        np.testing.assert_array_equal(rrb[fa], rra[fa])
        np.testing.assert_array_equal(rnb[fa], rna[fa])
        np.testing.assert_array_equal(ib, ia)
        checked += 1
    assert checked > 10


def test_alloc_all_jax_matches_numpy_randomized():
    _assert_oracle_bits(_alloc_all_pairs())


def test_alloc_all_jax_exact_under_device_float_noise(monkeypatch):
    """A TPU emulates float64 at ~2**-48 relative, so every array that
    crosses into the grant loop arrives a few ulps off.  The loop's
    decisions must not move, and what `alloc_all_jax` hands back must
    still be the numpy oracle's exact bits."""
    from repro.core import perf_model_jax as pmj
    real = pmj._alloc_all_jit
    noise = np.random.default_rng(7)

    def lossy(v):
        v = np.asarray(v)
        if v.dtype != np.float64:
            return v
        return v * (1.0 + noise.choice([-1.0, 1.0], v.shape) * 2.0 ** -47)

    def on_lossy_device(hw, *args):
        return real(hw, *(tuple(lossy(a) for a in x)
                          if isinstance(x, tuple) else lossy(x)
                          for x in args))

    monkeypatch.setattr(pmj, "_alloc_all_jit", on_lossy_device)
    _assert_oracle_bits(_alloc_all_pairs())


# ---------------------------------------------------------------------------
# The grant loop's device-resident cluster state
# ---------------------------------------------------------------------------

def _bits(a):
    return np.ascontiguousarray(a, dtype=np.float64).view(np.int64)


def _random_edit(rng, cls, profiles, step):
    """One random mutation, the same on every cluster of ``cls``: a new
    device, an entry (often on the first device, so that its row
    outgrows four slots), a new allocation row, a departure or a budget
    swap."""
    ref = cls[0]
    live = [q for q in range(ref.d) if ref.n[q]]
    op = rng.choice(["device", "entry", "entry", "entry", "r", "remove",
                     "budget"])
    if op == "device" or not ref.d:
        for cl in cls:
            cl.add_device()
    elif op == "entry" or not live:
        q = 0 if rng.random() < 0.4 else int(rng.integers(0, ref.d))
        m = str(rng.choice(["light", "mid", "heavy"]))
        s = WorkloadSpec(f"R{step}", m, float(rng.uniform(80, 400)),
                         float(rng.uniform(5, 60)))
        b = int(rng.integers(1, 17))
        r = float(rng.choice([0.05, 0.1, 0.2]))
        for cl in cls:
            cl.add_entry(q, s, profiles[m], b, r)
    elif op == "r":
        q = int(rng.choice(live))
        row = rng.choice([0.05, 0.1, 0.15, 0.2, 0.25], size=int(ref.n[q]))
        for cl in cls:
            cl.set_row_r(q, row)
    elif op == "remove":
        q = int(rng.choice(live))
        i = int(rng.integers(0, ref.n[q]))
        for cl in cls:
            cl.remove_entry(q, i)
    else:
        bm = (resolve("queueing").with_burstiness(float(rng.uniform(0.5, 2)))
              if rng.random() < 0.7 else resolve("half"))
        for cl in cls:
            cl.set_budget(bm)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_mirror_tracks_random_edits_bit_for_bit(seed):
    """After every random edit, the device copy that the incremental
    updates built equals the packed host state bit for bit, whichever
    way it got there (dirty-row block or whole state, across capacity
    doublings), and the jax grant loop gives the numpy oracle's answer."""
    from repro.core import perf_model_jax as pmj
    profiles = _profiles()
    rng = np.random.default_rng(seed)
    cl_np, cl_jx = (pmv.VecCluster(V5E, budget="queueing", backend=be)
                    for be in ("numpy", "jax"))
    cl_np.add_device()
    cl_jx.add_device()
    paths = set()
    for step in range(120):
        _random_edit(rng, (cl_np, cl_jx), profiles, step)
        dirty = int(cl_jx.dirty.sum())
        whole = cl_jx.mirror is None or dirty > pmj.K_ROWS
        m = str(rng.choice(["light", "mid", "heavy"]))
        s_new = WorkloadSpec("NEW", m, float(rng.uniform(80, 400)),
                             float(rng.uniform(5, 60)))
        try:
            b = prov.appropriate_batch(s_new, profiles[m], V5E)
            rl = prov.resource_lower_bound(s_new, profiles[m], V5E, b)
        except prov.InfeasibleError:
            sent = pmj.sync(cl_jx)
        else:
            _assert_oracle_bits_each(
                cl_np.alloc_all(s_new, profiles[m], b, rl),
                cl_jx.alloc_all(s_new, profiles[m], b, rl))
            sent = cl_jx.rows_sent
        assert sent == (cl_jx.mask.shape[0] if whole else dirty)
        paths.add("whole" if whole else "rows" if dirty else "none")
        assert not cl_jx.dirty.any()
        np.testing.assert_array_equal(_bits(cl_jx.mirror),
                                      _bits(pmj.pack(cl_jx)))
    assert cl_jx.mask.shape[0] >= 16 and cl_jx.mask.shape[1] >= 8
    assert paths == {"whole", "rows"} or paths == {"whole", "rows", "none"}


def test_one_pass_cluster_packs_and_places_as_the_loop():
    """An arrival's cluster built in one pass packs, for the device, to
    the loop-built cluster's bits, and a jax-backend `add_workload` on a
    standing m=100 plan returns the numpy backend's plan."""
    from repro.core import perf_model_jax as pmj
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    from tests.test_perf_model_vec import loop_cluster, random_residents
    devices = random_residents(np.random.default_rng(11), 20, (1, 4, 5, 9))
    for budget in ("half", "queueing"):
        ref = loop_cluster(devices, budget, backend="jax")
        got = pmv.VecCluster.from_devices(V5E, devices, budget=budget,
                                          backend="jax")
        np.testing.assert_array_equal(_bits(pmj.pack(got)),
                                      _bits(pmj.pack(ref)))
    ctx = fitted_context("tpu-v5e")
    specs = synthetic_workloads(101, seed=2)
    plan = prov.provision(specs[:100], ctx.profiles, ctx.hw)
    for spec in specs[100:] + [WorkloadSpec("EXTRA", specs[0].model,
                                            specs[0].slo_ms, 25.0)]:
        np_plan, jx_plan = (
            prov.add_workload(plan, spec, ctx.profiles, ctx.hw,
                              config=PlannerConfig(backend=be))
            for be in ("numpy", "jax"))
        assert plan_key(jx_plan) == plan_key(np_plan)
        plan = np_plan


def _assert_oracle_bits_each(a, b):
    (fa, rra, rna, ia), (fb, rrb, rnb, ib) = a, b
    np.testing.assert_array_equal(fb, fa)
    np.testing.assert_array_equal(rrb[fa], rra[fa])
    np.testing.assert_array_equal(rnb[fa], rna[fa])
    np.testing.assert_array_equal(ib, ia)


def test_steady_provision_sends_one_row_per_call(monkeypatch, tmp_path):
    """Under a recording trace, the ``rows_sent`` counter of each jax
    grant-loop call of a provision: the whole state on the first call
    and after each capacity change, else the one row that Alg. 1 changed
    since the previous call, never more than K_ROWS."""
    from repro.core import perf_model_jax as pmj
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    from tests.test_trace_spans import _traced
    ctx = fitted_context("tpu-v5e")
    specs = synthetic_workloads(60, seed=1)
    shapes = []
    real = pmv.VecCluster.alloc_all

    def alloc_all(self, *args):
        shapes.append(self.mask.shape)
        return real(self, *args)
    monkeypatch.setattr(pmv.VecCluster, "alloc_all", alloc_all)
    _, spans = _traced(lambda: prov.provision(
        specs, ctx.profiles, ctx.hw, config=PlannerConfig(backend="jax")),
        tmp_path)
    sent = [s[3]["rows_sent"] for s in spans if s[2] == "alloc_all"]
    assert len(sent) == len(shapes) >= 60
    assert shapes[-1][0] >= 32
    steady = 0
    for i, (n, shape) in enumerate(zip(sent, shapes)):
        if i == 0 or shape != shapes[i - 1]:
            assert n == shape[0], (i, shape)
        else:
            assert n == 1 <= pmj.K_ROWS, (i, n)
            steady += 1
    assert steady > len(sent) / 2


class _CountedReads:
    """A device array whose reads by the host are logged in ``log`` as
    ``(output index, method)``."""

    def __init__(self, a, i, log):
        self._a, self._i, self._log = a, i, log

    def __array__(self, *args, **kwargs):
        self._log.append((self._i, "__array__"))
        return self._a.__array__(*args, **kwargs)

    def __int__(self):
        self._log.append((self._i, "__int__"))
        return int(self._a)

    def copy_to_host_async(self):
        self._log.append((self._i, "copy_to_host_async"))
        self._a.copy_to_host_async()


def test_each_grant_loop_call_reads_one_array_off_a_trace(monkeypatch):
    """Off a trace, each jax grant-loop call of a provision copies ONE
    array to the host, the packed answer, and leaves the iteration count
    on the device; the plan is the numpy backend's."""
    from repro.core import perf_model_jax as pmj
    from repro.core import trace
    from repro.core.experiments import fitted_context
    from repro.serving.workload import synthetic_workloads
    real = pmj._alloc_all_jit
    calls = []

    def counted(*args, **kwargs):
        calls.append([])
        return tuple(_CountedReads(a, i, calls[-1])
                     for i, a in enumerate(real(*args, **kwargs)))
    monkeypatch.setattr(pmj, "_alloc_all_jit", counted)
    ctx = fitted_context("tpu-v5e")
    specs = synthetic_workloads(20, seed=0)
    assert not trace.active()
    jx = prov.provision(specs, ctx.profiles, ctx.hw,
                        config=PlannerConfig(backend="jax"))
    ref = prov.provision(specs, ctx.profiles, ctx.hw)
    assert len(calls) >= 20
    assert all(c == [(0, "__array__")] for c in calls), calls
    assert plan_key(jx) == plan_key(ref)


def test_numpy_planner_leaves_jax_module_and_x64_alone():
    """A numpy-backend provision, in a fresh process, imports no jax
    twin and leaves float64 off: the mirror lives on the jax side."""
    import os
    import subprocess
    import sys
    code = (
        "import sys, jax\n"
        "from repro.core import provisioner as prov\n"
        "from repro.core.experiments import fitted_context\n"
        "from repro.serving.workload import synthetic_workloads\n"
        "ctx = fitted_context('tpu-v5e')\n"
        "plan = prov.provision(synthetic_workloads(20, seed=0),"
        " ctx.profiles, ctx.hw)\n"
        "assert plan.n_gpus > 0\n"
        "assert 'repro.core.perf_model_jax' not in sys.modules\n"
        "assert not jax.config.jax_enable_x64\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH")) if p)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(__file__)),
                   timeout=300)


# ---------------------------------------------------------------------------
# Plan identity: backend="jax" end to end
# ---------------------------------------------------------------------------

def test_provision_backend_jax_plans_identical_randomized():
    profiles = _profiles()
    rng = np.random.default_rng(3)
    compared = 0
    for _ in range(25):
        specs = random_specs(rng)
        try:
            ref = prov.provision(specs, profiles, V5E)
        except prov.InfeasibleError:
            continue
        jx = prov.provision(specs, profiles, V5E,
                            config=PlannerConfig(backend="jax"))
        assert plan_key(jx) == plan_key(ref)
        compared += 1
    assert compared > 8


@pytest.mark.parametrize("budget", ["half", "queueing"])
def test_provision_backend_jax_identical_on_paper_workload(budget):
    from repro.core.experiments import fitted_context
    from repro.serving.workload import twelve_workloads
    ctx = fitted_context()
    specs = twelve_workloads()
    ref = prov.provision(specs, ctx.profiles, ctx.hw, budget=budget)
    jx = prov.provision(specs, ctx.profiles, ctx.hw,
                        config=PlannerConfig(budget=budget, backend="jax"))
    assert plan_key(jx) == plan_key(ref)


def test_replicate_no_split_plan_identical_on_jax():
    """replicate=True on a feasible workload set must be a no-op (k=1
    everywhere) on BOTH backends, and both land on the same plan."""
    profiles = _profiles()
    specs = [WorkloadSpec("W0", "mid", 150.0, 40.0),
             WorkloadSpec("W1", "light", 200.0, 30.0),
             WorkloadSpec("W2", "heavy", 300.0, 10.0)]
    ref = prov.provision(specs, profiles, V5E)
    for backend in ("numpy", "jax"):
        p = prov.provision(specs, profiles, V5E,
                           config=PlannerConfig(replicate=True,
                                                backend=backend))
        assert plan_key(p) == plan_key(ref)
        assert all("#" not in pl.workload.name for pl in p.placements)


# ---------------------------------------------------------------------------
# Simulator backend="jax": bulk table build parity
# ---------------------------------------------------------------------------

def test_physics_table_values_match_numpy():
    from repro.serving import physics
    from repro.serving import physics_jax
    rng = np.random.default_rng(4)
    for n in (1, 2, 3, 5):
        R = int(rng.integers(4, 64))
        shape = (R, n)
        args = (rng.uniform(1e6, 1e8, shape),    # d_load
                rng.uniform(1e5, 1e7, shape),    # d_fb
                rng.uniform(1e9, 1e12, shape),   # flops_i
                rng.uniform(1e7, 1e9, shape),    # w_bytes
                rng.uniform(1e5, 1e7, shape),    # a_bytes
                rng.integers(20, 400, shape).astype(float))   # n_kern
        b = rng.integers(1, 33, shape).astype(float)
        r = rng.uniform(0.05, 0.6, shape)
        ref = physics.device_state_arrays(*args, b, r, n, V5E)
        got = physics_jax.table_values(*args, b, r, n, V5E)
        for name, a, g in zip(("t_load", "t_sched", "t_act", "t_feedback",
                               "freq"),
                              (ref.t_load, ref.t_sched, ref.t_act,
                               ref.t_feedback, ref.freq), got):
            np.testing.assert_allclose(g, a, err_msg=name, **TOL)


def test_simulate_full_backend_jax_matches_numpy():
    from repro.core.experiments import fitted_context
    from repro.serving.simulator import simulate_full
    from repro.serving.workload import models, synthetic_workloads
    ctx = fitted_context("tpu-v5e")
    specs = synthetic_workloads(30, 0)
    plan = prov.provision(specs, ctx.profiles, ctx.hw)
    mods = models()
    res_n = simulate_full(plan, mods, ctx.hw, duration_s=3.0, seed=0)
    res_j = simulate_full(plan, mods, ctx.hw, duration_s=3.0, seed=0,
                          backend="jax")
    sb = {s.name: s for s in specs}
    assert res_j.violations(sb) == res_n.violations(sb)
    assert set(res_j.request_latencies) == set(res_n.request_latencies)
    for name, lat_n in res_n.request_latencies.items():
        lat_j = res_j.request_latencies[name]
        assert lat_j.shape == lat_n.shape
        np.testing.assert_allclose(lat_j, lat_n, **TOL)


def test_simulator_scalar_engine_rejects_jax_backend():
    from repro.core.experiments import fitted_context
    from repro.serving.simulator import simulate_full
    from repro.serving.workload import models, synthetic_workloads
    ctx = fitted_context("tpu-v5e")
    specs = synthetic_workloads(5, 0)
    plan = prov.provision(specs, ctx.profiles, ctx.hw)
    with pytest.raises(ValueError):
        simulate_full(plan, models(), ctx.hw, duration_s=0.5,
                      engine="scalar", backend="jax")
    with pytest.raises(ValueError):
        simulate_full(plan, models(), ctx.hw, duration_s=0.5,
                      backend="tensorflow")

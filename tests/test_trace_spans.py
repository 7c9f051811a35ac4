"""Profiler spans inside the planner and simulator (`repro.core.trace`).

Under `jax.profiler.trace` (here on the CPU, m=30): the ``igniter.*``
spans nest as `docs/observability.md` lists them; the ``iters`` counter
of every jax grant-loop call equals the numpy loop's count; plans are
the same with the profiler on and off; off a trace the jax path never
fetches the counter; the controller's phase walls are its spans.
"""
import glob
import os

import pytest

from repro.core import provisioner as prov
from repro.core import trace
from repro.core.experiments import fitted_context
from repro.core.types import PlannerConfig
from repro.serving import traces
from repro.serving.controller import Controller
from repro.serving.simulator import simulate_full, simulate_plan
from repro.serving.telemetry import Telemetry
from repro.serving.workload import models, synthetic_workloads

pytestmark = pytest.mark.jax

M = 30
JAX = PlannerConfig(backend="jax")

# span -> the spans it may sit directly under (None: the top)
PARENTS = {
    "provision": {None},
    "prepare": {"provision", "add_workload"},
    "alloc_all": {"provision", "add_workload"},
    "alloc_all.launch": {"alloc_all"},
    "alloc_all.fetch": {"alloc_all"},
    "alloc_all.replay": {"alloc_all"},
    "place": {"provision", "add_workload"},
    "add_workload": {None},
    "cluster_build": {"add_workload"},
    "remove_workload": {None},
    "simulate": {None},
    "sim.setup": {"simulate"},
    "sim.tables": {"simulate"},
    "sim.passes": {"simulate"},
    "sim.finalize": {"simulate"},
}


@pytest.fixture(scope="module")
def fleets():
    ctxs = [fitted_context("tpu-v5e"), fitted_context("tpu-v4")]
    return {c.hw.name: c.profiles for c in ctxs}, [c.hw for c in ctxs]


@pytest.fixture(scope="module")
def specs():
    return synthetic_workloads(M, seed=0)


def _traced(fn, log_dir):
    """``fn()`` under the profiler; its result and its program spans
    ``(start_ns, end_ns, name, counters)``, by start."""
    import jax
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(log_dir)):
        out = fn()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace.PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(trace.PREFIX):],
                                      dict(e.stats)))
    return out, sorted(spans, key=lambda s: (s[0], -s[1]))


def _parents(spans):
    """``(name, parent name)`` of every span; the parent of a top span
    is None."""
    stack, out = [], []
    for a, b, name, _ in spans:
        while stack and stack[-1][1] <= a:
            stack.pop()
        out.append((name, stack[-1][2] if stack else None))
        stack.append((a, b, name))
    return out


def _plan_key(plan):
    return [(p.workload.name, p.gpu, round(p.r, 9), p.batch)
            for p in plan.placements]


@pytest.fixture(scope="module")
def jax_run(fleets, specs, tmp_path_factory):
    """One provision, one departure and arrival, and one simulation, all
    on the jax backend under one trace."""
    profiles_by_hw, hardware = fleets

    def work():
        plan, hw = prov.provision_cheapest(specs, profiles_by_hw, hardware,
                                           config=JAX)
        edited = prov.remove_workload(plan, specs[0].name)
        edited = prov.add_workload(edited, specs[0], profiles_by_hw[hw.name],
                                   hw, config=JAX)
        simulate_full(edited, models(), hw, duration_s=1.0, seed=3,
                      backend="jax")
        return plan, hw
    return _traced(work, tmp_path_factory.mktemp("jax_run"))


def test_span_tree_nests_as_documented(jax_run, fleets):
    (_, _), spans = jax_run
    pairs = _parents(spans)
    for name, parent in pairs:
        assert parent in PARENTS[name], (name, parent)
    assert {n for n, _ in pairs} == set(PARENTS)
    count = {n: sum(1 for s in spans if s[2] == n) for n in PARENTS}
    assert count["provision"] == len(fleets[1])          # one per fleet
    assert count["add_workload"] == 1 and count["simulate"] == 1
    # every grant-loop call: one launch, fetch and replay; one place each
    for child in ("alloc_all.launch", "alloc_all.fetch", "alloc_all.replay",
                  "place"):
        assert count[child] == count["alloc_all"], child
    assert count["alloc_all"] >= M


def test_iters_counter_equals_the_numpy_loop(jax_run, fleets, specs,
                                             tmp_path):
    _, spans = jax_run
    profiles_by_hw, hardware = fleets
    _, np_spans = _traced(
        lambda: prov.provision_cheapest(specs, profiles_by_hw, hardware),
        tmp_path)

    def iters(ss, until):
        calls = [s for s in ss if s[2] == "alloc_all" and s[1] <= until]
        return [s[3].get("iters") for s in calls]
    # the jax run's provision is its first two top spans
    end = max(s[1] for s in spans if s[2] == "provision")
    got = iters(spans, end)
    want = iters(np_spans, float("inf"))
    assert len(got) == len(want) >= M
    assert all(isinstance(n, int) and n >= 1 for n in want)
    assert got == want


def test_plans_identical_with_the_profiler_on_and_off(jax_run, fleets,
                                                      specs):
    (plan_on, hw_on), _ = jax_run
    profiles_by_hw, hardware = fleets
    plan_off, hw_off = prov.provision_cheapest(specs, profiles_by_hw,
                                               hardware, config=JAX)
    plan_np, hw_np = prov.provision_cheapest(specs, profiles_by_hw, hardware)
    assert hw_on.name == hw_off.name == hw_np.name
    assert _plan_key(plan_on) == _plan_key(plan_off) == _plan_key(plan_np)


class _Trap:
    """A device array that must not reach the host."""

    def __array__(self, *args, **kwargs):
        raise AssertionError("the iteration count was fetched")

    def __int__(self):
        raise AssertionError("the iteration count was fetched")

    def copy_to_host_async(self):
        raise AssertionError("the iteration count was fetched")


def test_jax_path_leaves_the_counter_unfetched_off_a_trace(
        fleets, specs, monkeypatch, tmp_path):
    from repro.core import perf_model_jax as pmj
    from repro.core import perf_model_vec as pmv
    real = pmj._alloc_all_jit

    def trapped(*args, **kwargs):
        return real(*args, **kwargs)[0], _Trap()
    monkeypatch.setattr(pmj, "_alloc_all_jit", trapped)
    seen = []
    real_alloc = pmv.VecCluster.alloc_all

    def alloc_all(self, *args):
        out = real_alloc(self, *args)
        seen.append(self.iters)
        return out
    monkeypatch.setattr(pmv.VecCluster, "alloc_all", alloc_all)
    profiles_by_hw, hardware = fleets
    assert not trace.active()
    prov.provision_cheapest(specs[:10], profiles_by_hw, hardware,
                            config=JAX)
    assert seen and all(n is None for n in seen)
    with pytest.raises(AssertionError, match="was fetched"):
        _traced(lambda: prov.provision_cheapest(
            specs[:10], profiles_by_hw, hardware, config=JAX), tmp_path)


def test_controller_walls_are_its_spans(fleets, tmp_path):
    profiles_by_hw, hardware = fleets
    hw = hardware[0]
    specs = synthetic_workloads(8, seed=0)
    plan = prov.provision(specs, profiles_by_hw[hw.name], hw)
    tr = traces.diurnal([s.name for s in specs], 3000.0, peak=2.0)
    tel = Telemetry()
    ctl = Controller(plan, profiles_by_hw[hw.name], hw, telemetry=tel)
    _, spans = _traced(lambda: simulate_plan(
        plan, models(), hw, duration_s=3.0, seed=0, trace=tr,
        adjust_fn=ctl, adjust_scope="cluster", adjust_period_s=1.0,
        telemetry=tel), tmp_path)
    pairs = _parents(spans)
    ticks = [s for s in spans if s[2] == "sim.adjust"]
    assert len(ticks) == ctl.n_ticks == 2
    for phase, wall in (("ctl.probe", "ctl_probe"), ("ctl.solve", "ctl_solve"),
                        ("ctl.apply", "ctl_apply"),
                        ("sim.adjust", "sim_adjust")):
        ours = [s for s in spans if s[2] == phase]
        assert len(ours) == len(ticks), phase
        span_ms = sum(b - a for a, b, _, _ in ours) * 1e-6
        assert tel.walls[wall] == pytest.approx(span_ms, rel=0.05, abs=0.05)
    assert {p for n, p in pairs if n.startswith("ctl.")} == {"sim.adjust"}

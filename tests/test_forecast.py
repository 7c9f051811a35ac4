"""Predictive tier (docs/control-plane.md): the trend/seasonal rate
forecaster, forecast-armed Sec. 4.2 shadows, and the transactional
arming paths.

Property tests run under hypothesis when available and skip cleanly on
bare environments (`tests._hypothesis_stub`); every property also has a
plain seed-loop twin alongside so the invariants stay pinned without
hypothesis installed:

  * constant-rate input — deterministic or Poisson, any seed — NEVER
    breaches the forecast band (the no-false-positive contract the
    dynamic_sweep no-drift gate rides on);
  * a linear ramp's forecast is monotone and LEADS the smoothed rate;
  * a periodic series recovers its period within one monitor tick;
  * armed reservations never overcommit a device past r = 1.0;
  * a placement failure mid-edit restores the plan, the vec mirror,
    and the armed shadow book bit-identically (PR 8's checkpoint).
"""
import dataclasses
import math

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:      # bare env: property tests skip, unit tests run
    from tests._hypothesis_stub import given, settings, st

from repro.core import provisioner as prov
from repro.core.experiments import fitted_context
from repro.serving.controller import (ArrivalEstimator, ControllerConfig,
                                      Reconciler)
from repro.serving.workload import twelve_workloads

WINDOW_MS = 1000.0
FC = ControllerConfig(forecast=True)


def _poisson_window(rng, rate_rps, window_ms=WINDOW_MS, t0=0.0):
    n = rng.poisson(rate_rps * window_ms / 1000.0)
    return t0 + np.sort(rng.uniform(0.0, window_ms, size=n))


def _det_window(rate_rps, window_ms=WINDOW_MS, t0=0.0):
    period = 1000.0 / max(rate_rps, 1e-9)
    return t0 + np.arange(period / 2.0, window_ms, period)


def _breach(est, plan_rate, cfg=FC):
    """The exact trigger `Reconciler._forecast_pass` evaluates."""
    f = est.forecast_rps(cfg.forecast_horizon)
    band = max(cfg.forecast_band,
               cfg.forecast_sigmas * est.rate_sigma() / plan_rate)
    return f / plan_rate > 1.0 + band


@pytest.fixture(scope="module")
def ctx12():
    ctx = fitted_context()
    plan = prov.provision(twelve_workloads(), ctx.profiles, ctx.hw)
    return ctx, plan


def _estimators(plan, cfg=None):
    return {p.workload.name: ArrivalEstimator(p.workload.rate_rps, cfg)
            for p in plan.placements}


# ---------------------------------------------------------------------------
# Never-fires: constant-rate input stays forecast-silent
# ---------------------------------------------------------------------------

def test_constant_deterministic_never_breaches():
    for rate in (8.0, 30.0, 60.0, 250.0):
        est = ArrivalEstimator(rate, FC)
        for k in range(40):
            est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
            assert not _breach(est, rate), (rate, k)


def test_constant_poisson_never_breaches_seeds():
    """Seed-loop twin of the property below: 5 rates x 20 seeds x 50
    ticks of pure counting noise, not one band breach."""
    for rate in (5.0, 20.0, 60.0, 120.0, 300.0):
        for seed in range(20):
            rng = np.random.default_rng(seed)
            est = ArrivalEstimator(rate, FC)
            for k in range(50):
                est.observe(_poisson_window(rng, rate, t0=k * WINDOW_MS),
                            WINDOW_MS)
                assert not _breach(est, rate), (rate, seed, k)


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(3.0, 400.0))
def test_constant_poisson_never_breaches_property(seed, rate):
    rng = np.random.default_rng(seed)
    est = ArrivalEstimator(rate, FC)
    for k in range(40):
        est.observe(_poisson_window(rng, rate, t0=k * WINDOW_MS),
                    WINDOW_MS)
        assert not _breach(est, rate), (seed, rate, k)


def test_forecast_reconciler_noop_on_poisson(ctx12):
    """Closed over the real reconciler: forecast=True + noise-only input
    never reconfigures, never arms, and leaves the plan object itself
    untouched (the dynamic_sweep forecast no-drift gate)."""
    ctx, plan = ctx12
    for seed in range(3):
        rng = np.random.default_rng(seed)
        rec = Reconciler(plan, ctx.profiles, ctx.hw, cfg=FC)
        ests = _estimators(plan, FC)
        for k in range(25):
            for name, est in ests.items():
                rate = rec.targets[name].rate_rps
                est.observe(_poisson_window(rng, rate, t0=k * WINDOW_MS),
                            WINDOW_MS)
            assert not rec.reconcile(k + 1.0, ests)
        assert rec.edits == [] and rec.armed == {} and rec.plan is plan


# ---------------------------------------------------------------------------
# Ramp: monotone extrapolation that leads the smoothed rate
# ---------------------------------------------------------------------------

def _ramp_forecasts(rate0, slope_frac, n=20):
    est = ArrivalEstimator(rate0, FC)
    out = []
    for k in range(n):
        rate = rate0 * (1.0 + slope_frac * k)
        est.observe(_det_window(rate, t0=k * WINDOW_MS), WINDOW_MS)
        out.append((est.forecast_rps(FC.forecast_horizon), est.rate_rps))
    return out


def test_linear_ramp_forecast_monotone_and_leads():
    for slope in (0.02, 0.05, 0.10):
        hist = _ramp_forecasts(60.0, slope)
        f = [x[0] for x in hist]
        # monotone after the EWMA warm-up, and always >= smoothed rate
        assert all(b >= a - 1e-9 for a, b in zip(f[3:], f[4:])), slope
        assert all(fk >= rk for fk, rk in hist), slope
        # the horizon extrapolation actually LEADS: by mid-ramp the
        # forecast exceeds the current true rate
        assert f[10] > 60.0 * (1.0 + slope * 10)


@settings(max_examples=40, deadline=None)
@given(st.floats(0.01, 0.15), st.floats(20.0, 200.0))
def test_linear_ramp_forecast_monotone_property(slope, rate0):
    hist = _ramp_forecasts(rate0, slope)
    f = [x[0] for x in hist]
    assert all(b >= a - 1e-9 for a, b in zip(f[3:], f[4:]))
    assert all(fk >= rk - 1e-9 for fk, rk in hist)


# ---------------------------------------------------------------------------
# Periodicity: autocorrelation period scan
# ---------------------------------------------------------------------------

def _periodic_estimator(period, n=64, base=100.0, amp=0.8, noise_seed=None):
    est = ArrivalEstimator(base, FC)
    rng = (np.random.default_rng(noise_seed)
           if noise_seed is not None else None)
    for k in range(n):
        rate = base * (1.0 + amp * math.sin(2.0 * math.pi * k / period))
        w = (_poisson_window(rng, rate, t0=k * WINDOW_MS) if rng is not None
             else _det_window(rate, t0=k * WINDOW_MS))
        est.observe(w, WINDOW_MS)
    return est


def test_periodic_series_recovers_period_within_one_tick():
    for period in (6, 10, 16):
        est = _periodic_estimator(period)
        got = est.detect_period()
        assert got is not None and abs(got - period) <= 1, (period, got)


def test_periodic_series_recovers_period_under_noise():
    for period in (8, 12):
        est = _periodic_estimator(period, noise_seed=0)
        got = est.detect_period()
        assert got is not None and abs(got - period) <= 1, (period, got)


def test_constant_poisson_detects_no_period():
    for seed in range(10):
        rng = np.random.default_rng(seed)
        est = ArrivalEstimator(80.0, FC)
        for k in range(64):
            est.observe(_poisson_window(rng, 80.0, t0=k * WINDOW_MS),
                        WINDOW_MS)
        assert est.detect_period() is None, seed


@settings(max_examples=25, deadline=None)
@given(st.integers(5, 20))
def test_periodic_recovery_property(period):
    est = _periodic_estimator(period)
    got = est.detect_period()
    assert got is not None and abs(got - period) <= 1


def test_seasonal_lookup_raises_forecast_before_peak():
    """One period of history behind the horizon: the forecast at the
    trough's leading edge must already see next cycle's peak."""
    period = 10
    est = _periodic_estimator(period, n=35)
    # history ends at k=34 (sin phase 0.4 of cycle); the seasonal lookup
    # one period back at t+horizon covers the coming rise
    f = est.forecast_rps(FC.forecast_horizon)
    assert est.detect_period() is not None
    assert f >= est.rate_rps


# ---------------------------------------------------------------------------
# Spike: the reconciler fires, arms shadows, and never overcommits
# ---------------------------------------------------------------------------

def _drive_spike(rec, ests, scale=2.5, warm=4, hot=3):
    k = 0
    for _ in range(warm):
        for name, est in ests.items():
            est.observe(_det_window(rec.targets[name].rate_rps,
                                    t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
        k += 1
    for _ in range(hot):
        for name, est in ests.items():
            est.observe(_det_window(rec.targets[name].rate_rps * scale,
                                    t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
        k += 1
    return k


def _assert_no_overcommit(rec):
    """Plan r + armed reservations <= 1.0 on every device, exactly."""
    by_gpu = {}
    gpu_of = {}
    for p in rec.plan.placements:
        by_gpu.setdefault(p.gpu, []).append(p.r)
        gpu_of[p.workload.name] = p.gpu
    for name, sr in rec.armed.items():
        assert name in gpu_of, f"armed orphan {name}"
        by_gpu[gpu_of[name]].append(sr)
    for gpu, rs in by_gpu.items():
        assert math.fsum(rs) <= 1.0 + 1e-9, (gpu, rs)


def test_spike_fires_forecast_and_arms_shadows(ctx12):
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw, cfg=FC)
    ests = _estimators(plan, FC)
    _drive_spike(rec, ests)
    actions = {e.action for e in rec.edits}
    assert "forecast" in actions
    assert "shadow_arm" in actions
    assert rec.armed
    _assert_no_overcommit(rec)
    # the reservation book and the vec mirror share one dict BY
    # REFERENCE — placement feasibility sees every armed share
    assert rec._state is None or rec._state.shadow is rec.armed


def test_shadow_reservation_capped_by_free_share(ctx12):
    """Every granted reservation is at most shadow_extra and at most
    the device's free share at grant time."""
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw, cfg=FC)
    ests = _estimators(plan, FC)
    _drive_spike(rec, ests)
    assert rec.armed
    for name, sr in rec.armed.items():
        assert 0.0 < sr <= FC.shadow_extra + 1e-12, (name, sr)


def test_disarm_after_hold_releases_reservations(ctx12):
    """Breach-free for forecast_hold ticks with no ACTIVE shadow: the
    book empties and a shadow_disarm edit records the release."""
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw, cfg=FC)
    ests = _estimators(plan, FC)
    k = _drive_spike(rec, ests)
    assert rec.armed
    # back inside the (raised) targets: hold ticks of in-band traffic
    for _ in range(FC.forecast_hold + 2):
        for name, est in ests.items():
            est.observe(_det_window(rec.targets[name].rate_rps,
                                    t0=k * WINDOW_MS), WINDOW_MS)
        rec.reconcile(k + 1.0, ests)
        k += 1
    assert rec.armed == {}
    assert any(e.action == "shadow_disarm" for e in rec.edits)


# ---------------------------------------------------------------------------
# Transactional arming (satellite: PR 8 checkpoint covers the armed book)
# ---------------------------------------------------------------------------

def _plan_key(plan):
    return sorted((p.workload.name, p.gpu, p.r, p.batch)
                  for p in plan.placements)


def test_failed_edit_restores_plan_mirror_and_armed(ctx12, monkeypatch):
    """Inject a placement failure MID-edit, after `_resize_spec` has
    already dropped the workload's reservation: the checkpoint must
    hand back the plan, the rebuilt vec mirror, AND the armed book
    bit-identically (same dict object, same contents)."""
    ctx, plan = ctx12
    rec = Reconciler(plan, ctx.profiles, ctx.hw, cfg=FC)
    ests = _estimators(plan, FC)
    _drive_spike(rec, ests)
    assert rec.armed
    from repro.core import replication
    base = sorted(rec.armed)[0].split(replication.SEP)[0]
    est = ests[base]

    plan_before = _plan_key(rec.plan)
    armed_before = dict(rec.armed)
    armed_dict = rec.armed

    calls = {"n": 0}
    real_resize, real_remove = prov.PlanState.resize, prov.PlanState.remove

    # fail whichever op the edit takes first — a same-membership edit
    # goes through resize, a re-split through remove; both fire AFTER
    # `_resize_spec` / `_remove_name` dropped the armed reservation
    def failing_resize(self, spec, **kw):
        calls["n"] += 1
        raise prov.DeviceCapError(spec.name)

    def failing_remove(self, name, **kw):
        calls["n"] += 1
        raise prov.DeviceCapError(name)

    monkeypatch.setattr(prov.PlanState, "resize", failing_resize)
    monkeypatch.setattr(prov.PlanState, "remove", failing_remove)
    changed = rec._forecast_act(99.0, base, est,
                                est.rate_rps * 1.2,
                                backlog=0.0)
    monkeypatch.setattr(prov.PlanState, "resize", real_resize)
    monkeypatch.setattr(prov.PlanState, "remove", real_remove)

    assert calls["n"] >= 1, "injection never reached the edit path"
    # the pre-size failed; re-arming the unchanged group is a no-op, so
    # nothing changed at all
    assert changed is False
    assert _plan_key(rec.plan) == plan_before
    assert rec.armed == armed_before
    assert rec.armed is armed_dict          # identity preserved
    if rec._state is not None:
        assert _plan_key(rec._state.to_plan()) == plan_before
        assert rec._state.shadow is rec.armed
    assert not any(e.action == "forecast" and e.t_s == 99.0
                   for e in rec.edits)

"""Plain reference of the iGniter planner (Eqs. 1-11, 17, 18, Alg. 1/2).

Written from the paper and the deployment file alone: it imports nothing
of the system under test and reads its coefficients, prices and budget
split from the configuration dictionary.  Workloads are plain tuples
``(name, model, slo_ms, rate_rps)``; a plan is a list of placements
``(name, gpu, r, batch)`` in the order Alg. 1 emits them.

Every device is scored with the same Alg. 2 grant loop, run over all
candidate devices at once with numpy (one row per device).  ``dtype``
sets the precision of that loop's arithmetic: the latency model of
Eqs. 1-11 and its comparisons with the budgets and with a whole device.
The allocations move on the ``r_unit`` grid in float64, snapped to 10
decimals as the paper's 2.5 % grants are, and the budget split and
Eqs. 17/18 stay float64: a device loop in a lower precision changes
which grants are made, not where they land.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

R_MAX = 1.0
COEFFS = ("k1", "k2", "k3", "k4", "k5", "k_sch", "n_kernels", "d_load",
          "d_feedback", "alpha_power", "beta_power", "alpha_cacheutil",
          "beta_cacheutil", "alpha_cache")
Workload = Tuple[str, str, float, float]
Placement = Tuple[str, int, float, int]


class Infeasible(Exception):
    """A workload cannot meet its budget even alone on a full device."""


class Fleet:
    """One hardware type with its fitted per-model coefficients."""

    def __init__(self, hw: dict, profiles: Dict[str, dict], budget: dict,
                 dtype=np.float64):
        self.hw = hw
        self.name = hw["name"]
        self.unit = float(hw["r_unit"])
        self.price = float(hw["price_per_hour"])
        self.dt = np.dtype(dtype)
        self.models = sorted(profiles)
        self.mid = {m: i for i, m in enumerate(self.models)}
        self.coef = {f: np.array([profiles[m][f] for m in self.models],
                                 dtype=self.dt) for f in COEFFS}
        self.coef64 = {f: np.array([profiles[m][f] for m in self.models],
                                   dtype=np.float64) for f in COEFFS}
        self.bud = budget
        self._memo: Dict[tuple, float] = {}
        self.h = {k: self.dt.type(hw[k]) for k in
                  ("power_cap", "max_freq", "idle_power", "pcie_bw",
                   "alpha_f", "alpha_sch", "beta_sch")}

    # -- SLO budget split: B + tail queueing wait + slack <= T_slo ----------
    def budgets(self, slo, rate, b) -> np.ndarray:
        dt = np.dtype(np.float64)
        slo = np.asarray(slo, dtype=dt)
        rms = np.asarray(rate, dtype=dt) / dt.type(1000.0)
        b = np.asarray(b, dtype=dt)
        if self.bud["mode"] == "half":
            return slo / dt.type(2.0)
        target = slo * (dt.type(1.0) - dt.type(self.bud["slack_frac"]))
        qf = dt.type(-math.log1p(-self.bud["quantile"]))
        burst = dt.type(self.bud["burstiness"])
        lo, hi = np.zeros_like(slo), slo.copy()
        with np.errstate(divide="ignore", invalid="ignore"):
            for _ in range(int(self.bud["solve_iters"])):
                mid = dt.type(0.5) * (lo + hi)
                rho = rms * mid / b
                wait = burst * rho * mid / (dt.type(2.0) * b
                                            * (dt.type(1.0) - rho))
                tail = np.where(rho >= dt.type(1.0 - 1e-9), np.inf,
                                (b - dt.type(1.0)) / rms
                                + wait * qf)
                tail = np.where(rms > 0, tail, dt.type(0.0))
                ok = mid + tail <= target
                lo, hi = np.where(ok, mid, lo), np.where(ok, hi, mid)
        return np.minimum(lo, slo / dt.type(2.0))

    def budget(self, slo: float, rate: float, b: int) -> float:
        """One workload's inference budget (memoized)."""
        key = (slo, rate, b)
        if key not in self._memo:
            self.prime([key])
        return self._memo[key]

    def prime(self, keys) -> None:
        """Solve the budgets of many ``(slo, rate, batch)`` keys at once."""
        keys = [k for k in dict.fromkeys(keys) if k not in self._memo]
        if keys:
            slo, rate, b = zip(*keys)
            for k, v in zip(keys, self.budgets(slo, rate, b)):
                self._memo[k] = float(v)

    # -- Theorem 1 ---------------------------------------------------------
    def eq17(self, w: Workload, b_max: int = 64) -> int:
        """Eq. 17: the smallest batch that keeps up within T_slo / 2."""
        _, model, slo, rate = w
        pcie = float(self.hw["pcie_bw"])
        rms = rate / 1000.0
        d_load = float(self.coef64["d_load"][self.mid[model]])
        b = int(math.ceil(slo * rms * pcie / (2.0 * (pcie + rms * d_load))))
        return max(1, min(b, b_max))

    def batch(self, w: Workload, b_max: int = 64) -> int:
        """Eq. 17, shrunk while the solved budget is degenerate."""
        _, _, slo, rate = w
        b = self.eq17(w, b_max)
        if self.bud["mode"] != "half":
            while b > 1 and self.budget(slo, rate, b) <= 1e-6:
                b -= 1
        return b

    def _r_lower_at(self, i: int, b: int, budget: float) -> float:
        c = {f: float(v[i]) for f, v in self.coef64.items()}
        pcie = float(self.hw["pcie_bw"])
        delta = (budget - (c["d_load"] + c["d_feedback"]) * b / pcie
                 - c["k5"] - c["k_sch"] * c["n_kernels"])
        if delta <= 0:
            raise Infeasible
        r = (c["k1"] * b * b + c["k2"] * b + c["k3"]) / delta - c["k4"]
        units = math.ceil(r / self.unit - 1e-9)
        rl = max(self.unit, units * self.unit)
        if rl > R_MAX + 1e-9:
            raise Infeasible
        return min(rl, R_MAX)

    def r_lower(self, w: Workload, b: int) -> float:
        """Eq. 18; a tightened budget out of reach clamps to a full device."""
        _, model, slo, rate = w
        i = self.mid[model]
        try:
            return self._r_lower_at(i, b, self.budget(slo, rate, b))
        except Infeasible:
            if self.bud["mode"] == "half":
                raise
            self._r_lower_at(i, b, slo / 2.0)
            return R_MAX

    # -- Eqs. 1-11 over devices x slots --------------------------------------
    def t_inf(self, mi, b, r, mask):
        """Predicted inference latency of every slot; rows are devices."""
        dt, h = self.dt, self.h
        c = {f: v[mi] for f, v in self.coef.items()}
        b = b.astype(dt)
        r = r.astype(dt)
        k_act = (c["k1"] * b * b + c["k2"] * b + c["k3"]) / (r + c["k4"]) \
            + c["k5"]
        ability = b / k_act
        power = np.where(mask, c["alpha_power"] * ability + c["beta_power"],
                         dt.type(0))
        cache = np.where(mask, c["alpha_cacheutil"] * ability
                         + c["beta_cacheutil"], dt.type(0))
        n = mask.sum(axis=1)
        dsch = np.where(n <= 1, dt.type(0),
                        h["alpha_sch"] * n.astype(dt) + h["beta_sch"])
        p_dem = h["idle_power"] + power.sum(axis=1)
        freq = np.where(p_dem <= h["power_cap"], h["max_freq"],
                        np.maximum(h["max_freq"] + h["alpha_f"]
                                   * (p_dem - h["power_cap"]),
                                   dt.type(0.3) * h["max_freq"]))
        slow = (freq / h["max_freq"])[:, None]
        t_load = c["d_load"] * b / h["pcie_bw"]
        t_fb = c["d_feedback"] * b / h["pcie_bw"]
        t_sch = (c["k_sch"] + dsch[:, None]) * c["n_kernels"]
        other = cache.sum(axis=1)[:, None] - cache
        t_act = k_act * (dt.type(1) + c["alpha_cache"] * other)
        return t_load + (t_sch + t_act) / slow + t_fb

    def alg2(self, mi, b, r, bud, mask):
        """Alg. 2 on every row: grant +r_unit to each slot over its budget
        until no slot is, or the row passes a whole device.  Returns
        (feasible rows, final allocations)."""
        dt = self.dt
        r = r.copy()
        d = r.shape[0]
        feasible = np.zeros(d, dtype=bool)
        live = np.arange(d)
        while live.size:
            rr, mm = r[live], mask[live]
            over = np.where(mm, rr.astype(dt), dt.type(0)).sum(axis=1) \
                > dt.type(R_MAX + 1e-9)
            t = self.t_inf(mi[live], b[live], rr, mm)
            viol = mm & (t > bud[live].astype(dt) + dt.type(1e-9)) \
                & ~over[:, None]
            done = ~viol.any(axis=1) & ~over
            feasible[live[done]] = True
            grow = viol.any(axis=1)
            rr = np.where(viol, np.round(rr + self.unit, 10), rr)
            r[live] = rr
            live = live[grow]
        return feasible, r

    def self_grant(self, i: int, b: int, rl: float, bud: float) -> float:
        ok, r = self.alg2(np.array([[i]]), np.array([[b]]),
                          np.array([[rl]]), np.array([[bud]]),
                          np.ones((1, 1), dtype=bool))
        return float(r[0, 0]) if ok[0] else R_MAX


class Cluster:
    """Open devices of one fleet as padded (device, slot) arrays."""

    def __init__(self, fleet: Fleet, cap_k: int = 8):
        self.f = fleet
        self.gpus: List[int] = []
        self.names: List[List[str]] = []
        self.mi = np.zeros((0, cap_k), dtype=np.int64)
        self.b = np.ones((0, cap_k), dtype=np.int64)
        self.r = np.zeros((0, cap_k))
        self.bud = np.zeros((0, cap_k))
        self.n = np.zeros(0, dtype=np.int64)

    def _grow(self, d: int, k: int) -> None:
        d0, k0 = self.r.shape
        if d <= d0 and k <= k0:
            return
        d1, k1 = max(d, 2 * d0, 8), max(k, k0 if k <= k0 else 2 * k0)

        def pad(a, fill):
            out = np.full((d1, k1), fill, dtype=a.dtype)
            out[:d0, :k0] = a
            return out
        self.mi, self.b = pad(self.mi, 0), pad(self.b, 1)
        self.r, self.bud = pad(self.r, 0), pad(self.bud, 0.0)
        n = np.zeros(d1, dtype=np.int64)
        n[:d0] = self.n
        self.n = n

    def open(self, gpu: int) -> int:
        q = len(self.gpus)
        self._grow(q + 1, 1)
        self.gpus.append(gpu)
        self.names.append([])
        return q

    def append(self, q: int, name: str, i: int, b: int, r: float,
               bud: float) -> None:
        s = int(self.n[q])
        self._grow(len(self.gpus), s + 1)
        self.mi[q, s], self.b[q, s], self.r[q, s], self.bud[q, s] = \
            i, b, r, bud
        self.n[q] = s + 1
        self.names[q].append(name)

    def score(self, i: int, b: int, rl: float, bud: float, cand):
        """Alg. 2 of a newcomer on each candidate row; Alg. 1 line 8 pick:
        the earliest row whose added interference is least."""
        cand = np.asarray(cand, dtype=np.int64)
        if not cand.size:
            return -1, None
        k = int(self.n[cand].max())
        mask = np.arange(k + 1)[None, :] < self.n[cand][:, None]
        mask[:, k] = True

        def col(a, v):
            return np.concatenate([a[cand, :k], np.full((cand.size, 1), v,
                                                        dtype=a.dtype)], 1)
        r0 = col(self.r, rl)
        ok, r_new = self.f.alg2(col(self.mi, i), col(self.b, b), r0,
                                col(self.bud, bud), mask)
        inter = np.where(mask, np.maximum(0.0, r_new - r0), 0.0).sum(axis=1)
        best, best_inter = -1, R_MAX + 1.0
        for j in np.flatnonzero(ok):
            if inter[j] < best_inter - 1e-12:
                best, best_inter = int(j), float(inter[j])
        if best < 0:
            return -1, None
        q = int(cand[best])
        return q, np.concatenate([r_new[best, :self.n[q]], r_new[best, k:]])

    def place(self, q: int, alloc, name: str, i: int, b: int,
              bud: float) -> None:
        n = int(self.n[q])
        self.r[q, :n] = alloc[:n]
        self.append(q, name, i, b, alloc[n], bud)

    def entries(self, q: int):
        return [(self.names[q][s], self.gpus[q], float(self.r[q, s]),
                 int(self.b[q, s])) for s in range(int(self.n[q]))]


def _prepare(fleet: Fleet, w: Workload):
    b = fleet.batch(w)
    rl = fleet.r_lower(w, b)
    return b, rl, fleet.budget(w[2], w[3], b)


def provision(workloads: Sequence[Workload], fleet: Fleet) -> List[Placement]:
    """Alg. 1 on one fleet: largest lower bound first, each to the open
    device where Alg. 2 adds the least interference, else a fresh one."""
    prep = []
    fleet.prime([(w[2], w[3], fleet.eq17(w)) for w in workloads])
    for w in workloads:
        b, rl, bud = _prepare(fleet, w)
        prep.append((w, fleet.mid[w[1]], b, rl, bud))
    prep.sort(key=lambda t: -t[3])
    cl = Cluster(fleet)
    cl.open(0)
    for w, i, b, rl, bud in prep:
        q, alloc = cl.score(i, b, rl, bud, np.arange(len(cl.gpus)))
        if q < 0:
            q = cl.open(len(cl.gpus))
            cl.append(q, w[0], i, b, fleet.self_grant(i, b, rl, bud), bud)
        else:
            cl.place(q, alloc, w[0], i, b, bud)
    return [e for q in range(len(cl.gpus)) for e in cl.entries(q)]


def n_devices(plan: Sequence[Placement]) -> int:
    return len({p[1] for p in plan})


def provision_cheapest(workloads: Sequence[Workload],
                       fleets: Sequence[Fleet]):
    """Alg. 1 per fleet; the cheapest feasible plan (first on a tie)."""
    best = None
    for fleet in fleets:
        try:
            plan = provision(workloads, fleet)
        except Infeasible:
            continue
        cost = n_devices(plan) * fleet.price
        if best is None or cost < best[2]:
            best = (plan, fleet, cost)
    if best is None:
        raise Infeasible("no fleet can host the workloads")
    return best


def _cluster_of(plan: Sequence[Placement], fleet: Fleet,
                workloads: Dict[str, Workload]):
    """A plan's devices as a `Cluster`, rows in order of first appearance."""
    cl = Cluster(fleet)
    index: Dict[int, int] = {}
    for name, g, r, pb in plan:
        if g not in index:
            index[g] = cl.open(g)
        o = workloads[name]
        cl.append(index[g], name, fleet.mid[o[1]], pb, r,
                  fleet.budget(o[2], o[3], pb))
    return cl, index


def add_workload(plan: List[Placement], w: Workload, fleet: Fleet,
                 workloads: Dict[str, Workload]) -> List[Placement]:
    """Online arrival: place one workload into a standing plan, letting
    Alg. 2 regrow the residents of the chosen device."""
    i = fleet.mid[w[1]]
    b, rl, bud = _prepare(fleet, w)
    cl, index = _cluster_of(plan, fleet, workloads)
    q, alloc = cl.score(i, b, rl, bud, [index[g] for g in sorted(index)])
    if q < 0:
        g_new = max(index) + 1 if index else 0
        return list(plan) + [(w[0], g_new,
                              fleet.self_grant(i, b, rl, bud), b)]
    g = cl.gpus[q]
    out = [p for p in plan if p[1] != g]
    cl.place(q, alloc, w[0], i, b, bud)
    return out + cl.entries(q)


def remove_workload(plan: List[Placement], name: str) -> List[Placement]:
    out = [p for p in plan if p[0] != name]
    if len(out) == len(plan):
        raise KeyError(name)
    return out


def predicted_violations(plan: Sequence[Placement], fleet: Fleet,
                         workloads: Dict[str, Workload]) -> List[str]:
    """Placements whose predicted latency exceeds their budget."""
    cl, _ = _cluster_of(plan, fleet, workloads)
    d, k = len(cl.gpus), int(cl.n.max())
    mask = np.arange(k)[None, :] < cl.n[:d, None]
    t = fleet.t_inf(cl.mi[:d, :k], cl.b[:d, :k], cl.r[:d, :k], mask)
    over = mask & (t > cl.bud[:d, :k] + 1e-6)
    return [cl.names[q][s] for q, s in zip(*np.nonzero(over))]

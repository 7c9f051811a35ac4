"""Plain reference of the cluster simulator's ground truth.

Written from the simulator's documented semantics and the deployment
file alone; it imports nothing of the system under test.

- Arrivals: instance ``i`` of the plan (placements in plan order) draws
  its phase from ``default_rng([seed, i, 0])``, one uniform in
  ``[0, 1000 / rate)``, then arrives every ``1000 / rate`` ms before
  the horizon (constant-rate traffic).
- Serving: one greedy batching server per instance; a pass starts when
  the server is free and a request waits, takes up to ``batch`` waiting
  requests in arrival order, and lasts
  ``t_load + (t_sched * ns + t_act * na) / slow + t_feedback``, the
  ground-truth state of the device with this instance at the pass's
  batch and its co-residents at their configured batches.  ``na`` and
  ``ns`` are the next lognormal multipliers of the instance's streams
  ``[seed, i, 1]`` (sigma) and ``[seed, i, 2]`` (2 sigma), drawn in
  chunks of 512.  Requests still queued at the horizon are served.
- Ground truth: the co-location physics of the deployment file
  (time-share over-subscription, bandwidth knee, soft power-frequency
  curve, super-linear dispatch growth).

``dtype`` sets the precision of the physics, the latency tables of
each device; event times are float64 whatever it is, so a lower
precision shows as the tables' rounding carried into every latency.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

Placement = Tuple[str, int, float, int]
CHUNK = 512


def device_tables(members: Sequence[Tuple[dict, int, float]], hw: dict,
                  ph: dict, dtype=np.float64) -> List[np.ndarray]:
    """Pass latency terms of each member at every batch 1..b, the others
    at their configured batch: per member an array of rows
    ``(t_load, t_sched, t_act, t_feedback, slow)``."""
    dt = np.dtype(dtype)
    f = dt.type
    out = []
    n = len(members)
    for me in range(n):
        bmax = max(1, members[me][1])
        b = np.empty((bmax, n), dtype=dt)
        r = np.empty((bmax, n), dtype=dt)
        for j, (_, bj, rj) in enumerate(members):
            b[:, j], r[:, j] = bj, rj
        b[:, me] = np.arange(1, bmax + 1)

        def col(key):
            return np.array([m[0][key] for m in members], dtype=dt)[None, :]
        total_r = r.sum(axis=1)
        thrash = f(1) + f(0.6) * np.maximum(f(0), total_r - f(1))
        r = r / np.maximum(f(1), total_r)[:, None]
        t_load = col("d_load_mb") * b / f(hw["pcie_bw"])
        t_fb = col("d_feedback_mb") * b / f(hw["pcie_bw"])
        flops = col("flops_per_item") * b * (f(1) + f(0.004) * b)
        nbytes = col("weight_bytes") + col("act_bytes_per_item") * b
        t_c0 = flops / (f(hw["peak_flops"]) * f(hw["mxu_efficiency"])) \
            * f(1e3)
        t_m0 = nbytes / f(hw["hbm_bw"]) * f(1e3)
        share = np.maximum(r, f(1e-3))
        t_c, t_m = t_c0 / share, t_m0 / share
        t_solo = np.maximum(t_c, t_m) + f(0.35) * np.minimum(t_c, t_m) \
            + f(0.05)
        bw = np.minimum(f(1), nbytes / (t_solo * f(1e-3)) / f(hw["hbm_bw"]))
        power = f(hw["power_cap"]) * f(ph["active_w_scale"]) * share \
            * (f(0.35) + f(0.65) * (t_c / t_solo))
        dev_power = f(hw["idle_power"]) + power.sum(axis=1)
        excess = np.maximum(dev_power - f(hw["power_cap"]), f(0))
        freq = np.where(dev_power <= f(hw["power_cap"]), f(hw["max_freq"]),
                        np.maximum(f(hw["max_freq"]) + f(hw["alpha_f"])
                                   * excess ** f(ph["freq_exp"]),
                                   f(0.6) * f(hw["max_freq"])))
        kern = col("n_kernels")
        t_sched = (f(0.002) + f(5e-6) * kern) * f(
            1 + ph["sched_coloc_slope"]
            * max(0, n - 1) ** ph["sched_coloc_exp"]) * kern
        total_bw = bw.sum(axis=1)
        infl = np.where(total_bw > f(ph["bw_knee"]),
                        (total_bw / f(ph["bw_knee"])) ** f(ph["bw_exp"]),
                        f(1))
        t_mi = t_m * infl[:, None]
        t_act = (np.maximum(t_c, t_mi) + f(0.35) * np.minimum(t_c, t_mi)
                 + f(0.05)) * thrash[:, None]
        slow = freq / f(hw["max_freq"])
        out.append(np.stack([t_load[:, me],
                             np.broadcast_to(t_sched[:, me], (bmax,)),
                             t_act[:, me], t_fb[:, me], slow], axis=1))
    return out


def _noise(seed: int, i: int, k: int, sigma: float, n: int) -> np.ndarray:
    rng = np.random.default_rng([seed, i, k])
    chunks = -(-max(n, 1) // CHUNK)
    return np.concatenate([rng.lognormal(0.0, sigma, CHUNK)
                           for _ in range(chunks)])


def arrivals(rate_rps: float, horizon_ms: float, seed: int, i: int
             ) -> np.ndarray:
    period = 1000.0 / rate_rps
    t0 = float(np.random.default_rng([seed, i, 0]).uniform(0, period))
    if t0 >= horizon_ms:
        return np.empty(0)
    n = int(math.ceil((horizon_ms - t0) / period))
    ts = t0 + period * np.arange(n + 1)
    return ts[ts < horizon_ms]


def serve(arr: np.ndarray, table: np.ndarray, batch: int,
          na: np.ndarray, ns: np.ndarray) -> np.ndarray:
    """Latency of every request of one instance, in arrival order."""
    rows = [tuple(float(x) for x in row) for row in table]
    lat = np.empty(arr.size)
    free = -math.inf
    q, k, n = 0, 0, arr.size
    while q < n:
        start = free if free >= arr[q] else arr[q]
        waiting = int(np.searchsorted(arr, start, side="right")) - q
        nb = min(batch, waiting)
        t_load, t_sch, t_act, t_fb, slow = rows[nb - 1]
        done = start + (t_load + (t_sch * ns[k] + t_act * na[k]) / slow
                        + t_fb)
        lat[q:q + nb] = done - arr[q:q + nb]
        free = done
        q += nb
        k += 1
    return lat


def simulate(plan: Sequence[Placement], workloads: Dict[str, tuple],
             models: Dict[str, dict], hw: dict, ph: dict, horizon_ms: float,
             seed: int, dtype=np.float64) -> Dict[str, np.ndarray]:
    """Per-request latencies of every workload of ``plan``."""
    by_gpu: Dict[int, List[int]] = {}
    for i, p in enumerate(plan):
        by_gpu.setdefault(p[1], []).append(i)
    out: Dict[str, np.ndarray] = {}
    sigma = float(ph["noise_sigma"])
    for g, idxs in by_gpu.items():
        members = [(models[workloads[plan[i][0]][1]], plan[i][3], plan[i][2])
                   for i in idxs]
        tables = device_tables(members, hw, ph, dtype)
        for i, table in zip(idxs, tables):
            name, _, _, batch = plan[i]
            arr = arrivals(workloads[name][3], horizon_ms, seed, i)
            na = _noise(seed, i, 1, sigma, arr.size)
            ns = _noise(seed, i, 2, 2 * sigma, arr.size)
            out[name] = serve(arr, table, max(1, batch), na, ns)
    return out


def violations(lat: Dict[str, np.ndarray], workloads: Dict[str, tuple],
               duration_s: float) -> List[str]:
    """Workloads over their SLO at p99 or under 95 % of their rate."""
    out = []
    for name, x in lat.items():
        _, _, slo, rate = workloads[name]
        p99 = float(np.percentile(x, 99)) if x.size else math.inf
        if p99 > slo + 1e-9 or x.size / duration_s < 0.95 * rate:
            out.append(name)
    return out

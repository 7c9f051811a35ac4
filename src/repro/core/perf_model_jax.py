"""The Alg. 2 grant loop as a jitted XLA program (``backend="jax"``).

`repro.core.perf_model_vec` is the numpy hot path and stays the pinned
oracle.  This module runs `VecCluster.alloc_all`'s loop, one newcomer
against every open device, on the accelerator, for every placement
path (Alg. 1, `add_workload`, the controller's `PlanState`):

  * ``pack`` / ``sync``       the cluster as one packed float64 array
                              kept on the device (see Residency)
  * ``_scatter_rows_jit``     dirty rows written into that copy
  * ``_alloc_all_jit``        Algorithm 2 against every open device as
                              ONE `lax.while_loop`; returns the grant
                              counts and verdicts packed as one int32
                              (cap_d, cap_n + 2) array
  * ``alloc_all_jax``         the dispatch target: sync, launch, one
                              copy of that array to the host, and the
                              host replay of the grants

Layout contract: shapes are the VecCluster capacities (powers of two),
NOT the live device count d — d arrives as a traced scalar and
``row_valid = arange(cap_d) < d`` masks the padding rows, so XLA
recompiles only when a capacity doubles (~log2(D) times per sweep).
Per-entry SLO budgets are always solved on the numpy side
(`queueing.BudgetModel`) and passed in as arrays: both backends consume
bit-identical thresholds, and only the model arithmetic itself crosses
into XLA.

Residency: the grant loop reads the cluster from one packed float64
array of shape (cap_d, W), one record per device row (`pack`), which
stays on the device between calls as ``VecCluster.mirror``.  Each call
`sync`s it first: the rows the cluster marked dirty since the last call
go as one (K_ROWS, W + 1) block, scattered into the donated mirror; a
first call, a capacity change or more than K_ROWS dirty rows copy the
whole state instead.  The newcomer travels as one float64 vector.  So
a call of Alg. 1's provision loop, which changes one device row between
calls, copies two small arrays to the device, not the whole cluster.

Numerical contract: agreement with the numpy oracle is pinned at
<= 1e-6 (tests/test_perf_model_jax.py), NOT the scalar-vs-vec 1e-9 —
XLA may reassociate sums and fuse multiply-adds, and a TPU emulates
float64 at ~2**-48 relative, so last-bit equality is out of scope by
design (docs/reproduction-notes.md, deviation 5).  Plan-level decisions
still agree exactly because Alg. 1/2 thresholds carry 1e-9 epsilons,
orders of magnitude above the float divergence, and `alloc_all_jax`
hands back the oracle's own allocation bits: the device decides how
many grants, the host replays them on the oracle's grid.

float64 is mandatory: the 1e-9 decision epsilons drown in float32
noise.  Importing this module enables jax x64 mode process-wide.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after x64 switch on purpose)
from jax import lax  # noqa: E402

from repro.core import perf_model_vec as pmv  # noqa: E402
from repro.core import trace  # noqa: E402
from repro.core.types import (  # noqa: E402
    HardwareSpec, WorkloadCoefficients, WorkloadSpec)

R_MAX = pmv.R_MAX

# Index layout of the flat coefficient tuples handed to jitted kernels
# (same order as perf_model_vec.COEFF_FIELDS).
_F = {name: i for i, name in enumerate(pmv.COEFF_FIELDS)}


# The packed record of one device row: each per-slot field takes cap_n
# columns, in this order, then each per-row field one column.
SLOT_FIELDS = pmv.COEFF_FIELDS + (
    "b", "r", "budget_ms", "mask", "k_act", "power", "cache",
    "t_load", "t_feedback", "t_schk")
ROW_FIELDS = ("power_sum", "cache_sum", "n")
# Dirty rows sent as one block; with more, the whole state is copied.
K_ROWS = 8


def _coeff_scalars(c: WorkloadCoefficients) -> Tuple[float, ...]:
    return tuple(float(getattr(c, f)) for f in pmv.COEFF_FIELDS)


def _k_act(ca, b, r):
    """Eq. (11) on a flat coefficient tuple."""
    return ((ca[_F["k1"]] * b * b + ca[_F["k2"]] * b + ca[_F["k3"]])
            / (r + ca[_F["k4"]]) + ca[_F["k5"]])


# ---------------------------------------------------------------------------
# Algorithm 2 over every open device: lax.while_loop
# ---------------------------------------------------------------------------

def pack(cl: "pmv.VecCluster", rows=slice(None)) -> np.ndarray:
    """The packed float64 records of ``cl``'s device rows ``rows``
    (all by default), shape (rows, W): the layout `_unpack` reads."""
    return np.concatenate(
        [getattr(cl.ca, f)[rows] for f in pmv.COEFF_FIELDS]
        + [cl.b[rows], cl.r[rows], cl.budget_ms[rows], cl.mask[rows],
           cl.k_act[rows], cl.power[rows], cl.cache[rows],
           cl.t_io[rows, :, 0], cl.t_io[rows, :, 1], cl.t_schk[rows],
           cl.power_sum[rows, None], cl.cache_sum[rows, None],
           cl.n[rows, None]], axis=1, dtype=np.float64)


def _unpack(state):
    """`pack`'s fields, by name, sliced out of the (cap_d, W) state."""
    cap_n = (state.shape[1] - len(ROW_FIELDS)) // len(SLOT_FIELDS)
    f = {name: state[:, i * cap_n:(i + 1) * cap_n]
         for i, name in enumerate(SLOT_FIELDS)}
    f.update((name, state[:, i - len(ROW_FIELDS)])
             for i, name in enumerate(ROW_FIELDS))
    return f


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_rows_jit(state, block):
    """The donated device state with ``block``'s records written in: the
    last column of the block holds each record's row; padding records
    hold cap_d, out of bounds, and are dropped."""
    rows = block[:, -1].astype(jnp.int32)
    return state.at[rows].set(block[:, :-1], mode="drop")


def sync(cl: "pmv.VecCluster") -> int:
    """Bring ``cl.mirror``, the device copy of ``pack(cl)``, up to date
    and clear ``cl.dirty``; returns the rows copied (cap_d for the whole
    state).  `VecCluster._grow` drops the mirror when a capacity
    changes, so a mirror found here has the state's shape."""
    dirty = np.flatnonzero(cl.dirty)
    cap_d = cl.mask.shape[0]
    if cl.mirror is None or dirty.size > K_ROWS:
        cl.mirror = jax.device_put(pack(cl))
        sent = cap_d
    elif dirty.size:
        block = np.full((K_ROWS, cl.mirror.shape[1] + 1), float(cap_d))
        block[:dirty.size, :-1] = pack(cl, dirty)
        block[:dirty.size, -1] = dirty
        cl.mirror = _scatter_rows_jit(cl.mirror, block)
        sent = int(dirty.size)
    else:
        sent = 0
    cl.dirty[:] = False
    return sent


@functools.partial(jax.jit, static_argnames=("hw",))
def _alloc_all_jit(hw: HardwareSpec, state, new):
    """One newcomer vs every open device, full Alg. 2 grant loop.

    ``state`` is the packed cluster (`pack`); ``new`` is the newcomer's
    coefficients in `COEFF_FIELDS` order, then its batch, ``r_lower``,
    its inference budget and the open device count ``d``.  Counts
    travel as float64 and are rounded back to integers here.

    Shapes are the cluster CAPACITIES; ``d`` is traced and
    ``row_valid`` masks the padding rows (they start inactive and
    infeasible-irrelevant, and the caller slices them off).  The body
    mirrors `VecCluster.alloc_all` statement for statement, except
    that a grant adds ``r_unit`` without the oracle's 1e-10 grid snap,
    and the per-row grant delta sums (`np.subtract.at`'s sequential
    accumulation) become masked row sums.  Both stay ulps away from
    the oracle's values, far inside the 1e-9 decision epsilons.

    Returns ``(packed, iters)``.  ``packed`` is one int32 array of
    shape (cap_d, cap_n + 2), so that the host fetches the answer in
    one copy: columns ``0..cap_n-1`` hold how many ``+r_unit`` grants
    each resident took, column ``cap_n`` the newcomer's grants and
    column ``cap_n + 1`` the device's verdict as 0/1.  ``iters`` is the
    number of loop iterations (the numpy loop's count), a scalar of its
    own.  The caller replays the grants on the host (`alloc_all_jax`).
    """
    f = _unpack(state)
    ca = tuple(f[name] for name in pmv.COEFF_FIELDS)
    b, r0, budget_ms = f["b"], f["r"], f["budget_ms"]
    mask = f["mask"] > 0.5
    n = jnp.round(f["n"]).astype(jnp.int64)
    k_act0, power0, cache0 = f["k_act"], f["power"], f["cache"]
    t_load, t_feedback, t_schk = f["t_load"], f["t_feedback"], f["t_schk"]
    power_sum, cache_sum = f["power_sum"], f["cache_sum"]
    n_cw = len(pmv.COEFF_FIELDS)
    cw = tuple(new[i] for i in range(n_cw))
    bn, r_lower, budget_new = new[n_cw], new[n_cw + 1], new[n_cw + 2]
    d = jnp.round(new[n_cw + 3])

    cap_d = mask.shape[0]
    row_valid = jnp.arange(cap_d) < d

    def solo_new(rn):
        k_act = ((cw[_F["k1"]] * bn * bn + cw[_F["k2"]] * bn + cw[_F["k3"]])
                 / (rn + cw[_F["k4"]]) + cw[_F["k5"]])
        ability = bn / k_act
        return (k_act,
                cw[_F["alpha_power"]] * ability + cw[_F["beta_power"]],
                cw[_F["alpha_cacheutil"]] * ability
                + cw[_F["beta_cacheutil"]])

    rn0 = jnp.full(cap_d, r_lower)
    kan0, pn0, cn0 = solo_new(rn0)
    p_sum0 = power_sum + pn0
    c_sum0 = cache_sum + cn0
    n_co = n + 1
    ds = jnp.where(n_co <= 1, 0.0, hw.alpha_sch * n_co + hw.beta_sch)
    t_load_new = cw[_F["d_load"]] * bn / hw.pcie_bw
    t_fb_new = cw[_F["d_feedback"]] * bn / hw.pcie_bw
    t_schk_new = cw[_F["k_sch"]] * cw[_F["n_kernels"]]

    def cond(st):
        return st[-3].any()                                   # active

    def body(st):
        (rr, rn, g_res, g_new, ka, pw, cu, kan, pn, cn,
         p_sum, c_sum, active, feasible, it) = st
        tot = jnp.where(mask, rr, 0.0).sum(axis=1) + rn
        over = active & (tot > R_MAX + 1e-9)
        feasible = feasible & ~over
        act = active & ~over

        p_dem = hw.idle_power + p_sum                               # Eq. 10
        freq = jnp.where(p_dem <= hw.power_cap, hw.max_freq,        # Eq. 9
                         jnp.maximum(hw.max_freq + hw.alpha_f
                                     * (p_dem - hw.power_cap),
                                     0.3 * hw.max_freq))
        slow = freq / hw.max_freq
        other_res = c_sum[:, None] - cu
        t_act = ka * (1.0 + ca[_F["alpha_cache"]] * other_res)
        t_sch = t_schk + ds[:, None] * ca[_F["n_kernels"]]
        t_gpu = (t_sch + t_act) / slow[:, None]
        t_inf = t_load + t_gpu + t_feedback
        viol_res = mask & (t_inf > budget_ms + 1e-9) & act[:, None]

        other_new = c_sum - cn
        t_act_n = kan * (1.0 + cw[_F["alpha_cache"]] * other_new)
        t_gpu_n = (t_schk_new + ds * cw[_F["n_kernels"]] + t_act_n) / slow
        t_inf_n = t_load_new + t_gpu_n + t_fb_new
        viol_new = (t_inf_n > budget_new + 1e-9) & act

        conv = act & ~viol_res.any(axis=1) & ~viol_new
        act = act & ~conv

        # grants: +r_unit to every violator on still-active devices
        grow = viol_res & act[:, None]
        rr2 = jnp.where(grow, rr + hw.r_unit, rr)
        g_res = g_res + grow
        k_act_g = _k_act(ca, b, rr2)
        ability_g = b / k_act_g
        p_g = ca[_F["alpha_power"]] * ability_g + ca[_F["beta_power"]]
        c_g = (ca[_F["alpha_cacheutil"]] * ability_g
               + ca[_F["beta_cacheutil"]])
        ka = jnp.where(grow, k_act_g, ka)
        p_sum = p_sum - jnp.where(grow, pw - p_g, 0.0).sum(axis=1)
        c_sum = c_sum - jnp.where(grow, cu - c_g, 0.0).sum(axis=1)
        pw = jnp.where(grow, p_g, pw)
        cu = jnp.where(grow, c_g, cu)

        grow_n = viol_new & act
        rn2 = jnp.where(grow_n, rn + hw.r_unit, rn)
        g_new = g_new + grow_n
        kan_g, pn_g, cn_g = solo_new(rn2)
        p_sum = p_sum + jnp.where(grow_n, pn_g - pn, 0.0)
        c_sum = c_sum + jnp.where(grow_n, cn_g - cn, 0.0)
        kan = jnp.where(grow_n, kan_g, kan)
        pn = jnp.where(grow_n, pn_g, pn)
        cn = jnp.where(grow_n, cn_g, cn)
        return (rr2, rn2, g_res, g_new, ka, pw, cu, kan, pn, cn,
                p_sum, c_sum, act, feasible, it + 1)

    init = (r0, rn0, jnp.zeros(mask.shape, jnp.int32),
            jnp.zeros(cap_d, jnp.int32), k_act0, power0, cache0,
            kan0, pn0, cn0, p_sum0, c_sum0, row_valid,
            jnp.ones(cap_d, dtype=bool), jnp.int32(0))
    (_, _, g_res, g_new, *_, feasible, iters) = lax.while_loop(cond, body,
                                                               init)
    packed = jnp.concatenate(
        [g_res, g_new[:, None], feasible[:, None].astype(jnp.int32)], axis=1)
    return packed, iters


def alloc_all_jax(cl: "pmv.VecCluster", spec: WorkloadSpec,
                  coeffs: WorkloadCoefficients, batch: int, r_lower: float
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Backend dispatch target for `VecCluster.alloc_all` ("jax").

    The per-entry ``budget_ms`` thresholds and the newcomer's budget are
    numpy-solved (cached on the cluster / `BudgetModel.budget_ms`), so
    the jitted kernel sees bit-identical decision thresholds to the
    numpy loop.  The kernel decides how many ``+r_unit`` grants each
    entry takes; the allocations and the Alg. 1 score are then replayed
    here with the oracle's own numpy statements, so they are the
    oracle's bits whatever float64 the device has (a TPU emulates it at
    ~2**-48 relative: every value that crossed it came back ulps off).

    The cluster reaches the device through `sync` (its dirty rows, or
    the whole state), the number of rows copied into ``cl.rows_sent``.
    The answer comes back in one device-to-host copy of the kernel's
    packed array, split here into grants and verdicts.
    While a profiler trace records, the loop's iteration count is
    fetched too, into ``cl.iters``, its copy started before the fetch so
    that it overlaps it; otherwise it stays on the device.
    """
    d = cl.d
    if d == 0:
        z = np.zeros(0)
        return z.astype(bool), np.zeros((0, 1)), z, z
    hw = cl.hw
    with trace.span("alloc_all.launch"):
        budget_new = cl.bm.budget_ms(spec.slo_ms, spec.rate_rps, batch)
        cl.rows_sent = sync(cl)
        new = np.array(_coeff_scalars(coeffs)
                       + (batch, r_lower, budget_new, d), dtype=np.float64)
        packed, iters = _alloc_all_jit(hw, cl.mirror, new)
    counting = trace.active()
    if counting:
        iters.copy_to_host_async()
    with trace.span("alloc_all.fetch"):
        packed = np.asarray(packed)[:d]
        g_res, g_new = packed[:, :-2], packed[:, -2]
        feasible = packed[:, -1] != 0
    if counting:
        cl.iters = int(iters)
    with trace.span("alloc_all.replay"):
        r0 = cl.r[:d]
        rr = r0.copy()
        for i in range(int(g_res.max(initial=0))):
            rr = np.where(g_res > i, np.round(rr + hw.r_unit, 10), rr)
        rn = np.full(d, r_lower)
        for i in range(int(g_new.max(initial=0))):
            rn = np.where(g_new > i, np.round(rn + hw.r_unit, 10), rn)
        # Alg. 1 line 8, as `VecCluster.alloc_all` computes it
        grown = np.where(cl.mask[:d], np.maximum(0.0, rr - r0), 0.0)
        r_inter = grown.sum(axis=1) + np.maximum(0.0, rn - r_lower)
        r_inter = np.where(feasible, r_inter, np.inf)
    return feasible, rr, rn, r_inter

"""From a profiler trace to device busy time, module times and idle gaps.

`extract` reads a JAX profiler ``.xplane.pb`` into plain events; `reduce`
turns them into a `Summary`.  Only events inside the harness's window
span (``bench.window``) count.

- busy: the union of the intervals in which an operation or a program
  runs on a device (the device plane's ``XLA Ops`` and ``XLA Modules``
  lines: a program holds the device also while it waits on its own
  copies), averaged over the devices that ran any;
- modules: device time and execution count of each jitted program (the
  ``XLA Modules`` line), keyed by the jitted function's name with the
  ``jit_`` prefix and the ``(id)`` suffix taken off;
- idle gaps: the stretches of the window in which no device ran an
  operation, each charged to the harness span (``bench.<name>``) that
  covered most of it, or to ``outside`` where none did.

`extract` keeps a device plane's timestamps as the profiler gives them,
on the host's clock, so device intervals and host spans compare
directly.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class Summary:
    window_s: float
    busy_s: float                           # mean over devices that ran
    n_devices: int
    modules: Dict[str, Tuple[float, int]]   # name -> (device s, executions)
    ops: Dict[str, float]                   # op name -> device s
    idle_by_span: Dict[str, float]          # span name -> idle s
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> Optional[float]:
        if not self.n_devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(name: str) -> str:
    """``%while.4 = (f32[...]) while(...)`` -> ``%while.4``."""
    return name.split(" = ", 1)[0].strip()


def module_name(name: str) -> str:
    """``jit__alloc_all_jit(42)`` -> ``_alloc_all_jit``."""
    name = _SUFFIX.sub("", name.strip())
    return name[4:] if name.startswith("jit_") else name


def extract(path: str) -> dict:
    """Plain events of one trace file:
    ``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [...]}``, each event ``[name, start_ns, end_ns]``.  A path
    ending in ``.gz`` is read as a gzipped trace file."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            dev = {}
            for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules")):
                if line in lines:
                    dev[key] = [[e.name, e.start_ns, e.start_ns
                                 + e.duration_ns]
                                for e in lines[line].events]
            if dev.get("ops") or dev.get("modules"):
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns,
                                      e.start_ns + e.duration_ns])
    return {"devices": devices, "spans": spans}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, t0, t1) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def reduce(events: dict) -> Summary:
    wins = [s for s in events["spans"] if s[0] == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    t0, t1 = wins[0][1], wins[0][2]
    spans = sorted((s[1], s[2], s[0][len(SPAN_PREFIX):])
                   for s in events["spans"]
                   if s[0] != WINDOW and s[2] > t0 and s[1] < t1)
    starts = [s[0] for s in spans]
    busy_total, n_dev = 0.0, 0
    modules: Dict[str, list] = {}
    ops: Dict[str, float] = {}
    all_busy: List[Interval] = []
    for dev in events["devices"].values():
        ev = dev.get("ops", []) + dev.get("modules", [])
        busy = union(_clip([(a, b) for _, a, b in ev], t0, t1))
        if not busy:
            continue
        n_dev += 1
        busy_total += sum(b - a for a, b in busy)
        all_busy.extend(busy)
        for name, a, b in dev.get("modules", []):
            if b > t0 and a < t1:
                m = modules.setdefault(module_name(name), [0.0, 0])
                m[0] += (min(b, t1) - max(a, t0)) * 1e-9
                m[1] += 1
        for name, a, b in dev.get("ops", []):
            if b > t0 and a < t1:
                op = op_name(name)
                ops[op] = ops.get(op, 0.0) + (min(b, t1) - max(a, t0)) * 1e-9
    gaps: List[Tuple[str, float]] = []
    idle: Dict[str, float] = {}
    cursor = t0
    for a, b in union(all_busy) + [(t1, t1)]:
        if a > cursor:
            name = _owner(spans, starts, cursor, a)
            gaps.append((name, (a - cursor) * 1e-9))
            idle[name] = idle.get(name, 0.0) + (a - cursor) * 1e-9
        cursor = max(cursor, b)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(t1 - t0) * 1e-9,
                   busy_s=busy_total * 1e-9 / n_dev if n_dev else 0.0,
                   n_devices=n_dev,
                   modules={k: (v[0] / n_dev, v[1]) for k, v in
                            modules.items()},
                   ops={k: v / max(n_dev, 1) for k, v in ops.items()},
                   idle_by_span=idle, gaps=gaps)


def _owner(spans, starts, a: float, b: float) -> str:
    """The span covering most of [a, b].  Harness spans follow one
    another without nesting, so only those starting before ``b`` and
    ending after ``a`` are looked at, walking back from ``b``."""
    best, cover = "outside", 0.0
    i = bisect.bisect_left(starts, b) - 1
    while i >= 0 and spans[i][1] > a:
        s0, s1, name = spans[i]
        c = min(b, s1) - max(a, s0)
        if c > cover:
            best, cover = name, c
        i -= 1
    return best


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time; the idle time by the span that was open, each span's
    total as ``<span>:total``, then the longest single gaps."""
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:top]
    totals = sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])
    idle = [[k + ":total", v] for k, v in totals]
    idle += [[k, v] for k, v in summary.gaps[:max(0, top - len(idle))]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle[:top]}

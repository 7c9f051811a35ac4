"""The program's own spans in a profiler trace, beside the device.

The program marks its regions as profiler spans named ``igniter.<region>``
(`src/repro/core/trace.py`); they lie on the host plane of the same
trace, on the same clock as the device's operations.  `load` reads them
from the harness's trace (once per file), clipped to the harness's
window span (``bench.window``), each with its counters (``iters`` on
``igniter.alloc_all``).  `Spans` then gives:

- `total` and `count` of a span name;
- `self_times`: each span's time less what its child spans cover;
- `idle_by_span`: the device's idle time, each stretch of it split at
  span boundaries and each piece charged to the innermost program span
  open over it (``outside`` where none is);
- `leaf_idle_share`: the share of the idle time inside the harness's
  step spans (``bench.<step>``) that lies under a program span with no
  child, where the host was in one known region.

A trace of a program without these spans holds none: `load` then gives
an empty `Spans`, and every reader returns None.
"""
from __future__ import annotations

import gzip
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

PREFIX = "igniter."
OUTSIDE = "outside"

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float                   # ns, host clock
    end: float
    stats: Dict[str, object] = field(default_factory=dict)
    child_ns: float = 0.0          # time covered by direct children
    leaf: bool = True


@dataclass
class Spans:
    events: List[Event]            # program spans, by start
    steps: List[Interval]          # harness step spans
    busy: List[Interval]           # union of device busy intervals
    window: Interval

    def count(self, name: str) -> int:
        return sum(1 for e in self.events if e.name == name)

    def total(self, name: str) -> float:
        """Summed duration of every ``name`` span [s]."""
        return 1e-9 * sum(e.end - e.start for e in self.events
                          if e.name == name)

    def counter_mean(self, name: str, key: str) -> Optional[float]:
        vals = [float(e.stats[key]) for e in self.events
                if e.name == name and key in e.stats]
        return sum(vals) / len(vals) if vals else None

    def self_times(self) -> Dict[str, float]:
        """Each span name's time less its children's [s]."""
        out: Dict[str, float] = {}
        for e in self.events:
            out[e.name] = out.get(e.name, 0.0) + 1e-9 * (
                e.end - e.start - e.child_ns)
        return out

    def innermost(self) -> List[Tuple[float, float, str, bool]]:
        """The window cut into pieces ``(a, b, span, leaf)``, each piece
        labelled with the innermost program span open over it."""
        t0, t1 = self.window
        pieces: List[Tuple[float, float, str, bool]] = []
        stack: List[Event] = []
        cursor = t0

        def emit(upto: float) -> None:
            nonlocal cursor
            if upto > cursor:
                top = stack[-1] if stack else None
                pieces.append((cursor, upto,
                               top.name if top else OUTSIDE,
                               bool(top and top.leaf)))
                cursor = upto

        for e in self.events:
            while stack and stack[-1].end <= e.start:
                emit(stack[-1].end)
                stack.pop()
            emit(e.start)
            stack.append(e)
        while stack:
            emit(stack[-1].end)
            stack.pop()
        emit(t1)
        return pieces

    def idle(self) -> List[Interval]:
        """Stretches of the window in which no device ran anything."""
        out, cursor = [], self.window[0]
        for a, b in self.busy + [(self.window[1], self.window[1])]:
            if a > cursor:
                out.append((cursor, a))
            cursor = max(cursor, b)
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """Device idle time [s] by the innermost program span over it."""
        out: Dict[str, float] = {}
        for a, b, name, _ in _overlap(self.innermost(), self.idle()):
            out[name] = out.get(name, 0.0) + 1e-9 * (b - a)
        return out

    def leaf_idle_share(self) -> Optional[float]:
        """Share of the idle time inside harness step spans that lies
        under a program span with no child span."""
        idle_in_steps = _intersect(self.idle(), self.steps)
        total = sum(b - a for a, b in idle_in_steps)
        if total <= 0:
            return None
        leaf = sum(b - a for a, b, _, is_leaf
                   in _overlap(self.innermost(), idle_in_steps) if is_leaf)
        return leaf / total


def _overlap(pieces, intervals):
    """Each labelled piece cut to the sorted, disjoint ``intervals``."""
    out, j = [], 0
    for a, b, name, leaf in pieces:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < b:
            lo, hi = max(a, intervals[k][0]), min(b, intervals[k][1])
            if hi > lo:
                out.append((lo, hi, name, leaf))
            k += 1
    return out


def _intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    return [(a, b) for a, b, _, _ in
            _overlap([(a, b, "", False) for a, b in xs], ys)]


def build(events: dict) -> Spans:
    """`Spans` from plain events: ``{"window": [start, end], "steps":
    [[start, end], ...], "busy": [[start, end], ...], "spans": [[name,
    start, end, stats], ...]}`` (ns).  Busy intervals and program spans
    are clipped to the window."""
    from bench import trace_reduce as tr
    t0, t1 = events["window"]
    evs = sorted((Event(n, max(a, t0), min(b, t1), dict(st))
                  for n, a, b, st in events["spans"] if b > t0 and a < t1),
                 key=lambda e: (e.start, -e.end))
    stack: List[Event] = []
    for e in evs:                  # the program's spans nest on one thread
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack:
            stack[-1].child_ns += e.end - e.start
            stack[-1].leaf = False
        stack.append(e)
    clip = [(max(a, t0), min(b, t1)) for a, b in events["busy"]
            if b > t0 and a < t1]
    steps = tr.union([(max(a, t0), min(b, t1)) for a, b in events["steps"]
                      if b > t0 and a < t1])
    return Spans(evs, steps, tr.union(clip), (t0, t1))


def extract(path: str) -> dict:
    """The plain events `build` takes, from one ``.xplane.pb`` (or
    ``.xplane.pb.gz``) of the harness."""
    from jax.profiler import ProfileData
    from bench import trace_reduce as tr
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(path)
    busy: List[list] = []
    spans: List[list] = []
    window, steps = None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    busy.extend([e.start_ns, e.start_ns + e.duration_ns]
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PREFIX):
                        spans.append([e.name, e.start_ns, end,
                                      dict(e.stats)])
                    elif e.name == tr.WINDOW:
                        window = [e.start_ns, end]
                    elif e.name.startswith(tr.SPAN_PREFIX):
                        steps.append([e.start_ns, end])
    if window is None:
        raise ValueError(f"trace holds no {tr.WINDOW!r} span")
    return {"window": window, "steps": steps, "busy": busy, "spans": spans}


_CACHE: Dict[str, tuple] = {}


def load(path: Optional[str] = None) -> Optional[Spans]:
    """The spans of the harness's newest trace (or of ``path``); None
    where there is no trace.  Read once per file."""
    from bench import harness, trace_reduce as tr
    try:
        path = path or tr.find_xplane(harness.TRACE_DIR)
        st = os.stat(path)
    except FileNotFoundError:
        return None
    key = (st.st_mtime_ns, st.st_size)
    hit = _CACHE.get(path)
    if hit is None or hit[0] != key:
        _CACHE.clear()
        _CACHE[path] = (key, build(extract(path)))
    return _CACHE[path][1]


def per_call(name: str, child: Optional[str] = None) -> Optional[float]:
    """Mean time of one ``name`` span less its ``child`` spans [ms]."""
    sp = load()
    n = sp.count(name) if sp else 0
    if not n:
        return None
    return 1e3 * (sp.total(name) - (sp.total(child) if child else 0.0)) / n


def per_unit(name: str, units: float) -> Optional[float]:
    """Summed time of every ``name`` span over ``units`` [s per unit]."""
    sp = load()
    if not sp or not sp.count(name) or units <= 0:
        return None
    return sp.total(name) / units

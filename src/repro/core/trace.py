"""Profiler spans for the planner and the simulator.

``with span("alloc_all"):`` opens a `jax.profiler.TraceAnnotation`
named ``igniter.alloc_all``.  While a profiler trace records
(`jax.profiler.trace`), the span is a host event of that trace, on the
same clock as the device's operations, so a device-idle gap can be
charged to the program region the host was in.  With no trace
recording, a span costs about a microsecond.

A span always times its region (``span.ms``, wall milliseconds).  Given
a `Telemetry` recorder (duck-typed, as every ``telemetry=`` keyword in
core is) and a ``wall`` key, it also adds that wall to
``telemetry.walls[wall]``: the span and the wall are one timing of one
region.

Counters ride on the open span (`span.set`), and only while a trace
records (`active()`): a caller computes a counter only then, so no
counter costs anything with the profiler off.

Spans sit at call granularity, never inside a per-entry, per-device,
per-instance or per-pass loop.  `docs/observability.md` lists every
span and counter.

This module imports `jax.profiler` alone: `perf_model_jax` switches on
float64 process-wide, and the numpy planner must not.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

PREFIX = "igniter."


def active() -> bool:
    """True while a profiler trace records."""
    return TraceAnnotation.is_enabled()


class span:
    """One program region as a profiler span ``igniter.<name>``."""

    __slots__ = ("_ann", "_telemetry", "_wall", "_t0", "ms")

    def __init__(self, name: str, telemetry=None,
                 wall: Optional[str] = None):
        self._ann = TraceAnnotation(PREFIX + name)
        self._telemetry = telemetry if wall is not None else None
        self._wall = wall
        self.ms = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1000.0
        if self._telemetry is not None:
            self._telemetry.add_wall(self._wall, self.ms)
        self._ann.__exit__(*exc)

    def set(self, **counters) -> None:
        """Attach counters to this span; call only under `active()`."""
        self._ann.set_metadata(**counters)


def spanned(name: str) -> Callable:
    """Decorator: every call of the function runs inside `span(name)`."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap

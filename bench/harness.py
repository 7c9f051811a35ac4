"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The harness finds
everything by name:

- the configuration's file, from the ``configs`` entry of BENCHMARK.json;
- the traffic mix ``bench/traffic/<traffic>.json``, whose ``entry`` names
  the driver ``bench/drivers/<entry>.py`` that drives one public entry of
  the system with the mix's parameters;
- each per-layer metric's reader ``bench/metrics/<name>.py``, or, where no
  file has the whole name, ``bench/metrics/<name up to its first dot>.py``.

A driver provides ``setup()``, ``warmup()``, ``step()`` (one unit of
measured work, returning a record), ``end_to_end(records, window_s)``
and ``check(records)`` (the comparison with the plain reference, as
``{name: (value, limit)}``; a check passes while ``value <= limit``),
and may provide ``facts(records)`` for the per-layer readers.  It counts
the steps whose answers were wrong in ``failed``.  The driver's module
also provides ``control(config, dtype)``, the program attributes that
its plain reference in a lower precision replaces (`bench/control.py`).

The run: set-up and warm-up (counted in ``setup_s`` from the start of
the process), then steps for ``--seconds`` seconds, all of them started
inside the window and each finished; then the device's peak memory,
then the reference check.  With ``--trace 1`` the first ``trace_steps``
steps of the window (a number the traffic mix sets) run under the
profiler, and the result carries the per-layer metrics read from that
trace and a breakdown instead of the end-to-end metrics.  The last line
of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_path(name: str, bench_dir: str = BENCH) -> str:
    """The reader of a per-layer metric: its own file, else its family's."""
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(bench_dir, "metrics", stem + ".py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_path = os.path.join(root,
                                        configs[self.spec["config"]]["file"])
        bench_dir = os.path.join(root, "bench")
        self.traffic_path = os.path.join(bench_dir, "traffic",
                                         self.spec["traffic"] + ".json")
        with open(self.config_path) as fh:
            self.config = json.load(fh)
        with open(self.traffic_path) as fh:
            self.traffic = json.load(fh)
        self.driver_path = os.path.join(bench_dir, "drivers",
                                        self.traffic["entry"] + ".py")
        if not os.path.exists(self.driver_path):
            raise FileNotFoundError(self.driver_path)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
        self.readers = {m["name"]: metric_path(m["name"], bench_dir)
                        for m in self.per_layer}

    def module(self):
        """The driver's module: its ``Driver`` and its ``control``."""
        return _load_module(self.driver_path,
                            "bench_driver_" + self.traffic["entry"])

    def driver(self, seed: int):
        return self.module().Driver(self.config, self.traffic, seed)

    def reader(self, metric: str) -> Callable:
        return _load_module(self.readers[metric],
                            "bench_metric_" + metric.replace(".", "_")).read


def check_devices(chips: int):
    """The accelerators JAX sees; raises where they are not enough."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < chips:
        raise NoAccelerator(
            f"needs {chips} accelerator chip(s); JAX found "
            f"{len(devices)} {devices[0].platform!r} device(s)")
    return devices


def setup_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    from benchmarks.compile_cache import setup_compile_cache as _setup
    _setup()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Span:
    """A harness span: a profiler annotation while tracing, else nothing."""

    def __init__(self, tracing: bool):
        self.tracing = tracing

    def __call__(self, name: str):
        if self.tracing:
            import jax
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()


def _compile_counter():
    """Counts backend compilations from here on."""
    import jax
    box = [0]

    def listener(event: str, *args, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def _trace_steps(driver, span: Span, n: int, records: List) -> None:
    """The first ``n`` steps of the window under the profiler, inside a
    ``window`` span; host Python calls are not traced."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with span("window"):
            for _ in range(n):
                records.append(driver.step())
    finally:
        jax.profiler.stop_trace()


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices=None) -> dict:
    """One run of ``cell``; returns the result object."""
    span = Span(trace)
    driver = cell.driver(seed)
    driver.span = span
    driver.setup()
    driver.warmup()
    setup_s = time.perf_counter() - t_start
    compiles = _compile_counter()
    records: List = []
    traced = int(cell.traffic.get("trace_steps", 0)) if trace else 0
    w0 = time.perf_counter()
    if trace:
        _trace_steps(driver, span, traced, records)
    while time.perf_counter() - w0 < seconds:
        records.append(driver.step())
    window_s = time.perf_counter() - w0
    n_compiles = compiles[0]
    device = _device_info(devices)
    result: Dict = {"attempted": len(records)}
    if trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce(trace_reduce.extract(
            trace_reduce.find_xplane(TRACE_DIR)))
        facts = {"steps": traced, "window_s": summary.window_s}
        if hasattr(driver, "facts"):
            facts.update(driver.facts(records[:traced]))
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(summary, facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = trace_reduce.breakdown(summary)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        e2e = driver.end_to_end(records, window_s)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = driver.check(records)
    checks["compiles_in_window"] = (n_compiles, 0)
    failed = int(getattr(driver, "failed", 0))
    correct = all(v <= lim for v, lim in checks.values())
    result.update({"correct": correct, "failed": failed, "metrics": metrics,
                   "device": device})
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _device_info(devices) -> dict:
    if devices is None:
        import jax
        devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict) -> None:
    """Each compared number beside its limit as the last lines on stderr;
    the result object as the last line on stdout."""
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    order = ["correct", "attempted", "failed", "metrics", "device"]
    if "breakdown" in result:
        order.append("breakdown")
    order.append("checks")
    sys.stderr.flush()
    print(json.dumps({k: result[k] for k in order}), flush=True)


def parse(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        cell = Cell(load_benchmark(), args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        devices = check_devices(cell.chips)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    setup_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                 devices)
    emit(result)
    return 0

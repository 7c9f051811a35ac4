"""The control of each cell's check: the plain reference, computed one
precision below the deployment's float64, put in the program's place,
and judged by the harness's own comparison.

Each driver's ``control(config, dtype)`` names the program attributes
that its reference replaces; `patched` installs them for the length of a
run.  Nothing here runs in a benchmark run.  The control's readings give
the upper end of each check's limit; ``bench/tests/test_control.py``
keeps them at a size a test run can hold, and

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

prints the run's result, each compared number beside its limit, as one
JSON line at the cell's own size.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness as H  # noqa: E402


@contextlib.contextmanager
def patched(replacements: dict):
    """Set each ``(module, name)`` to its replacement; restore on exit."""
    saved = {key: getattr(*key) for key in replacements}
    try:
        for (mod, name), fn in replacements.items():
            setattr(mod, name, fn)
        yield
    finally:
        for (mod, name), fn in saved.items():
            setattr(mod, name, fn)


def run(cell: H.Cell, seed: int, seconds: float, dtype=np.float32) -> dict:
    """One run of ``cell`` with the reference in ``dtype`` in the
    program's place."""
    with patched(cell.module().control(cell.config, dtype)):
        return H.run(cell, seed, seconds, False, time.perf_counter())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(H.ROOT, "src"))
    cell = H.Cell(H.load_benchmark(), args.workload)
    t0 = time.perf_counter()
    res = run(cell, args.seed, args.seconds)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control": "float32", "correct": res["correct"],
                      "attempted": res["attempted"],
                      "checks": res["checks"],
                      "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

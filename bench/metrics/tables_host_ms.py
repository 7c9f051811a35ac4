"""The simulator's latency-table builds on the host side, each call whole
[ms per simulated second]: the program span ``igniter.sim.tables``."""
from bench import program_spans


def read(summary, facts):
    s = program_spans.per_unit("igniter.sim.tables",
                               facts.get("simulated_s", 0.0))
    return None if s is None else 1e3 * s

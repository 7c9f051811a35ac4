"""Simulator set-up, the instances and their arrival and noise streams
[ms per simulated second]: the program span ``igniter.sim.setup``."""
from bench import program_spans


def read(summary, facts):
    s = program_spans.per_unit("igniter.sim.setup",
                               facts.get("simulated_s", 0.0))
    return None if s is None else 1e3 * s

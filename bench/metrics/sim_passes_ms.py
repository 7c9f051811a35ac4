"""The simulator's pass recurrence, every epoch's sweep over all instances
[ms per simulated second]: the program span ``igniter.sim.passes``."""
from bench import program_spans


def read(summary, facts):
    s = program_spans.per_unit("igniter.sim.passes",
                               facts.get("simulated_s", 0.0))
    return None if s is None else 1e3 * s

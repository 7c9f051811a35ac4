"""Alg. 2 loop iterations of one grant-loop call: the mean of the
``iters`` counter that the program span ``igniter.alloc_all`` carries."""
from bench import program_spans


def read(summary, facts):
    sp = program_spans.load()
    return sp.counter_mean("igniter.alloc_all", "iters") if sp else None

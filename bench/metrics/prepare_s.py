"""Theorem 1 for every workload, sorted (Alg. 1 lines 2-3) [s per step]:
the program span ``igniter.prepare``."""
from bench import program_spans


def read(summary, facts):
    return program_spans.per_unit("igniter.prepare", facts["steps"])

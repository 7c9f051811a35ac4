"""Alg. 1 lines 8-14 after each grant-loop call, the choice of device
and the cluster update [s per step]: the program span ``igniter.place``."""
from bench import program_spans


def read(summary, facts):
    return program_spans.per_unit("igniter.place", facts["steps"])

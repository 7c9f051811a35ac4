"""The cluster rebuild of one arrival, the device grouping of the plan
and its ``VecCluster`` [ms per step]: the program span
``igniter.cluster_build``."""
from bench import program_spans


def read(summary, facts):
    s = program_spans.per_unit("igniter.cluster_build", facts["steps"])
    return None if s is None else 1e3 * s

"""Device rows copied to the device by one Alg. 2 grant-loop call: the
mean of the ``rows_sent`` counter that the program span
``igniter.alloc_all`` carries (the cluster's row capacity where the call
copied the whole state)."""
from bench import program_spans


def read(summary, facts):
    sp = program_spans.load()
    return sp.counter_mean("igniter.alloc_all", "rows_sent") if sp else None

"""Host time of one Alg. 2 grant-loop call [ms]: the program span
``igniter.alloc_all`` less its ``igniter.alloc_all.fetch`` child (where
the host waits on the device and the copies), per call."""
from bench import program_spans


def read(summary, facts):
    return program_spans.per_call("igniter.alloc_all",
                                  "igniter.alloc_all.fetch")

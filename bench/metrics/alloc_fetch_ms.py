"""Fetch time of one Alg. 2 grant-loop call [ms]: the program span
``igniter.alloc_all.fetch``, the host waiting on the jitted loop and
copying its answer back, per ``igniter.alloc_all`` call."""
from bench import program_spans


def read(summary, facts):
    sp = program_spans.load()
    if not sp or not sp.count("igniter.alloc_all.fetch"):
        return None
    return 1e3 * sp.total("igniter.alloc_all.fetch") / \
        sp.count("igniter.alloc_all")

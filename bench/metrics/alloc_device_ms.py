"""Device time of one Alg. 2 grant-loop call [ms]: the time of the jitted
``_alloc_all_jit`` module in the trace over its executions."""

MODULE = "_alloc_all_jit"


def read(summary, facts):
    seconds, runs = summary.modules.get(MODULE, (0.0, 0))
    return 1e3 * seconds / runs if runs else None

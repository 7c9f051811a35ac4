"""Device time of the simulator's latency-table builds (the jitted
``_tables_jit`` module) per simulated second [ms/s]."""

MODULE = "_tables_jit"


def read(summary, facts):
    seconds, runs = summary.modules.get(MODULE, (0.0, 0))
    sim_s = facts.get("simulated_s", 0.0)
    return 1e3 * seconds / sim_s if runs and sim_s > 0 else None

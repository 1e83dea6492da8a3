"""Device milliseconds per job in top-level ops under the ``groupby``
operator scope and outside any ``shuffle`` scope: the local work of the
plan's groupby nodes (``planner.physical.eval_node``).  Ops are named by
the scopes in their programs' HLO (``chipbench.program``); summed per
chip, averaged over chips, divided by the jobs traced."""

from chipbench.program import scope_ms_per_job


def read(run):
    return scope_ms_per_job(run, "groupby")

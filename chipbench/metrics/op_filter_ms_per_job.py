"""Device milliseconds per job in top-level ops under the ``filter``
operator scope and outside any ``shuffle`` scope: the predicates and the
compaction of the plan's filter nodes (``ops_local.filter_expr``).  Ops
are named by the scopes in their programs' HLO (``chipbench.program``);
summed per chip, averaged over chips, divided by the jobs traced."""

from chipbench.program import scope_ms_per_job


def read(run):
    return scope_ms_per_job(run, "filter")

"""Device milliseconds per job in top-level ops under a ``shuffle``
scope, whichever operator called it: hash destinations, the
radix partition, the all-to-all and the receive-side compaction
(``dataframe.shuffle``).  Ops are named by the scopes in their programs'
HLO (``chipbench.program``); summed per chip, averaged over chips,
divided by the jobs traced."""

from chipbench.program import scope_ms_per_job


def read(run):
    return scope_ms_per_job(run, "shuffle")

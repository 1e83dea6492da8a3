"""Device milliseconds per job in HLO ``sort`` ops: the local join and
groupby lexsorts (``dataframe.ops_local``) and XLA's TPU lowering of the
shuffle's scatters (``dataframe.shuffle``).  Summed per chip, averaged
over chips, divided by the jobs traced."""


def read(run):
    s = run.op_seconds(("sort",))
    return None if s is None else 1e3 * s / run.jobs

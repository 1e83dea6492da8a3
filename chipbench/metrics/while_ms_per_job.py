"""Device milliseconds per job in HLO ``while`` loops, body included: on
this program the binary searches of ``jnp.searchsorted`` in the local join
(``dataframe.ops_local.join_local``), one loop per search.  Summed per
chip, averaged over chips, divided by the jobs traced."""


def read(run):
    s = run.op_seconds(("while",), top_level=True)
    return None if s is None else 1e3 * s / run.jobs

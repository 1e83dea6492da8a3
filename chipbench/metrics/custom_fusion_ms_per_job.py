"""Device milliseconds per job in fusions that XLA's TPU backend emits
with ``kind=kCustom``, outside any loop: on this program the gathers and
scatters of the join, groupby and shuffle (their operands are a table
column and a row index of another length).  Summed per chip, averaged
over chips, divided by the jobs traced."""


def read(run):
    s = run.op_seconds(("fusion",), fusion="kCustom", top_level=True)
    return None if s is None else 1e3 * s / run.jobs

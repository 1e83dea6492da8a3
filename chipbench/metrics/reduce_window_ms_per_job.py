"""Device milliseconds per job in HLO ``reduce-window`` ops: XLA's TPU
lowering of ``cumsum``, the prefix sums of the shuffle's partition and
compaction (``kernels.radix_partition.xla``, ``dataframe.shuffle``).
Summed per chip, averaged over chips, divided by the jobs traced."""


def read(run):
    s = run.op_seconds(("reduce-window",))
    return None if s is None else 1e3 * s / run.jobs

"""Device milliseconds per job in collectives (all-to-all, all-gather,
all-reduce, reduce-scatter, collective-permute: the ``comm`` layer).
Summed per chip, averaged over chips, divided by the jobs traced."""

from chipbench.trace import COLLECTIVES


def read(run):
    s = run.op_seconds(COLLECTIVES)
    return None if s is None else 1e3 * s / run.jobs

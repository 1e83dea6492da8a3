"""Device milliseconds per job in HLO ``while`` loops, body included: on
this program the shuffle's radix partition (``kernels/radix_partition/
xla.py``), whose blocked ``lax.scan`` runs where rows times buckets pass
2**22, as on four chips; with one bucket on one chip it takes the dense
path and no loop.  Summed per chip, averaged over chips, divided by the
jobs traced."""


def read(run):
    s = run.op_seconds(("while",), top_level=True)
    return None if s is None else 1e3 * s / run.jobs

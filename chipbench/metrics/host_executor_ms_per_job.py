"""Host milliseconds per job in the executor's own spans: ``adapt:sample``
(the skew detector's key sample, read back from the devices on more than
one chip), ``dispatch`` (``CylonEnv.run`` until the program is enqueued)
and ``readback`` (the shuffle counters read back, ``ExecStats`` built).
From the traced jobs' own spans (``chipbench.program``)."""

from chipbench.program import host_ms_per_job


def read(run):
    return host_ms_per_job(run, ("adapt:sample", "dispatch", "readback"))

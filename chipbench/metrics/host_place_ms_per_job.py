"""Host milliseconds per job in the engine's ``place:<table>`` spans:
routing each host-held scan table into per-chip buffers and handing them
to the devices (``core.store.rescatter`` -> ``core.env.put_rows``), once
per table per job.  From the traced jobs' own spans
(``chipbench.program``)."""

from chipbench.program import host_ms_per_job


def read(run):
    return host_ms_per_job(run, ("place:",))

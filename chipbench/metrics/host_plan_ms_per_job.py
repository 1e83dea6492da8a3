"""Host milliseconds per job in the engine's ``plan`` span: building,
optimising, lowering and fingerprinting the query's plan
(``planner.compile_plan``), which runs on every ``collect``.  From the
traced jobs' own spans (``chipbench.program``)."""

from chipbench.program import host_ms_per_job


def read(run):
    return host_ms_per_job(run, ("plan",))

"""Run ``fig9-4chip.incore`` at a tiny size on four CPU devices with the
engine tracing itself, and read the seven engine-fed per-layer metrics;
prints them as one JSON line.  The CPU backend records no device ops, so
each virtual chip gets one synthetic op of 1000 ns per operator scope,
named after an op of the program that ran.  Started by
``test_program_trace.py`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

import json
import os
import sys
import tempfile

sys.path[:0] = [os.path.join(os.path.dirname(__file__), *up)
                for up in ((os.pardir, os.pardir),
                           (os.pardir, os.pardir, "src"))]

import jax  # noqa: E402

from chipbench.bench import Workload  # noqa: E402
from chipbench.program import op_scopes  # noqa: E402
from chipbench.spec import load_reader  # noqa: E402
from chipbench.tests.conftest import SEED, tiny_cell  # noqa: E402
from chipbench.trace import Op, TraceRun  # noqa: E402

READERS = ("host_plan_ms_per_job", "host_place_ms_per_job",
           "host_executor_ms_per_job", "op_join_ms_per_job",
           "op_groupby_ms_per_job", "op_sort_ms_per_job",
           "op_shuffle_ms_per_job")
SCOPES = ("join", "groupby", "sort", "shuffle")
OP_NS = 1000


def main():
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    with tempfile.TemporaryDirectory() as work:
        wl = Workload(tiny_cell("fig9-4chip.incore"), SEED, devices, work)
        stats = []
        for _ in range(2):
            q = wl.frame()
            res, st = q.collect(env=wl.env, mode="bsp", collect_stats=True,
                                trace=True)
            jax.block_until_ready((res.columns, res.row_counts))
            stats.append(st)
    run = TraceRun({}, [("job", 0, 10 * OP_NS)], 2, stats)
    scopes = op_scopes(run)
    named = {s: next(n for n, k in sorted(scopes.items()) if k == s)
             for s in SCOPES}
    run.ops = {f"/device:CPU:{c}": [
        Op(named[s], "fusion", i * OP_NS, (i + 1) * OP_NS)
        for i, s in enumerate(SCOPES)] for c in range(4)}
    print(json.dumps({"readings": {m: load_reader(m)(run) for m in READERS},
                      "programs": [len(st.trace.programs) for st in stats],
                      "op_ns": OP_NS}))


if __name__ == "__main__":
    main()

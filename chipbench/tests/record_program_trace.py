"""Record a profiler trace of a few whole jobs with the engine's own spans,
and read it as the per-layer metrics do.

  python chipbench/tests/record_program_trace.py [--workload W] [--rows N]
      [--jobs J] [--seed S] [--out F] [--overhead-seconds T] [--json F]

On a TPU, runs a cell (default ``fig9-1chip.incore``) at ``N`` rows per
table per chip (default: the cell's own size): one warm-up job, then ``J``
whole jobs (default 2) under ``jax.profiler`` with the harness's spans,
where the engine traces itself.  Prints one JSON object (also written to
``--json``): the cell's per-layer metrics read from the trace; the idle
time of the window per innermost span, the engine's or the harness's; the
device time per operator scope; the breakdown with idle gaps named by the
engine's spans; and what building the op -> scope map cost.

``--out F`` keeps the trace (gzipped when ``F`` ends in ``.gz``) and the
op -> scope map beside it (``F`` up to ``.xplane.pb``, then
``.scopes.json``): the fixtures under ``data/`` that
``test_program_trace.py`` reads were made so, at 4096 rows.
``--overhead-seconds T`` then runs closed-loop windows of ``T`` seconds
with no profiler, alternating the engine's tracing off and on, twice
each, and reports the input rows per second of each.
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from chipbench.bench import Workload  # noqa: E402
from chipbench.program import (SpanRun, load_engine_spans,  # noqa: E402
                               op_scopes, scope_seconds, scopes_path)
from chipbench.run import enable_compile_cache, require_chips  # noqa: E402
from chipbench.spec import resolve  # noqa: E402
from chipbench.tests.conftest import SEED, tiny_cell  # noqa: E402
from chipbench.trace import load_profile  # noqa: E402


def rows_per_s(wl: Workload, seconds: float, trace: bool) -> float:
    """Input rows per second of a closed loop of whole jobs, no profiler,
    with the engine's tracing ``trace``."""
    jobs, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        q = wl.frame()
        res, _ = q.collect(env=wl.env, mode="bsp", collect_stats=True,
                           trace=trace)
        jax.block_until_ready((res.columns, res.row_counts))
        jobs += 1
    return wl.input_rows * jobs / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="fig9-1chip.incore")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--out", default=None)
    ap.add_argument("--overhead-seconds", type=float, default=0.0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    cell = (tiny_cell(args.workload, args.rows) if args.rows
            else resolve(args.workload))
    devices = require_chips(cell.chips)
    enable_compile_cache()
    work = os.path.join(ROOT, "chipbench", ".trace", "record-program")
    shutil.rmtree(work, ignore_errors=True)
    t0 = time.perf_counter()
    wl = Workload(cell, args.seed, devices, os.path.join(work, "data"))
    t_data = time.perf_counter()
    wl.run_job()
    t_warm = time.perf_counter()
    stats, job_s = [], []
    with jax.profiler.trace(os.path.join(work, "trace")):
        for _ in range(args.jobs):
            start = time.perf_counter()
            with jax.profiler.TraceAnnotation("job"):
                _, st = wl.run_job()
            job_s.append(time.perf_counter() - start)
            stats.append(st)
    (path,) = glob.glob(os.path.join(work, "trace", "**", "*.xplane.pb"),
                        recursive=True)
    ops, spans = load_profile(path)
    run = SpanRun(ops, spans, args.jobs, stats, wl.input_rows,
                  engine=load_engine_spans(path))
    t_map = time.perf_counter()
    scopes = op_scopes(run)
    map_s = time.perf_counter() - t_map
    per_scope = scope_seconds(run) or {}
    engine_ms = {}
    for name, s, e in run.engine:
        engine_ms[name] = engine_ms.get(name, 0.0) + (e - s) * 1e-6 / run.jobs
    idle = run.idle_by_phase()
    report = {
        "workload": cell.name,
        "rows_per_chip": cell.config["in_core_rows_per_chip"],
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": {"data_s": t_data - t0, "warm_s": t_warm - t_data,
                    "scope_map_s": map_s},
        "jobs": args.jobs, "job_s": job_s,
        "window_s": run.window_s, "busy_s": run.busy_s,
        "metrics": {m.name: m.read(run) for m in cell.per_layer},
        "idle_s": sum(idle.values()),
        "idle_named_by_engine_share": run.engine_idle_share(),
        "idle_ms_per_job": {k: 1e3 * v / run.jobs for k, v in
                            sorted(idle.items(), key=lambda kv: -kv[1])},
        "scope_ms_per_job": {k or "(none)": 1e3 * v / run.jobs
                             for k, v in sorted(per_scope.items(),
                                                key=lambda kv: -kv[1])},
        "engine_span_ms_per_job": engine_ms,
        "breakdown": run.breakdown(),
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        if args.out.endswith(".gz"):
            with open(path, "rb") as src, gzip.open(args.out, "wb", 9) as dst:
                shutil.copyfileobj(src, dst)
        else:
            shutil.copy(path, args.out)
        with open(scopes_path(args.out), "w") as f:
            json.dump(scopes, f, sort_keys=True, indent=0)
            f.write("\n")
    if args.overhead_seconds:
        off, on = [], []
        for _ in range(2):
            off.append(rows_per_s(wl, args.overhead_seconds, False))
            on.append(rows_per_s(wl, args.overhead_seconds, True))
        report["overhead"] = {
            "rows_per_s_trace_off": off, "rows_per_s_trace_on": on,
            "traced_job_s_median": statistics.median(job_s)}
    shutil.rmtree(work, ignore_errors=True)
    text = json.dumps(report)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)


if __name__ == "__main__":
    main()

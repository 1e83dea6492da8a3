"""One run of one cell: set-up, a closed loop of whole jobs, the check.

Set-up reads the cell's Parquet dataset once.  A job is one query over
those frames, fenced until its result is on the device.  Jobs run back to
back from one client.  None starts once ``seconds`` have passed since the
window opened.  The untraced run reports the cell's end-to-end metrics;
the traced run records a profiler trace of a few whole jobs and reports
the per-layer metrics read from it.  Either run compares with the cell's
reference, once the window has closed, a sample of the window's results
drawn from the seed and its last result.

The data, the job and the comparison come from the cell's own modules
(``spec.Cell.recipe`` and ``spec.Cell.query``); nothing here names a
table, a column or an operation.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from .data import write_dataset
from .spec import Cell
from .trace import read_trace

#: jobs of the window compared with the reference, besides the last one:
#: a uniform sample over the whole window, drawn from the seed
SAMPLED_JOBS = 6
TRACE_DIR = ".trace"


@dataclasses.dataclass
class Job:
    start: float
    end: float
    stats: Any
    compiled: int


class CompileCounter:
    """Counts programs traced or compiled in this process (JAX's own
    monitoring events), so that a compile inside the window shows,
    whether or not the engine's own program cache missed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def read_tables(cell: Cell, seed: int, chips: int
                ) -> Dict[str, Dict[str, np.ndarray]]:
    """The tables the cell's query reads, as its recipe makes them for
    ``chips`` chips from ``seed``, in the order the query names them."""
    names = cell.query.tables_read(cell.traffic["query"])
    tables = cell.recipe.make_tables(cell.config, seed, chips, names)
    return {name: tables[name] for name in names}


def count_rows(tables: Dict[str, Dict[str, np.ndarray]]) -> int:
    """Rows of all ``tables`` together: the input rows of one job."""
    return sum(len(next(iter(t.values()))) for t in tables.values())


class Workload:
    """The cell's data, frames and job, built from its files and a seed."""

    def __init__(self, cell: Cell, seed: int, devices, data_dir: str):
        import repro.df as rdf
        from repro.core import CylonEnv
        self.env = CylonEnv(list(devices))
        self.cell = cell
        self.tables = read_tables(cell, seed, len(devices))
        self.input_rows = count_rows(self.tables)
        files = int(cell.config["parquet_files_per_table"])
        self.frames = {}
        for name, t in self.tables.items():
            glob = write_dataset(t, os.path.join(data_dir, name), files)
            self.frames[name] = rdf.read_parquet(glob, env=self.env,
                                                 name=name)

    def frame(self):
        """The job's lazy frame, built anew as a client would."""
        cell = self.cell
        return cell.query.build_frame(self.frames, cell.traffic["query"],
                                      cell.config)

    def run_job(self):
        """One whole job; returns ``(result, ExecStats)``."""
        q = self.frame()
        with jax.profiler.TraceAnnotation("collect"):
            res, stats = q.collect(env=self.env, mode="bsp",
                                   collect_stats=True)
        with jax.profiler.TraceAnnotation("fence"):
            jax.block_until_ready((res.columns, res.row_counts))
        return res, stats


def _failed(job: Job) -> bool:
    s = job.stats
    return bool(s.rows_dropped or s.degraded or s.retries or job.compiled)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, work_dir: str, control_dtype=None,
             init_parts: Optional[Dict[str, float]] = None
             ) -> Dict[str, Any]:
    """Run ``cell`` once on ``devices``; returns the result object.

    ``t_start`` is when the process started.  ``control_dtype`` replaces
    the engine's answers by the reference's computed in that lower
    precision: the control that the comparison has to reject.
    ``init_parts`` are the caller's timings of what came before (JAX's
    import, the backend's start), reported with the rest of set-up."""
    counter = CompileCounter()
    try:
        return _run_cell(cell, seed, seconds, trace, devices, t_start,
                         work_dir, control_dtype, counter, init_parts or {})
    finally:
        counter.close()


def _run_cell(cell, seed, seconds, trace, devices, t_start, work_dir,
              control_dtype, counter, init_parts):
    t_init = time.perf_counter()
    data_dir = os.path.join(work_dir, ".data", cell.name)
    wl = Workload(cell, seed, devices, data_dir)
    t_data = time.perf_counter()
    wl.run_job()                                   # warm-up: compiles
    t_warm = time.perf_counter()
    rng = np.random.default_rng([seed % (1 << 63), 7])
    max_jobs = int(cell.traffic.get("trace_jobs", 3)) if trace else None
    trace_dir = os.path.join(work_dir, TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    jobs: List[Job] = []
    kept: List[Any] = []        # a uniform sample of the jobs so far
    t0 = time.perf_counter()
    try:
        while (time.perf_counter() - t0 < seconds
               and (max_jobs is None or len(jobs) < max_jobs)):
            c0 = counter.count
            start = time.perf_counter()
            with jax.profiler.TraceAnnotation("job"):
                res, stats = wl.run_job()
            jobs.append(Job(start, time.perf_counter(), stats,
                            counter.count - c0))
            if len(jobs) <= SAMPLED_JOBS:
                kept.append(res)
            else:
                slot = int(rng.integers(0, len(jobs)))
                if slot < SAMPLED_JOBS:
                    kept[slot] = res
    finally:
        if trace:
            jax.profiler.stop_trace()
    if not any(r is res for r in kept):
        kept.append(res)                          # the last job, always
    del res
    window = jobs[-1].end - jobs[0].start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    with jax.profiler.TraceAnnotation("compare"):
        results = [r.to_numpy(nulls="mask") for r in kept]
        kept.clear()
        query, spec = cell.query, cell.traffic["query"]
        ref = query.reference(wl.tables, spec)
        if control_dtype is not None:
            ctl = query.reference(wl.tables, spec, control_dtype)
            results = [ctl for _ in results]
        readings: Dict[str, float] = {}
        for got in results:
            for k, v in query.compare(got, ref).items():
                readings[k] = max(readings.get(k, 0.0), v)
    limits = cell.traffic["limits"]
    missing = sorted(set(readings) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the traffic file")
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in sorted(readings)}
    correct = bool(results) and all(c["value"] <= c["limit"]
                                    for c in checks.values())

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(jobs),
                           "failed": sum(_failed(j) for j in jobs)}
    if trace:
        run = read_trace(trace_dir, jobs=len(jobs),
                         stats=[j.stats for j in jobs],
                         input_rows=wl.input_rows)
        metrics = {}
        for m in cell.per_layer:
            v = m.read(run)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        device.update(busy_s=run.busy_s, window_s=run.window_s)
        out.update(metrics=metrics, device=device,
                   breakdown=run.breakdown())
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        setup = jobs[0].start - t_start
        values = {"rows_per_s": wl.input_rows * len(jobs) / window,
                  "setup_s": setup}
        out.update(metrics={m.name: {"value": values[m.name],
                                     "unit": m.unit}
                            for m in cell.end_to_end},
                   device=device)
    job_s = [j.end - j.start for j in jobs]
    out["setup_parts"] = {**init_parts, "init_s": t_init - t_start,
                          "data_s": t_data - t_init,
                          "warm_s": t_warm - t_data,
                          "jobs": len(jobs), "window_s": window,
                          "job_s": [min(job_s), statistics.median(job_s),
                                    max(job_s)],
                          "compiles_in_window": sum(j.compiled for j in jobs),
                          "compared": len(results)}
    out["checks"] = checks
    shutil.rmtree(data_dir, ignore_errors=True)
    return out

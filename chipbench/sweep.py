"""Scale sweep on the chip: how long one warm job of a cell takes at each
size, to choose the rows of its configuration.

  python3 chipbench/sweep.py --workload fig9-1chip.incore --exp 18,19,20

For each exponent e it sets the cell's rows per table per chip to 2^e,
builds the cell's data and frames as a run does, and times one compiling
call and two warm jobs.  It stops growing once a warm
job passes ``--max-job-s``.  One JSON line per size goes to stdout.
"""

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--exp", default="18,19,20,21,22")
    ap.add_argument("--max-job-s", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1234567)
    args = ap.parse_args(argv)
    from chipbench.bench import Workload
    from chipbench.run import enable_compile_cache, require_chips
    from chipbench.spec import resolve
    cell = resolve(args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache()
    key = "in_core_rows_per_chip"
    for exp in (int(e) for e in args.exp.split(",")):
        cell.config[key] = 1 << exp
        wl = Workload(cell, args.seed, devices,
                      os.path.join(HERE, ".data", "sweep"))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            wl.run_job()
            times.append(time.perf_counter() - t0)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        print(json.dumps({"workload": args.workload, key: 1 << exp,
                          "cold_s": times[0], "warm_s": times[1:],
                          "memory_peak_bytes": peak}), flush=True)
        if min(times[1:]) > args.max_job_s:
            break
    shutil.rmtree(os.path.join(HERE, ".data", "sweep"), ignore_errors=True)


if __name__ == "__main__":
    main()

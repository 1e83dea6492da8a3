"""Readings that the limits of ``correct`` are set from, at a cell's own size.

  python chipbench/calibrate.py --workload <name> --seeds <n> --seconds <s>

For each of ``n`` seeds it makes one run of the cell with a short window
(the program's readings: the largest sets the lower reading of each
limit), and compares the reference computed in bfloat16 with the float32
one on the same tables (the control's readings: the smallest sets the
upper reading).  One JSON line per seed and a summary go to stdout.
Runs only on a TPU, in one process, so that the program compiles once.
"""

import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402

#: first seed; the others follow it, each above 2**32 like the driver's
SEED0 = 3_000_000_017


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed0", type=int, default=SEED0)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import ml_dtypes

    from chipbench.bench import read_tables, run_cell
    from chipbench.run import enable_compile_cache, require_chips
    from chipbench.spec import resolve
    cell = resolve(args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache()
    program, control = {}, {}
    for i in range(args.seeds):
        seed = args.seed0 + 7919 * i
        out = run_cell(cell, seed, args.seconds, False, devices,
                       time.perf_counter(), HERE)
        tables = read_tables(cell, seed, len(devices))
        spec = cell.traffic["query"]
        ref = cell.query.reference(tables, spec)
        ctl = cell.query.reference(tables, spec, ml_dtypes.bfloat16)
        c = cell.query.compare(ctl, ref)
        p = {k: v["value"] for k, v in out["checks"].items()}
        for k, v in p.items():
            program[k] = max(program.get(k, 0.0), v)
        for k, v in c.items():
            control[k] = min(control.get(k, float("inf")), v)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "jobs": out["attempted"], "failed": out["failed"],
                          "program": p, "control": c}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": program, "control_min": control,
                      "elapsed_s": time.perf_counter() - T_START}),
          flush=True)


if __name__ == "__main__":
    main()

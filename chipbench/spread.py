"""Run a cell several times, each run its own process, and report how far
its metrics spread: what the bounds in ``BENCHMARK.json`` are set from.

  python3 chipbench/spread.py --workload <name> --seeds 11,12,13 --sets 2 \\
      --seconds 20 [--trace 1] [--out chiprun_out/<name>.jsonl]

Each set runs every seed once, in order; with ``--sets 2`` both sets use
the same seeds.  For every metric and set it prints the values, the
median and the spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.  This
process never imports JAX, so each child has the chips to itself.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            cmd = [sys.executable, os.path.join(HERE, "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(args.seconds), "--trace", str(args.trace)]
            t0 = time.perf_counter()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=args.timeout)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1]) if p.returncode == 0 else None
            except (IndexError, json.JSONDecodeError):
                res = None
            rec = {"set": k, "seed": seed, "rc": p.returncode,
                   "wall_s": wall, "result": res}
            if res is None or not res.get("correct"):
                rec["stderr_tail"] = p.stderr[-4000:]
            print(json.dumps(rec), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            runs.append(rec)
        sets.append(runs)
    summary = {}
    for k, runs in enumerate(sets):
        ok = [r["result"] for r in runs if r["result"]]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            summary.setdefault(m, []).append({
                "set": k, "values": vals, "median": statistics.median(vals),
                "spread": spread(vals)})
        summary.setdefault("correct", []).append(
            [bool(r["result"] and r["result"]["correct"]) for r in runs])
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)


if __name__ == "__main__":
    main()

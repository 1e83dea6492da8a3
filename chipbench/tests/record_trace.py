"""Record the small profiler trace that ``test_trace.py`` reduces.

  python chipbench/tests/record_trace.py [--workload W --rows N --out F]

On a TPU, runs a cell (default ``fig9-1chip.incore``) at ``N`` rows per
table per chip (default 4096): one warm-up job, then two whole jobs under
``jax.profiler`` with the harness's spans, and keeps the ``.xplane.pb``
(default ``chipbench/tests/data/fig9_4096.xplane.pb``).  The four-chip
trace there was recorded with ``--workload fig9-4chip.incore`` and
compressed with ``gzip -9``, which ``trace.load_profile`` reads.
"""

import argparse
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from chipbench.bench import Workload  # noqa: E402
from chipbench.run import require_chips  # noqa: E402
from chipbench.tests.conftest import SEED, tiny_cell  # noqa: E402

ROWS = 4096
OUT = os.path.join(HERE, "data", f"fig9_{ROWS}.xplane.pb")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="fig9-1chip.incore")
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()
    cell = tiny_cell(args.workload, args.rows)
    devices = require_chips(cell.chips)
    work = os.path.join(ROOT, "chipbench", ".trace", "record")
    shutil.rmtree(work, ignore_errors=True)
    wl = Workload(cell, SEED, devices, os.path.join(work, "data"))
    wl.run_job()
    with jax.profiler.trace(os.path.join(work, "trace")):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("job"):
                wl.run_job()
    (path,) = glob.glob(os.path.join(work, "trace", "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(work, ignore_errors=True)
    print(args.out, os.path.getsize(args.out))


if __name__ == "__main__":
    main()

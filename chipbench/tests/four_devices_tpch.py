"""Run TPC-H Q3 at a tiny scale on four CPU devices: its GROUP BY through
``repro.df`` beside pandas, and the whole query through the harness;
prints both as one JSON line.  Started by ``test_tpch.py`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

import json
import os
import sys
import tempfile
import time

sys.path[:0] = [os.path.join(os.path.dirname(__file__), *up)
                for up in ((os.pardir, os.pardir),
                           (os.pardir, os.pardir, "src"))]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro.df as rdf  # noqa: E402
from chipbench.bench import read_tables, run_cell  # noqa: E402
from chipbench.data import write_dataset  # noqa: E402
from chipbench.tests.conftest import SEED, tiny_cell  # noqa: E402
from repro.core import CylonEnv  # noqa: E402


def main():
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    cell = tiny_cell("tpch-1chip.q3")
    spec = cell.traffic["query"]
    tables = read_tables(cell, SEED, 4)
    env = CylonEnv(devices)
    out = {}
    with tempfile.TemporaryDirectory() as work:
        frames = {name: rdf.read_parquet(
            write_dataset(t, os.path.join(work, name), 3), env=env, name=name)
            for name, t in tables.items()}
        got = (cell.query.revenue_by_group(frames, spec)
               .collect(env=env).to_numpy())
        ranked = cell.query.reference(tables, spec)["ranked"]
        order = np.lexsort((got["o_orderdate"], -got["revenue_sum"]))
        out["groups"] = len(ranked["revenue"])
        out["keys_equal"] = bool(
            len(order) == len(ranked["revenue"])
            and np.array_equal(got["l_orderkey"][order],
                               ranked["l_orderkey"])
            and np.array_equal(got["o_orderdate"][order],
                               ranked["o_orderdate"]))
        out["revenue_max_abs_err"] = float(np.max(np.abs(
            got["revenue_sum"][order] - ranked["revenue"]), initial=0.0))
        out["q3"] = run_cell(cell, SEED, 0.2, False, devices,
                             time.perf_counter(), work)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

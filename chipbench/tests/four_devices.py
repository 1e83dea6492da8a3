"""Run ``fig9-4chip.incore`` at a tiny size on four CPU devices, sound
and with the exchange between devices left out; prints both results as
one JSON line.  Started by ``test_correct.py`` with
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

from chipbench.bench import run_cell  # noqa: E402
from chipbench.tests.conftest import SEED, tiny_cell  # noqa: E402


def main():
    from repro.comm.xla import XlaCommunicator
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    out = {}
    with tempfile.TemporaryDirectory() as work:
        cell = tiny_cell("fig9-4chip.incore")
        out["sound"] = run_cell(cell, SEED, 0.2, False, devices,
                                time.perf_counter(), work)
        XlaCommunicator.all_to_all = lambda self, x: x
        out["no_exchange"] = run_cell(tiny_cell("fig9-4chip.incore"), SEED,
                                      0.2, False, devices,
                                      time.perf_counter(), work)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

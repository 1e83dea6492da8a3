"""Fixtures for the benchmark's CPU tests: cells cut to a few thousand
rows, run by the harness on the CPU with the chip check skipped."""

import time

import jax
import pytest

from chipbench.bench import run_cell
from chipbench.spec import resolve

#: a seed above 2**32, like those the benchmark is run with
SEED = 2 ** 33 + 12345
TINY_ROWS = 2048


def tiny_cell(name: str, rows: int = TINY_ROWS):
    cell = resolve(name)
    cell.config["in_core_rows_per_chip"] = rows
    return cell


@pytest.fixture
def run_tiny(tmp_path):
    """Run a cell, by name at TINY_ROWS or as given, on this process's
    first devices."""
    def run(cell, trace=False, seconds=0.2, **kw):
        if isinstance(cell, str):
            cell = tiny_cell(cell)
        return run_cell(cell, SEED, seconds, trace, jax.devices()[:cell.chips],
                        time.perf_counter(), str(tmp_path), **kw)
    return run

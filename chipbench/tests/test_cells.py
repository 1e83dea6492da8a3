"""Every cell of ``BENCHMARK.json`` resolves to its files by name, and the
command refuses to run anywhere but on a TPU."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.bench import read_tables
from chipbench.spec import HERE, ROOT, load_benchmark, resolve
from chipbench.tests.conftest import SEED

BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = resolve(name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.chips == w["chips"] == cell.config["chips"]
    assert os.path.exists(os.path.join(HERE, "traffic",
                                       f"{w['traffic']}.json"))
    assert {m.name for m in cell.end_to_end} >= {"rows_per_s", "setup_s"}
    assert cell.per_layer and all(callable(m.read) for m in cell.per_layer)
    # every table the query reads is one the recipe makes, and the cell's
    # own query module knows every part of its spec: its reference runs,
    # and every reading it compares has a limit
    cell.config["in_core_rows_per_chip"] = 64
    spec = cell.traffic["query"]
    tables = read_tables(cell, SEED, cell.chips)
    assert list(tables) == cell.query.tables_read(spec)
    ref = cell.query.reference(tables, spec)
    assert ref and set(cell.traffic["limits"]) >= set(
        cell.query.compare(ref, ref))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(cfg):
    path = os.path.join(ROOT, cfg["file"])
    with open(path) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"]
    assert len(data["source"]) <= 200
    assert set(cfg["reduced"]) == set(data["reduced"])
    assert all(k in data for k in cfg["reduced"])
    assert os.path.exists(os.path.join(HERE, "recipes",
                                       f"{data['recipe']}.py"))
    assert data["assumed"]
    assert data["guarantee"].startswith("exact")


def test_every_metric_reader_is_found():
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py"))
        for w in m.get("workloads", []):
            assert w in CELLS


def test_run_refuses_a_platform_that_is_not_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         CELLS[0], "--seed", str(2 ** 40), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert p.stdout == ""

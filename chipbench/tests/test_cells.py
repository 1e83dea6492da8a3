"""Every cell of ``BENCHMARK.json`` resolves to its files by name, and the
command refuses to run anywhere but on a TPU."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.spec import HERE, ROOT, load_benchmark, resolve

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
    for op in cell.traffic["query"]:
        assert op["op"] in ("merge", "groupby_agg", "sort_values",
                            "add_scalar")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(cfg):
    path = os.path.join(ROOT, cfg["file"])
    with open(path) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"]
    assert len(data["source"]) <= 200
    assert set(cfg["reduced"]) == set(data["reduced"])
    assert all(k in data for k in cfg["reduced"])
    assert {"key_cardinality", "out_capacity",
            "parquet_files_per_table"} <= set(data["assumed"])
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

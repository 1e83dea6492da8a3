"""The check that decides ``correct``: each traffic mix's jobs, run
through the library at a tiny size, equal the pandas reference; the
control (the reference in bfloat16) and each fault a cell can have make
``correct`` come out false."""

import json
import os
import subprocess
import sys

import ml_dtypes
import numpy as np
import pandas as pd
import pytest

from chipbench import query
from chipbench.bench import SAMPLED_JOBS, make_tables
from chipbench.data import make_table_data
from chipbench.spec import HERE, ROOT, load_benchmark, resolve

ONE_CHIP = ["fig9-1chip.incore"]
FIG9 = resolve("fig9-1chip.incore").traffic["query"]


def test_reference_equals_pandas_groupby():
    left = make_table_data(3000, [5, 0])
    right = make_table_data(3000, [5, 1])
    ref = query.reference({"left": left, "right": right}, FIG9)
    joined = pd.DataFrame(left).merge(pd.DataFrame(right), on="k",
                                      suffixes=("", "_r"))
    g = joined.groupby("k", sort=True)["v0"].sum()
    assert np.array_equal(ref.index.to_numpy(), g.index.to_numpy())
    assert np.array_equal(ref["v0_sum"].to_numpy(), g.to_numpy() + 1.0)


@pytest.mark.parametrize("cfg", ["fig9-1chip", "fig9-4chip"])
def test_tables_have_the_configured_column_types(cfg):
    cell = next(resolve(w["name"]) for w in load_benchmark()["workloads"]
                if w["config"] == cfg)
    tables = make_tables(cell, 2 ** 40 + 3, 1000)
    assert list(tables) == cell.config["tables"]
    for t in tables.values():
        assert {c: str(v.dtype) for c, v in t.items()} == \
            cell.config["columns"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_jobs_equal_the_reference(run_tiny, name):
    out = run_tiny(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_a_sample_of_the_whole_window_and_the_last_job_are_compared(
        run_tiny):
    out = run_tiny("fig9-1chip.incore", seconds=2.0)
    n = out["attempted"]
    assert n > SAMPLED_JOBS + 1, n
    assert out["setup_parts"]["compared"] in (SAMPLED_JOBS,
                                              SAMPLED_JOBS + 1)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_traced_run_is_checked_too(run_tiny, name):
    out = run_tiny(name, trace=True)
    assert out["correct"], out["checks"]
    assert "busy_s" in out["device"] and "breakdown" in out


@pytest.mark.parametrize("name", ONE_CHIP)
def test_bfloat16_control_is_not_correct(run_tiny, name):
    out = run_tiny(name, control_dtype=ml_dtypes.bfloat16)
    assert not out["correct"]
    assert out["checks"]["v0_sum_max_abs_err"]["value"] > 0


def _scan_half_rows(eval_node):
    def patched(node, *a, **kw):
        t = eval_node(node, *a, **kw)
        if node.op == "scan":
            t = type(t)(columns=t.columns, row_count=t.row_count // 2)
        return t
    return patched


def _alter_answer(eval_node):
    def patched(node, *a, **kw):
        t = eval_node(node, *a, **kw)
        if node.op == "sort" and "v0_sum" in t.columns:
            cols = dict(t.columns)
            cols["v0_sum"] = cols["v0_sum"].at[0].add(1.0)
            t = type(t)(columns=cols, row_count=t.row_count)
        return t
    return patched


def _half_rows(monkeypatch, physical):
    monkeypatch.setattr(physical, "eval_node",
                        _scan_half_rows(physical.eval_node))


def _altered(monkeypatch, physical):
    monkeypatch.setattr(physical, "eval_node",
                        _alter_answer(physical.eval_node))


@pytest.mark.parametrize("fault", [_half_rows, _altered],
                         ids=["half_the_rows_left_out", "answer_altered"])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_fault_in_the_timed_path_is_not_correct(run_tiny, monkeypatch,
                                                name, fault):
    import repro.planner.physical as physical
    fault(monkeypatch, physical)
    out = run_tiny(name)
    assert not out["correct"], out["checks"]


def test_four_chips_and_the_exchange_left_out():
    """fig9-4chip.incore on four virtual CPU devices: sound, it equals
    pandas; with the all-to-all returning its input, it does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "tests",
                                                     "four_devices.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["sound"]["correct"], res["sound"]["checks"]
    assert res["sound"]["device"]["count"] == 4
    assert not res["no_exchange"]["correct"], res["no_exchange"]["checks"]


def test_a_traffic_file_alone_makes_a_new_job(tmp_path):
    """A new query needs only a traffic file: here one is given in place
    of ``incore.json`` and its jobs equal the reference."""
    import time

    import jax

    from chipbench.bench import run_cell
    from chipbench.tests.conftest import SEED, tiny_cell
    cell = tiny_cell("fig9-1chip.incore")
    cell.traffic = dict(cell.traffic, query=[
        {"op": "groupby_agg", "by": "k", "aggs": {"v0": ["sum"]}},
        {"op": "sort_values", "by": "k"},
        {"op": "add_scalar", "col": "v0_sum", "value": 2.5}])
    out = run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                   time.perf_counter(), str(tmp_path))
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"key_mismatch", "v0_sum_max_abs_err"}

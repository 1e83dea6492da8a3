"""The check that decides ``correct``: each traffic mix's jobs, run
through the library at a tiny size, equal the pandas reference; the
control (the reference in bfloat16) and each fault a cell can have make
``correct`` come out false.  A cell defined by new files alone, a traffic
file, or a recipe and a query module of its own, is run and checked as the
committed cells are."""

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import jax
import ml_dtypes
import numpy as np
import pandas as pd
import pytest

from chipbench.bench import SAMPLED_JOBS, count_rows, read_tables, run_cell
from chipbench.spec import HERE, ROOT, load_benchmark, load_module, resolve
from chipbench.tests.conftest import SEED, TINY_ROWS, tiny_cell

#: v0 >= 128 over the first of the Fig-9 tables, then groupby(sum) and
#: sort: the op-list query's filter, which no committed cell runs, given as
#: a traffic file's ``query`` would give it
FILTER_QUERY = {"module": "oplist", "table": "left", "ops": [
    {"op": "filter", "col": "v0", "ge": 128},
    {"op": "groupby_agg", "by": "k", "aggs": {"v0": ["sum"]}},
    {"op": "sort_values", "by": "k"}]}
#: the committed one-chip cell, and the filter query over its tables
ONE_CHIP = ["fig9-1chip.incore", "fig9-1chip.filter"]
#: input rows of one job, in rows per table per chip
INPUT_ROWS = {"fig9-1chip.incore": 2, "fig9-4chip.incore": 8,
              "fig9-1chip.filter": 1}
FIG9 = load_module("recipes", "fig9")
OPLIST = load_module("queries", "oplist")


def _tiny(name):
    """A committed cell at TINY_ROWS, or ``fig9-1chip.filter``: the
    committed one-chip cell with ``FILTER_QUERY`` for its query."""
    if name != "fig9-1chip.filter":
        return tiny_cell(name)
    cell = tiny_cell("fig9-1chip.incore")
    cell.name = name
    cell.traffic = dict(cell.traffic, query=FILTER_QUERY)
    return cell


def _cell_of(cfg):
    return next(resolve(w["name"]) for w in load_benchmark()["workloads"]
                if w["config"] == cfg)


def _make_table_data_before(rows, seed, cardinality=0.9, value_max=256):
    """The Fig-9 recipe as it was before it became a recipe module, kept
    to pin the committed cells' data."""
    rng = np.random.default_rng(seed)
    n_unique = max(1, int(rows * cardinality))
    k = rng.integers(0, n_unique, rows, dtype=np.int64)
    v = rng.integers(0, value_max, rows)
    return {"k": k, "v0": v.astype(np.float32)}


def test_reference_equals_pandas_groupby():
    left = FIG9.make_table_data(3000, [5, 0])
    right = FIG9.make_table_data(3000, [5, 1])
    spec = resolve("fig9-1chip.incore").traffic["query"]
    ref = OPLIST.reference({"left": left, "right": right}, spec)
    joined = pd.DataFrame(left).merge(pd.DataFrame(right), on="k",
                                      suffixes=("", "_r"))
    g = joined.groupby("k", sort=True)["v0"].sum()
    assert list(ref) == ["k", "v0_sum"]
    assert np.array_equal(ref["k"], g.index.to_numpy())
    assert np.array_equal(ref["v0_sum"], g.to_numpy() + 1.0)


def test_filter_reference_equals_pandas():
    left = FIG9.make_table_data(3000, [5, 0])
    ref = OPLIST.reference({"left": left}, FILTER_QUERY)
    df = pd.DataFrame(left)
    kept = df[df.v0 >= 128]
    assert 0.45 * len(df) < len(kept) < 0.55 * len(df)
    g = kept.groupby("k", sort=True)["v0"].sum()
    assert np.array_equal(ref["k"], g.index.to_numpy())
    assert np.array_equal(ref["v0_sum"], g.to_numpy())


@pytest.mark.parametrize("cfg", ["fig9-1chip", "fig9-4chip"])
def test_tables_have_the_configured_column_types(cfg):
    cell = _cell_of(cfg)
    cell.config["in_core_rows_per_chip"] = 1000
    tables = cell.recipe.make_tables(cell.config, 2 ** 40 + 3, cell.chips,
                                     cell.config["tables"])
    assert list(tables) == cell.config["tables"]
    for t in tables.values():
        assert {c: str(v.dtype) for c, v in t.items()} == \
            cell.config["columns"]


@pytest.mark.parametrize("seed", [SEED, 2 ** 40 + 3])
@pytest.mark.parametrize("cfg", ["fig9-1chip", "fig9-4chip"])
def test_fig9_recipe_makes_the_tables_it_made_before(cfg, seed):
    """Both Fig-9 configurations' tables, from their recipe module, equal
    array for array what the recipe gave before it was a module: each
    table ``i`` of ``R * chips`` rows from ``[seed mod 2**63, i]``."""
    cell = _cell_of(cfg)
    assert {"key_cardinality", "out_capacity",
            "parquet_files_per_table"} <= set(cell.config["assumed"])
    cell.config["in_core_rows_per_chip"] = TINY_ROWS
    data = cell.config["data"]
    tables = cell.recipe.make_tables(cell.config, seed, cell.chips,
                                     ["left", "right"])
    assert list(tables) == ["left", "right"]
    for i, name in enumerate(tables):
        want = _make_table_data_before(
            TINY_ROWS * cell.chips, [seed % (1 << 63), i],
            cardinality=data["key_cardinality"],
            value_max=data["value_max"])
        assert list(tables[name]) == list(want)
        for c in want:
            assert tables[name][c].dtype == want[c].dtype
            assert np.array_equal(tables[name][c], want[c])
    # a table made alone is the table made with the others
    (right,) = cell.recipe.make_tables(cell.config, seed, cell.chips,
                                       ["right"]).values()
    assert all(np.array_equal(right[c], tables["right"][c]) for c in right)


@pytest.mark.parametrize("name", sorted(INPUT_ROWS))
def test_input_rows_are_the_rows_of_the_tables_read(name):
    cell = _tiny(name)
    tables = read_tables(cell, SEED, cell.chips)
    assert list(tables) == cell.query.tables_read(cell.traffic["query"])
    assert count_rows(tables) == INPUT_ROWS[name] * TINY_ROWS


@pytest.mark.parametrize("name", ONE_CHIP)
def test_jobs_equal_the_reference(run_tiny, name):
    out = run_tiny(_tiny(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    # rows_per_s counts the rows of the tables the query reads, per job
    parts = out["setup_parts"]
    assert out["metrics"]["rows_per_s"]["value"] * parts["window_s"] == \
        pytest.approx(INPUT_ROWS[name] * TINY_ROWS * parts["jobs"])


def test_a_sample_of_the_whole_window_and_the_last_job_are_compared(
        run_tiny):
    out = run_tiny("fig9-1chip.incore", seconds=2.0)
    n = out["attempted"]
    assert n > SAMPLED_JOBS + 1, n
    assert out["setup_parts"]["compared"] in (SAMPLED_JOBS,
                                              SAMPLED_JOBS + 1)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_traced_run_is_checked_too(run_tiny, name):
    out = run_tiny(_tiny(name), trace=True)
    assert out["correct"], out["checks"]
    assert "busy_s" in out["device"] and "breakdown" in out


@pytest.mark.parametrize("name", ONE_CHIP)
def test_bfloat16_control_is_not_correct(run_tiny, name):
    out = run_tiny(_tiny(name), control_dtype=ml_dtypes.bfloat16)
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


def _keep_every_row(monkeypatch, physical):
    monkeypatch.setattr(physical.ops_local, "filter_expr",
                        lambda table, expr: table)


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
    out = run_tiny(_tiny(name))
    assert not out["correct"], out["checks"]


def test_filter_that_keeps_every_row_is_not_correct(run_tiny, monkeypatch):
    """The filter query with the engine's filter keeping every row."""
    import repro.planner.physical as physical
    _keep_every_row(monkeypatch, physical)
    out = run_tiny(_tiny("fig9-1chip.filter"))
    assert not out["correct"], out["checks"]
    assert out["checks"]["key_mismatch"]["value"] > 0


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


#: cells defined by new files alone, laid over a copy of the committed
#: benchmark: the entries each adds to ``BENCHMARK.json`` and its files.
#: ``traffic_file``: a new job over a committed configuration.
#: ``recipe_and_query``: a recipe of two tables of different sizes, of which
#: the query reads one, and a query module with its own reference,
#: comparison and readings.
NEW_CELLS = {
    "traffic_file": (
        {"workloads": [{"name": "fig9-1chip.groupby", "config": "fig9-1chip",
                        "traffic": "groupby", "chips": 1}]},
        {"chipbench/traffic/groupby.json": json.dumps({
            "query": {"module": "oplist", "table": "left", "ops": [
                {"op": "groupby_agg", "by": "k", "aggs": {"v0": ["sum"]}},
                {"op": "sort_values", "by": "k"},
                {"op": "add_scalar", "col": "v0_sum", "value": 2.5}]},
            "limits": {"key_mismatch": 0, "v0_sum_max_abs_err": 0}})}),
    "recipe_and_query": (
        {"configs": [{"name": "t", "file": "chipbench/configs/t.json"}],
         "workloads": [{"name": "t.sums", "config": "t", "traffic": "sums",
                        "chips": 1}]},
        {"chipbench/configs/t.json": json.dumps({
            "recipe": "facts_and_dims", "chips": 1,
            "tables": ["facts", "dims"],
            "in_core_rows_per_chip": TINY_ROWS,
            "parquet_files_per_table": 2}),
         "chipbench/traffic/sums.json": json.dumps({
             "query": {"module": "sums_by_group", "table": "facts"},
             "limits": {"groups_off": 0, "total_err": 0}}),
         "chipbench/recipes/facts_and_dims.py": textwrap.dedent("""
            import numpy as np

            def make_tables(config, seed, chips, tables):
                rows = config["in_core_rows_per_chip"] * chips
                rng = np.random.default_rng([seed, 1])
                made = {
                    "facts": {"g": rng.integers(0, 40, rows, dtype=np.int64),
                              "x": rng.integers(0, 200, rows).astype(
                                  np.float32)},
                    "dims": {"g": np.arange(rows // 2, dtype=np.int64)}}
                return {name: made[name] for name in tables}
            """),
         "chipbench/queries/sums_by_group.py": textwrap.dedent("""
            import numpy as np

            def tables_read(spec):
                return [spec["table"]]

            def build_frame(frames, spec, config):
                f = frames[spec["table"]]
                return f.groupby("g").agg({"x": ["sum"]}).sort_values("g")

            def reference(tables, spec, dtype=np.float32):
                t = tables[spec["table"]]
                groups = np.unique(t["g"])
                acc = np.float64 if dtype == np.float32 else dtype
                sums = np.zeros(len(groups), acc)
                for i, g in enumerate(groups):
                    for v in t["x"][t["g"] == g]:
                        sums[i] = acc(sums[i] + acc(v))
                return {"g": groups, "x_sum": sums.astype(np.float64)}

            def compare(got, ref):
                same = np.array_equal(np.asarray(got["g"]), ref["g"])
                err = (np.max(np.abs(np.asarray(got["x_sum"], np.float64)
                                     - ref["x_sum"])) if same else np.inf)
                return {"groups_off": float(not same),
                        "total_err": float(err)}
            """)}),
}


@pytest.mark.parametrize("case", sorted(NEW_CELLS))
def test_a_cell_of_new_files_alone_is_run_and_checked(tmp_path, case):
    """A cell whose files exist only in a copy of the benchmark resolves
    there, runs through ``run_cell``, counts the rows of the one table its
    query reads, and is checked: its jobs equal its reference, and its
    reference in bfloat16 does not."""
    root = tmp_path / "checkout"
    for kind in ("configs", "traffic", "recipes", "queries", "metrics"):
        shutil.copytree(os.path.join(HERE, kind), root / "chipbench" / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    entries, files = NEW_CELLS[case]
    bench = load_benchmark()
    for key, added in entries.items():
        bench[key] = bench[key] + added
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel, text in files.items():
        (root / rel).write_text(text)
    (name,) = [w["name"] for w in entries["workloads"]]

    def run(**kw):
        cell = resolve(name, root=str(root))
        assert cell.recipe.__file__.startswith(str(root))
        assert cell.query.__file__.startswith(str(root))
        cell.config["in_core_rows_per_chip"] = TINY_ROWS
        return run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                        time.perf_counter(), str(tmp_path / "work"), **kw)
    out = run()
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(json.loads(
        next(t for r, t in files.items() if "/traffic/" in r))["limits"])
    assert all(c["value"] == 0 for c in out["checks"].values())
    parts = out["setup_parts"]
    assert out["metrics"]["rows_per_s"]["value"] * parts["window_s"] == \
        pytest.approx(TINY_ROWS * parts["jobs"])
    ctl = run(control_dtype=ml_dtypes.bfloat16)
    assert not ctl["correct"], ctl["checks"]

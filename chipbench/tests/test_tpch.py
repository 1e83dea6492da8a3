"""The TPC-H cell at a tiny scale on the CPU: the recipe keeps the
distributions of clause 4.2.3, Q3 through ``repro.df`` equals the pandas
reference on one device and (its GROUP BY, and the whole query) on four
virtual devices, and the bfloat16 control and faults in the timed path
make ``correct`` false."""

import json
import os
import subprocess
import sys

import ml_dtypes
import numpy as np
import pandas as pd
import pytest

from chipbench.bench import count_rows, read_tables
from chipbench.spec import HERE, ROOT, load_module
from chipbench.tests.conftest import SEED, TINY_ROWS, tiny_cell

CELL = "tpch-1chip.q3"
TPCH = load_module("recipes", "tpch")
#: ORDERS rows for the recipe's properties: 1/75 of SF 1
ORDERS = 20_000


def _tables(seed=SEED, rows=ORDERS, names=("customer", "orders",
                                           "lineitem")):
    cell = tiny_cell(CELL, rows)
    return cell.recipe.make_tables(cell.config, seed, cell.chips, names)


@pytest.mark.parametrize("seed", [SEED, 2 ** 40 + 3])
def test_recipe_keeps_the_distributions_of_clause_4_2_3(seed):
    t = _tables(seed)
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    n_cust = ORDERS // 10
    assert len(o["o_orderkey"]) == ORDERS and len(c["c_custkey"]) == n_cust
    # unique customer keys 1..N/10; five segments, near-uniform
    np.testing.assert_array_equal(c["c_custkey"], np.arange(1, n_cust + 1))
    seg = pd.Series(c["c_mktsegment"]).value_counts(normalize=True)
    assert set(seg.index) == set(TPCH.SEGMENTS) and seg.min() > 0.15
    # sparse order keys: the first 8 of each 32
    assert np.all((o["o_orderkey"] - 1) % 32 < 8)
    assert len(np.unique(o["o_orderkey"])) == ORDERS
    # customer keys in range and never a multiple of 3
    assert o["o_custkey"].min() >= 1 and o["o_custkey"].max() <= n_cust
    assert np.all(o["o_custkey"] % 3 != 0)
    assert o["o_orderdate"].min() >= np.datetime64("1992-01-01")
    assert o["o_orderdate"].max() <= np.datetime64("1998-08-02")
    assert np.all(o["o_shippriority"] == 0)
    # 1..7 lines per order, every line one of an order's
    _, lines = np.unique(li["l_orderkey"], return_counts=True)
    assert lines.min() == 1 and lines.max() == 7
    assert 3.8 < len(li["l_orderkey"]) / ORDERS < 4.2
    assert set(np.unique(li["l_orderkey"])) == set(o["o_orderkey"])
    # ship dates 1..121 days after their order's date
    odate = dict(zip(o["o_orderkey"].tolist(),
                     o["o_orderdate"].astype(np.int64).tolist()))
    lag = (li["l_shipdate"].astype(np.int64)
           - np.array([odate[k] for k in li["l_orderkey"].tolist()]))
    assert lag.min() == 1 and lag.max() == 121
    # discounts 0.00..0.10; prices a whole quantity of a retail price
    np.testing.assert_allclose(np.unique(li["l_discount"]),
                               np.arange(11) / 100, atol=1e-7)
    assert li["l_extendedprice"].min() >= 900.0
    assert li["l_extendedprice"].max() <= 50 * 2099.0
    # column types as the configuration lists them
    cfg = tiny_cell(CELL).config
    for name, cols in cfg["columns"].items():
        assert list(t[name]) == list(cols)
        for col, want in cols.items():
            got = t[name][col].dtype
            assert got.kind == np.dtype(want).kind
            assert got.kind == "U" or got == np.dtype(want)


def test_retail_price_and_a_table_made_alone():
    # (90000 + ((partkey / 10) mod 20001) + 100 (partkey mod 1000)) / 100
    np.testing.assert_allclose(
        TPCH.p_retailprice(np.array([1, 1000, 20010, 199999])),
        [901.00, 901.00, 930.01, 2098.99])
    both = _tables(rows=2048)
    alone = _tables(rows=2048, names=("lineitem",))
    for col, v in alone["lineitem"].items():
        np.testing.assert_array_equal(v, both["lineitem"][col])


def test_input_rows_are_the_three_tables():
    cell = tiny_cell(CELL)
    tables = read_tables(cell, SEED, 1)
    assert list(tables) == ["customer", "orders", "lineitem"]
    assert count_rows(tables) == sum(len(t[next(iter(t))])
                                     for t in tables.values())
    assert len(tables["orders"]["o_orderkey"]) == TINY_ROWS


def test_reference_equals_pandas_q3():
    t = _tables(rows=4096)
    spec = tiny_cell(CELL).traffic["query"]
    ref = load_module("queries", "tpch_q3").reference(t, spec)
    c, o, li = (pd.DataFrame(dict(t[n]))
                for n in ("customer", "orders", "lineitem"))
    day = pd.Timestamp(spec["date"])
    j = (li[li.l_shipdate > day]
         .merge(o[o.o_orderdate < day], left_on="l_orderkey",
                right_on="o_orderkey")
         .merge(c[c.c_mktsegment == spec["segment"]], left_on="o_custkey",
                right_on="c_custkey"))
    j["revenue"] = (j.l_extendedprice.astype(np.float64)
                    * (1 - j.l_discount.astype(np.float64)))
    want = (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                      as_index=False)["revenue"].sum()
            .sort_values(["revenue", "o_orderdate"], ascending=[False, True])
            .head(spec["limit"]))
    assert len(want) == spec["limit"] == len(ref["l_orderkey"])
    np.testing.assert_array_equal(ref["l_orderkey"], want["l_orderkey"])
    np.testing.assert_allclose(ref["revenue"], want["revenue"], rtol=1e-12)


def test_q3_through_the_harness_equals_the_reference(run_tiny):
    out = run_tiny(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["row_mismatch"]["value"] == 0
    assert out["checks"]["revenue_max_abs_err"]["value"] < 0.1
    assert out["setup_parts"]["compiles_in_window"] == 0


def test_bfloat16_control_is_not_correct(run_tiny):
    out = run_tiny(CELL, control_dtype=ml_dtypes.bfloat16)
    assert not out["correct"]
    assert out["checks"]["revenue_max_abs_err"]["value"] > 1.0


def _keep_every_row(monkeypatch, physical):
    monkeypatch.setattr(physical.ops_local, "filter_expr",
                        lambda table, expr: table)


def _keep_one_more_row(monkeypatch, physical):
    limit = physical.df_limit
    monkeypatch.setattr(physical, "df_limit",
                        lambda table, comm, n: limit(table, comm, n + 1))


def _ascending(monkeypatch, physical):
    sort = physical.df_sort
    monkeypatch.setattr(physical, "df_sort",
                        lambda *a, ascending=None, **kw: sort(*a, **kw))


@pytest.mark.parametrize("fault", [_keep_every_row, _keep_one_more_row,
                                   _ascending],
                         ids=["filters_keep_every_row", "limit_one_more",
                              "sort_ascending"])
def test_fault_in_the_timed_path_is_not_correct(run_tiny, monkeypatch,
                                                fault):
    import repro.planner.physical as physical
    fault(monkeypatch, physical)
    out = run_tiny(CELL)
    assert not out["correct"], out["checks"]


def test_compare_forgives_only_swaps_within_the_revenue_error():
    q3 = load_module("queries", "tpch_q3")
    ranked = {"l_orderkey": np.array([1, 2, 3, 4]),
              "revenue": np.array([10.0, 9.99, 5.0, 4.0]),
              "o_orderdate": np.array(["1995-01-01"] * 4, "datetime64[D]"),
              "o_shippriority": np.zeros(4, np.int32)}
    ref = {k: v[:3] for k, v in ranked.items()}
    ref["ranked"] = ranked
    swapped = {k: v[[1, 0, 2]] for k, v in ranked.items()}
    swapped["revenue"] = np.array([9.995, 9.995, 5.0])
    out = q3.compare(swapped, ref)
    assert out == {"row_mismatch": 0.0,
                   "revenue_max_abs_err": pytest.approx(0.005)}
    exact = {k: v[[1, 0, 2]] for k, v in ranked.items()}
    assert q3.compare(exact, ref)["row_mismatch"] == 2
    tie = {k: v[[0, 1, 3]] for k, v in ranked.items()}
    assert q3.compare(tie, ref)["row_mismatch"] == 1


def test_four_devices_group_by_and_q3_equal_pandas():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "tests",
                                                     "four_devices_tpch.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["groups"] > 20 and res["keys_equal"]
    assert res["revenue_max_abs_err"] < 0.1
    assert res["q3"]["correct"], res["q3"]["checks"]
    assert res["q3"]["device"]["count"] == 4

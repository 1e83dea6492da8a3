"""Local DDF operators vs numpy oracles (unit + hypothesis property)."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.dataframe import Table, groupby_local, join_local, join_overflow
from repro.dataframe.ops_local import _merge_rank

INT32_MAX = np.iinfo(np.int32).max


def _mk(keys, vals, cap_extra=0):
    keys = np.asarray(keys, np.int32)
    vals = np.asarray(vals, np.float32)
    return Table.from_arrays({"k": keys, "v": vals},
                             capacity=len(keys) + cap_extra)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 15), min_size=1, max_size=40),
       st.lists(st.integers(0, 15), min_size=1, max_size=40))
def test_join_local_row_count_and_sums(lk, rk):
    lt = _mk(lk, np.arange(len(lk)))
    rt = Table.from_arrays({"k": np.asarray(rk, np.int32),
                            "w": np.ones(len(rk), np.float32)})
    out_cap = 4 * (len(lk) + len(rk)) * 4
    out = join_local(lt, rt, "k", out_capacity=out_cap).to_numpy()
    rmap = collections.Counter(rk)
    expect = sum(rmap[k] for k in lk)
    assert len(out["k"]) == expect
    # each left row appears exactly count[k] times
    vmap = collections.Counter(out["v"].tolist())
    for i, k in enumerate(lk):
        if rmap[k]:
            assert vmap[float(i)] == rmap[k]


def test_join_overflow_counts(rng):
    lt = _mk([1] * 10, np.zeros(10))
    rt = _mk([1] * 10, np.zeros(10))
    # 100 result rows, capacity 30 -> 70 dropped
    dropped = int(join_overflow(lt, rt, "k", out_capacity=30))
    assert dropped == 70


# (array, query); each is sorted in lax.sort's order before the search
MERGE_RANK_CASES = {
    "int32_duplicates_sentinels": (
        np.array([1, 1, 2, 2, 2, 5, 7, 7, INT32_MAX, INT32_MAX], np.int32),
        np.array([0, 1, 2, 2, 3, 7, 8, INT32_MAX, INT32_MAX], np.int32)),
    "float32_nan_signed_zero_inf": (
        np.array([np.nan, -np.inf, -1.5, 0.0, -0.0, 0.0, 2.0, np.inf, np.nan],
                 np.float32),
        np.array([-np.inf, 0.0, -0.0, 1.0, np.inf, np.inf, np.nan],
                 np.float32)),
    "all_equal": (np.full(8, 3, np.int32), np.array([2, 3, 3, 4], np.int32)),
    "single_element": (np.array([4], np.int32), np.array([4], np.int32)),
    # the join's l_row: cumulative match counts against 4x as many slots
    "query_longer_than_array": (
        np.cumsum(np.array([0, 2, 0, 3, 1, 0, 0, 4, 2, 0, 1, 3], np.int32)),
        np.arange(48, dtype=np.int32)),
}


def _check_merge_rank(arr, query):
    arr, query = jnp.sort(jnp.asarray(arr)), jnp.sort(jnp.asarray(query))
    lo, hi = _merge_rank(arr, query)
    np.testing.assert_array_equal(
        lo, jnp.searchsorted(arr, query, side="left"))
    np.testing.assert_array_equal(
        hi, jnp.searchsorted(arr, query, side="right"))
    (right,) = _merge_rank(arr, query, sides=("right",))
    np.testing.assert_array_equal(right, hi)


@pytest.mark.parametrize("case", sorted(MERGE_RANK_CASES))
def test_merge_rank_matches_searchsorted(case):
    _check_merge_rank(*MERGE_RANK_CASES[case])


@settings(max_examples=40, deadline=None)
@given(*[st.lists(st.one_of(st.integers(-6, 6), st.just(INT32_MAX)),
                  max_size=40)] * 2)
def test_merge_rank_property(arr, query):
    _check_merge_rank(np.asarray(arr, np.int32), np.asarray(query, np.int32))


@pytest.mark.parametrize("op", ["join_local", "join_local_with_overflow",
                                "join_overflow"])
def test_join_lowers_without_while_loop(op):
    """The join's ranks are merges: no binary-search loop in the program."""
    fn = {"join_local": lambda a, b: join_local(a, b, "k", out_capacity=256),
          "join_local_with_overflow": lambda a, b: join_local(
              a, b, "k", out_capacity=256, with_overflow=True),
          "join_overflow": lambda a, b: join_overflow(a, b, "k", 256)}[op]
    t = _mk(np.arange(40) % 7, np.ones(40), cap_extra=24)
    hlo = jax.jit(fn).lower(t, t).as_text(dialect="hlo")
    assert " sort(" in hlo
    assert not re.search(r"\bwhile\(", hlo)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 8),
                          st.floats(-100, 100, allow_nan=False,
                                    allow_subnormal=False,  # XLA FTZ
                                    width=32)),
                min_size=1, max_size=50))
def test_groupby_local_all_aggs(pairs):
    keys = np.asarray([p[0] for p in pairs], np.int32)
    vals = np.asarray([p[1] for p in pairs], np.float32)
    t = Table.from_arrays({"k": keys, "v": vals}, capacity=len(pairs) + 7)
    out = groupby_local(t, ["k"], {"v": ["sum", "count", "min", "max"]})
    res = out.to_numpy()
    order = np.argsort(res["k"])
    uk = np.unique(keys)
    np.testing.assert_array_equal(res["k"][order], uk)
    for i, k in enumerate(uk):
        sel = vals[keys == k]
        j = order[i]
        np.testing.assert_allclose(res["v_sum"][j], sel.sum(), rtol=2e-5,
                                   atol=1e-4)
        assert res["v_count"][j] == len(sel)
        np.testing.assert_allclose(res["v_min"][j], sel.min(), rtol=1e-6)
        np.testing.assert_allclose(res["v_max"][j], sel.max(), rtol=1e-6)

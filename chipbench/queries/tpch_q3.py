"""TPC-H Q3, the Shipping Priority Query (TPC Benchmark H, clause 2.4.3),
run through ``repro.df`` and through pandas.

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = '[SEGMENT]' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date '[DATE]'
      and l_shipdate > date '[DATE]'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate
    limit [LIMIT];

The spec (a traffic file's ``query``) gives the substitution parameters:

  {"module": "tpch_q3", "segment": <str>, "date": "<YYYY-MM-DD>",
   "limit": <n>}

``build_frame`` writes the query as a user of ``repro.df`` would, renaming
the join keys with ``assign`` and ``select``; ``reference`` computes it
with pandas alone, in float64, from the same host columns, and imports
nothing of the program.  ``compare`` reduces one result against the
reference to two readings, each held to a limit by the harness.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

import numpy as np
import pandas as pd

#: the group keys, in the order Q3 returns them
KEYS = ("l_orderkey", "o_orderdate", "o_shippriority")
#: the columns of Q3's result
RESULT = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")


def tables_read(spec: dict) -> List[str]:
    return ["customer", "orders", "lineitem"]


def build_frame(frames: Mapping[str, object], spec: dict, config: dict):
    """Q3 over ``repro.df`` frames: ``revenue_by_group``, then ORDER BY
    and LIMIT, and the result's columns named and ordered as Q3's."""
    top = (revenue_by_group(frames, spec)
           .sort_values(["revenue_sum", "o_orderdate"],
                        ascending=[False, True])
           .head(int(spec["limit"])))
    return top.assign(revenue=top.revenue_sum)[list(RESULT)]


def revenue_by_group(frames: Mapping[str, object], spec: dict):
    """Q3 up to its GROUP BY: ``revenue_sum`` per (``l_orderkey``,
    ``o_orderdate``, ``o_shippriority``).  Each join has its many side on
    the left, so its output capacity, the left side's, holds every
    match."""
    date = np.datetime64(spec["date"], "D")
    c, o, li = frames["customer"], frames["orders"], frames["lineitem"]
    c = c[c.c_mktsegment == spec["segment"]]
    c = c.assign(custkey=c.c_custkey)[["custkey"]]
    o = o[o.o_orderdate < date]
    o = o.assign(custkey=o.o_custkey)[
        ["custkey", "o_orderkey", "o_orderdate", "o_shippriority"]]
    co = o.merge(c, on="custkey")
    co = co.assign(l_orderkey=co.o_orderkey)[
        ["l_orderkey", "o_orderdate", "o_shippriority"]]
    li = li[li.l_shipdate > date]
    j = li.merge(co, on="l_orderkey")
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    return j.groupby(list(KEYS)).agg({"revenue": "sum"})


def reference(tables: Mapping[str, Mapping[str, np.ndarray]], spec: dict,
              dtype=np.float32) -> Dict[str, object]:
    """pandas Q3 as host columns, plus ``ranked``: every group in Q3's
    order before the LIMIT (the tie handling of ``compare`` reads it).

    ``dtype`` is the precision of the revenue arithmetic.  The stated one,
    ``np.float32``, is computed in float64 (the value the program
    approximates); a lower one, such as ``ml_dtypes.bfloat16``, computes
    each line's revenue and accumulates each sum in that type, and is the
    control that the comparison has to reject."""
    date = np.datetime64(spec["date"], "D")
    c = pd.DataFrame(dict(tables["customer"]))
    o = pd.DataFrame(dict(tables["orders"]))
    li = pd.DataFrame(dict(tables["lineitem"]))
    c = c[c.c_mktsegment == spec["segment"]]
    o = o[o.o_orderdate.to_numpy() < date]
    li = li[li.l_shipdate.to_numpy() > date]
    co = o.merge(c, left_on="o_custkey", right_on="c_custkey")
    j = li.merge(co, left_on="l_orderkey", right_on="o_orderkey")
    acc = np.float64 if dtype == np.float32 else dtype
    price = j.l_extendedprice.to_numpy(np.float32).astype(acc)
    disc = j.l_discount.to_numpy(np.float32).astype(acc)
    j = j.assign(line=price * (acc(1) - disc))
    j = j.sort_values(list(KEYS), kind="stable")
    keys = [j[k].to_numpy() for k in KEYS]
    same = np.ones(max(len(j) - 1, 0), bool)
    for k in keys:
        same &= k[1:] == k[:-1]
    starts = np.flatnonzero(np.r_[True, ~same]) if len(j) else []
    sums = (np.add.reduceat(j.line.to_numpy(), starts) if len(j)
            else np.zeros(0, acc))
    groups = pd.DataFrame({KEYS[0]: keys[0][starts],
                           "revenue": sums.astype(np.float64),
                           KEYS[1]: keys[1][starts],
                           KEYS[2]: keys[2][starts]})
    ranked = groups.sort_values(["revenue", "o_orderdate"],
                                ascending=[False, True], kind="stable")
    top = ranked.head(int(spec["limit"]))
    out: Dict[str, object] = {c: top[c].to_numpy() for c in RESULT}
    out["ranked"] = {c: ranked[c].to_numpy() for c in RESULT}
    return out


def _key_tuples(cols: Mapping[str, np.ndarray]) -> List[tuple]:
    return list(zip(np.asarray(cols["l_orderkey"]).astype(np.int64).tolist(),
                    np.asarray(cols["o_orderdate"]).astype("datetime64[D]")
                    .astype(np.int64).tolist(),
                    np.asarray(cols["o_shippriority"]).astype(np.int64)
                    .tolist()))


def compare(got: Mapping[str, np.ndarray], ref: Mapping[str, object]
            ) -> Dict[str, float]:
    """Readings of one result against the reference.

    ``revenue_max_abs_err``: the largest distance, in dollars, between a
    result row's revenue and the reference's revenue of the same group
    (infinite for a row that is no group of the reference).
    ``row_mismatch``: result rows missing or extra, plus positions whose
    (``l_orderkey``, ``o_orderdate``, ``o_shippriority``) differ from the
    reference's, except where the reference revenues of the two rows lie
    within the revenue error of both: a swap of two rows of near-equal
    revenue, or a tie at the last row, that the revenue reading already
    holds to its limit."""
    ranked = ref["ranked"]
    ref_rev = dict(zip(_key_tuples(ranked),
                       np.asarray(ranked["revenue"], np.float64).tolist()))
    got_keys = _key_tuples(got)
    want_keys = _key_tuples(ref)
    got_rev = np.asarray(got["revenue"], np.float64)
    errs = [abs(g - ref_rev[k]) if k in ref_rev else np.inf
            for k, g in zip(got_keys, got_rev.tolist())]
    err = float(max(errs, default=0.0))
    mismatch = (abs(len(got_keys) - len(want_keys))
                + len(got_keys) - len(set(got_keys)))
    for g, w in zip(got_keys, want_keys):
        if g != w and (g not in ref_rev
                       or abs(ref_rev[g] - ref_rev[w]) > 2 * err):
            mismatch += 1
    return {"row_mismatch": float(mismatch), "revenue_max_abs_err": err}

"""TPC-H data (TPC Benchmark H, Standard Specification, clause 4.2.3, the
distributions of dbgen), drawn with numpy from a seed: only the columns
the configuration lists, each with the type the configuration gives it.

The scale factor comes from ``in_core_rows_per_chip``, the ORDERS rows per
chip (SF x 1,500,000), so a configuration cut to a few rows is a tiny
TPC-H of the same shape.  With ``N`` ORDERS rows in all:

* CUSTOMER: ``N / 10`` rows; ``c_custkey`` 1..N/10, unique;
  ``c_mktsegment`` uniform over the five segments.
* ORDERS: ``N`` rows; ``o_orderkey`` sparse, the first 8 of each 32 key
  values; ``o_custkey`` uniform over the customer keys that are not a
  multiple of 3; ``o_orderdate`` uniform in [STARTDATE, ENDDATE - 151
  days] = [1992-01-01, 1998-08-02]; ``o_shippriority`` 0.
* LINEITEM: 1 to 7 lines per order, uniform; ``l_orderkey`` its order's
  key; ``l_extendedprice`` = ``l_quantity`` [1, 50] x ``p_retailprice``
  of ``l_partkey``, uniform in [1, N x 2 / 15] (SF x 200,000);
  ``l_discount`` 0.00..0.10 in steps of 0.01; ``l_shipdate`` its order's
  date plus 1..121 days.

Each table comes from its own seed sequence ``[seed mod 2**63, i]``, ``i``
its index in the configuration's ``tables``, so a table made alone is the
table made with the others.  LINEITEM reads its orders' keys and dates,
which it draws again from the ORDERS sequence.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 150_000
PARTS_PER_SF = 200_000
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
STARTDATE = np.datetime64("1992-01-01")
#: ENDDATE (1998-12-31) less 151 days: the last order date
LAST_ORDERDATE = np.datetime64("1998-08-02")


def p_retailprice(partkey: np.ndarray) -> np.ndarray:
    """PART.P_RETAILPRICE (clause 4.2.3), in dollars."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100


def _counts(config: dict, chips: int):
    orders = int(config["in_core_rows_per_chip"]) * chips
    customers = max(1, orders * CUSTOMERS_PER_SF // ORDERS_PER_SF)
    parts = max(1, orders * PARTS_PER_SF // ORDERS_PER_SF)
    return orders, customers, parts


def _customer(rng, customers: int) -> Dict[str, np.ndarray]:
    seg = rng.integers(0, len(SEGMENTS), customers)
    return {"c_custkey": np.arange(1, customers + 1, dtype=np.int64),
            "c_mktsegment": np.asarray(SEGMENTS, dtype=object)[seg]}


def _orders(rng, orders: int, customers: int) -> Dict[str, np.ndarray]:
    i = np.arange(orders, dtype=np.int64)
    # the j-th customer key that is not a multiple of 3: 1, 2, 4, 5, 7, ...
    allowed = customers - customers // 3
    j = rng.integers(0, max(allowed, 1), orders)
    days = (LAST_ORDERDATE - STARTDATE).astype(np.int64)
    return {
        "o_orderkey": (i // 8) * 32 + i % 8 + 1,
        "o_custkey": 3 * (j // 2) + 1 + j % 2,
        "o_orderdate": STARTDATE + rng.integers(0, days + 1, orders),
        "o_shippriority": np.zeros(orders, np.int32),
    }


def _lineitem(rng, orders: Dict[str, np.ndarray], parts: int
              ) -> Dict[str, np.ndarray]:
    lines = rng.integers(1, 8, len(orders["o_orderkey"]))
    n = int(lines.sum())
    partkey = rng.integers(1, parts + 1, n)
    quantity = rng.integers(1, 51, n)
    discount = rng.integers(0, 11, n) / 100
    return {
        "l_orderkey": np.repeat(orders["o_orderkey"], lines),
        "l_extendedprice": (quantity * p_retailprice(partkey)
                            ).astype(np.float32),
        "l_discount": discount.astype(np.float32),
        "l_shipdate": (np.repeat(orders["o_orderdate"], lines)
                       + rng.integers(1, 122, n)),
    }


def make_tables(config: dict, seed: int, chips: int,
                tables: Sequence[str]) -> Dict[str, Dict[str, np.ndarray]]:
    """The configuration's ``tables`` (of ``customer``, ``orders``,
    ``lineitem``) on ``chips`` chips, from ``seed``, with the columns and
    types of the configuration's ``columns``."""
    n_orders, n_customers, n_parts = _counts(config, chips)
    base = seed % (1 << 63)
    index = {name: i for i, name in enumerate(config["tables"])}

    def rng(name):
        return np.random.default_rng([base, index[name]])

    made: Dict[str, Dict[str, np.ndarray]] = {}
    if "customer" in tables:
        made["customer"] = _customer(rng("customer"), n_customers)
    if "orders" in tables or "lineitem" in tables:
        orders = _orders(rng("orders"), n_orders, n_customers)
        if "orders" in tables:
            made["orders"] = orders
        if "lineitem" in tables:
            made["lineitem"] = _lineitem(rng("lineitem"), orders, n_parts)
    out = {}
    for name in tables:
        types = config["columns"][name]
        out[name] = {c: made[name][c].astype(t) for c, t in types.items()}
    return out

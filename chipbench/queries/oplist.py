"""A query as a list of operations, run two ways: through ``repro.df`` and
through pandas.

The spec (a traffic file's ``query``) names the table the operations start
from and the operations, applied in order:

  {"module": "oplist", "table": <table>, "ops": [<op>, ...]}

  {"op": "merge", "right": <table>, "on": <col>, "out_capacity": <x>}
      inner equi-join; the engine's ``out_capacity`` is ``x`` times the
      configuration's rows per chip (``in_core_rows_per_chip``)
  {"op": "filter", "col": <col>, "ge": <number>}
      keeps the rows where ``<col> >= <number>``
  {"op": "groupby_agg", "by": <col>, "aggs": {<col>: ["sum"]}}
      output ``<by>`` first, then ``<col>_sum``, one row per group
  {"op": "sort_values", "by": <col>}
  {"op": "add_scalar", "col": <col>, "value": <number>}

``build_frame`` turns the spec into library calls; ``reference`` computes
the same result with pandas alone, from the same host columns, and imports
nothing of the program.  ``compare`` reduces one engine result against the
reference to a few readings, each held to a limit by the harness.  It
takes the result's first column as its key: one row per key, in the order
of the reference, as a ``groupby_agg`` and a ``sort_values`` by its key
leave it.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import numpy as np
import pandas as pd


def tables_read(spec: dict) -> List[str]:
    """The tables the query reads: its first, then each merge's right."""
    return [spec["table"]] + [op["right"] for op in spec["ops"]
                              if op["op"] == "merge"]


def build_frame(frames: Mapping[str, object], spec: dict, config: dict):
    """The library pipeline for ``spec`` over ``repro.df`` frames."""
    from repro.expr import Col
    rows_per_rank = int(config["in_core_rows_per_chip"])
    df = frames[spec["table"]]
    for op in spec["ops"]:
        kind = op["op"]
        if kind == "merge":
            df = df.merge(frames[op["right"]], on=op["on"],
                          out_capacity=int(op["out_capacity"] * rows_per_rank))
        elif kind == "filter":
            df = df.filter(Col(op["col"]) >= op["ge"])
        elif kind == "groupby_agg":
            df = df.groupby(op["by"]).agg(dict(op["aggs"]))
        elif kind == "sort_values":
            df = df.sort_values(op["by"])
        elif kind == "add_scalar":
            df = df.assign(**{op["col"]: Col(op["col"]) + op["value"]})
        else:
            raise ValueError(f"unknown query op {kind!r}")
    return df


def reference(tables: Mapping[str, Mapping[str, np.ndarray]], spec: dict,
              dtype=np.float32) -> Dict[str, np.ndarray]:
    """pandas result of ``spec`` as host columns, in the engine's order.

    ``dtype`` is the precision of payload arithmetic.  ``np.float32`` is
    exact here (whole-number payloads, sums far below 2**24); a lower one,
    such as ``ml_dtypes.bfloat16``, accumulates every sum in that type and
    is the control that the comparison has to reject."""
    df = pd.DataFrame(dict(tables[spec["table"]]))
    for op in spec["ops"]:
        kind = op["op"]
        if kind == "merge":
            df = df.merge(pd.DataFrame(dict(tables[op["right"]])),
                          on=op["on"], suffixes=("", "_r"))
        elif kind == "filter":
            df = df[df[op["col"]].to_numpy() >= op["ge"]]
        elif kind == "groupby_agg":
            df = _aggregate(df, op["by"], op["aggs"], dtype)
        elif kind == "sort_values":
            df = df.sort_values(op["by"], kind="stable")
        elif kind == "add_scalar":
            col = op["col"]
            df[col] = (df[col].to_numpy().astype(dtype)
                       + np.asarray(op["value"], dtype)).astype(np.float64)
        else:
            raise ValueError(f"unknown query op {kind!r}")
    return {c: df[c].to_numpy() for c in df.columns}


def _aggregate(df: pd.DataFrame, by: str, aggs: Mapping[str, Sequence[str]],
               dtype) -> pd.DataFrame:
    df = df.sort_values(by, kind="stable")
    keys = df[by].to_numpy()
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    # the stated precision is float32: accumulate exactly in float64 and
    # round each result once, as pandas does; a lower type accumulates in
    # itself
    acc = np.float64 if dtype == np.float32 else dtype
    cols = {by: keys[starts]}
    for col, names in aggs.items():
        v = df[col].to_numpy(np.float32).astype(acc)
        for agg in names:
            if agg != "sum":
                raise ValueError(f"unknown aggregate {agg!r}")
            r = np.add.reduceat(v, starts)
            cols[f"{col}_{agg}"] = r.astype(dtype).astype(np.float64)
    return pd.DataFrame(cols)


def compare(got: Mapping[str, np.ndarray], ref: Mapping[str, np.ndarray]
            ) -> Dict[str, float]:
    """Readings of one engine result against the reference.

    ``key_mismatch``: groups missing, extra or out of order (0 when the
    key column, the reference's first, equals the reference's, position
    by position).  For each other column, ``<col>_max_abs_err``.  Where
    the keys differ, value readings are taken over the keys both sides
    have."""
    key, *values = ref
    keys = np.asarray(got[key])
    want = np.asarray(ref[key])
    n = min(len(keys), len(want))
    mismatch = abs(len(keys) - len(want)) + int(np.sum(keys[:n] != want[:n]))
    out = {"key_mismatch": float(mismatch)}
    if mismatch:
        _, gi, ri = np.intersect1d(keys, want, return_indices=True)
    else:
        gi = ri = slice(None)
    for col in values:
        name = f"{col}_max_abs_err"
        if col not in got:
            out[name] = float("inf")
            continue
        gap = (np.asarray(got[col], np.float64)[gi]
               - np.asarray(ref[col], np.float64)[ri])
        out[name] = float(np.max(np.abs(gap), initial=0.0))
    return out

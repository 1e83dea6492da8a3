"""A query as data, run two ways: through ``repro.df`` and through pandas.

A traffic file gives the query as a list of operations over named tables.
``build_frame`` turns it into library calls; ``reference`` computes the
same result with pandas alone, from the same host columns, and imports
nothing of the program.  ``compare`` reduces one engine result against the
reference to a few readings, each held to a limit by the harness.

Operations, applied in order to the first table (those of the paper's
Fig-9 pipeline; a query that needs another adds it here):

  {"op": "merge", "right": <table>, "on": <col>, "out_capacity": <x>}
      inner equi-join; the engine's ``out_capacity`` is ``x`` times the
      rows per rank of one input table
  {"op": "groupby_agg", "by": <col>, "aggs": {<col>: ["sum"]}}
      output ``<col>_sum``
  {"op": "sort_values", "by": <col>}
  {"op": "add_scalar", "col": <col>, "value": <number>}
"""

from __future__ import annotations

from typing import Dict, Mapping, Sequence

import numpy as np
import pandas as pd


def build_frame(frames: Mapping[str, object], ops: Sequence[dict],
                rows_per_rank: int):
    """The library pipeline for ``ops`` over ``repro.df`` frames."""
    from repro.expr import Col
    first = next(iter(frames))
    df = frames[first]
    for op in ops:
        kind = op["op"]
        if kind == "merge":
            df = df.merge(frames[op["right"]], on=op["on"],
                          out_capacity=int(op["out_capacity"] * rows_per_rank))
        elif kind == "groupby_agg":
            df = df.groupby(op["by"]).agg(dict(op["aggs"]))
        elif kind == "sort_values":
            df = df.sort_values(op["by"])
        elif kind == "add_scalar":
            df = df.assign(**{op["col"]: Col(op["col"]) + op["value"]})
        else:
            raise ValueError(f"unknown query op {kind!r}")
    return df


def reference(tables: Mapping[str, Mapping[str, np.ndarray]],
              ops: Sequence[dict], dtype=np.float32) -> pd.DataFrame:
    """pandas result of ``ops``: one row per group, indexed by the group
    key in ascending order, one column ``<col>_<agg>`` per aggregate.

    ``dtype`` is the precision of payload arithmetic.  ``np.float32`` is
    exact here (whole-number payloads, sums far below 2**24); a lower one,
    such as ``ml_dtypes.bfloat16``, accumulates every sum in that type and
    is the control that the comparison has to reject."""
    first = next(iter(tables))
    df = pd.DataFrame(dict(tables[first]))
    out = None
    for op in ops:
        kind = op["op"]
        if kind == "merge":
            df = df.merge(pd.DataFrame(dict(tables[op["right"]])),
                          on=op["on"], suffixes=("", "_r"))
        elif kind == "groupby_agg":
            out = _aggregate(df, op["by"], op["aggs"], dtype)
        elif kind == "sort_values":
            out = out.sort_index(kind="stable")
        elif kind == "add_scalar":
            col = op["col"]
            out[col] = (out[col].to_numpy().astype(dtype)
                        + np.asarray(op["value"], dtype)).astype(np.float64)
        else:
            raise ValueError(f"unknown query op {kind!r}")
    return out


def _aggregate(df: pd.DataFrame, by: str, aggs: Mapping[str, Sequence[str]],
               dtype) -> pd.DataFrame:
    df = df.sort_values(by, kind="stable")
    keys = df[by].to_numpy()
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    # the stated precision is float32: accumulate exactly in float64 and
    # round each result once, as pandas does; a lower type accumulates in
    # itself
    acc = np.float64 if dtype == np.float32 else dtype
    cols = {}
    for col, names in aggs.items():
        v = df[col].to_numpy(np.float32).astype(acc)
        for agg in names:
            if agg != "sum":
                raise ValueError(f"unknown aggregate {agg!r}")
            r = np.add.reduceat(v, starts)
            cols[f"{col}_{agg}"] = r.astype(dtype).astype(np.float64)
    return pd.DataFrame(cols, index=pd.Index(keys[starts], name=by))


def as_result(ref: pd.DataFrame) -> Dict[str, np.ndarray]:
    """A reference frame in the shape of an engine result's host columns:
    what the control hands to ``compare`` in the engine's place."""
    return {ref.index.name: ref.index.to_numpy(),
            **{c: ref[c].to_numpy() for c in ref.columns}}


def compare(got: Mapping[str, np.ndarray], ref: pd.DataFrame
            ) -> Dict[str, float]:
    """Readings of one engine result against the reference.

    ``key_mismatch``: groups missing, extra or out of order (0 when the
    key column equals the reference's, position by position).  For each
    value column, ``<col>_max_abs_err``.  Where the keys differ, value
    readings are taken over the keys both sides have."""
    keys = np.asarray(got[ref.index.name])
    want = ref.index.to_numpy()
    n = min(len(keys), len(want))
    mismatch = abs(len(keys) - len(want)) + int(np.sum(keys[:n] != want[:n]))
    out = {"key_mismatch": float(mismatch)}
    if mismatch:
        _, gi, ri = np.intersect1d(keys, want, return_indices=True)
    else:
        gi = ri = slice(None)
    for col in ref.columns:
        name = f"{col}_max_abs_err"
        if col not in got:
            out[name] = float("inf")
            continue
        gap = (np.asarray(got[col], np.float64)[gi]
               - ref[col].to_numpy(np.float64)[ri])
        out[name] = float(np.max(np.abs(gap), initial=0.0))
    return out

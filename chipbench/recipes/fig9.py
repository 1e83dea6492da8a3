"""The paper's data recipe (CylonFlow §V): every table of the
configuration ``{k: int64, v0: float32}``, uniform keys at a cardinality
of the rows, whole-number payloads, so that every sum is exact and
independent of summation order.

Reads from the configuration ``tables``, ``in_core_rows_per_chip`` and
``data`` (``key_cardinality``, ``value_max``).  Every table has
``in_core_rows_per_chip`` rows per chip.
"""

from __future__ import annotations

from typing import Dict, Sequence, Union

import numpy as np


def make_table_data(rows: int, seed: Union[int, Sequence[int]],
                    cardinality: float = 0.9,
                    value_max: int = 256) -> Dict[str, np.ndarray]:
    """One table ``{k: int64, v0: float32}`` of ``rows`` rows.

    Keys are drawn uniformly from ``cardinality * rows`` distinct values;
    payloads are whole numbers in ``[0, value_max)``.  ``seed`` is
    anything ``numpy.random.default_rng`` takes: the same seed, the same
    table."""
    rng = np.random.default_rng(seed)
    n_unique = max(1, int(rows * cardinality))
    k = rng.integers(0, n_unique, rows, dtype=np.int64)
    v = rng.integers(0, value_max, rows)
    return {"k": k, "v0": v.astype(np.float32)}


def make_tables(config: dict, seed: int, chips: int,
                tables: Sequence[str]) -> Dict[str, Dict[str, np.ndarray]]:
    """The configuration's ``tables`` on ``chips`` chips, from ``seed``:
    the ``i``-th table of the configuration from the seed sequence
    ``[seed mod 2**63, i]``, whichever others are made."""
    rows = int(config["in_core_rows_per_chip"]) * chips
    data = config["data"]
    return {name: make_table_data(
        rows, [seed % (1 << 63), i], cardinality=data["key_cardinality"],
        value_max=data["value_max"])
        for i, name in enumerate(config["tables"]) if name in tables}

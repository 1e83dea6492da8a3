"""Input tables from a seed, and the Parquet datasets the cells read.

``make_table_data`` is the paper's data recipe (CylonFlow §V): uniform
int64 keys at 90% cardinality and integer-valued float32 payloads, so
that every sum is exact and independent of summation order.
"""

from __future__ import annotations

import os
import shutil
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


def write_dataset(data: Dict[str, np.ndarray], path: str,
                  files: int) -> str:
    """Write ``data`` as ``files`` Parquet files under ``path`` (replacing
    what was there); returns the glob that reads them back."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    n = len(next(iter(data.values())))
    step = -(-n // files)
    for i in range(files):
        part = {c: v[i * step:(i + 1) * step] for c, v in data.items()}
        pq.write_table(pa.table(part), os.path.join(path, f"part{i}.parquet"))
    return os.path.join(path, "*.parquet")

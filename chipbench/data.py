"""The Parquet datasets the cells read, written from a recipe's tables."""

from __future__ import annotations

import os
import shutil
from typing import Dict

import numpy as np


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

"""Jit'd public wrapper for the radix-partition kernel (pads + dispatches).

Implementation selection (``impl``):

* ``"auto"``   — the sort-free XLA segment-cumsum path (``xla.py``) on
                 every backend; this is what the dataframe shuffle uses.
                 It is pure ``jnp``, so it is safe inside ``shard_map`` /
                 ``vmap`` regions, and it compiles for a v5e at 2^24 rows
                 where the Pallas kernel's lane-padded layout does not fit
                 in HBM (``radix_partition.py``).  No chip measurement has
                 favoured the kernel yet.
* ``"pallas"`` — the Pallas kernel (interpret mode off-TPU).
* ``"xla"``    — the same as ``"auto"``.
* ``"ref"``    — the sort-based jnp oracle (``ref.py``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..common import round_up
from .radix_partition import radix_partition_pallas
from .ref import radix_partition_ref
from .xla import radix_partition_xla


def radix_partition(dest: jax.Array, num_buckets: int, block_rows: int = 256,
                    use_kernel: bool = True,
                    interpret: Optional[bool] = None,
                    impl: str = "auto"):
    """(ranks, hist) for destination buckets; see module docstring for ``impl``."""
    if not use_kernel or impl == "ref":
        return radix_partition_ref(dest, num_buckets)
    if impl in ("auto", "xla"):
        return radix_partition_xla(dest, num_buckets)
    if impl != "pallas":
        raise ValueError(f"unknown radix_partition impl {impl!r}")
    n = dest.shape[0]
    n_pad = round_up(max(n, block_rows), block_rows)
    # padded rows need a bucket strictly above every real bucket — round up
    # PAST num_buckets when rows are padded so the pad bucket never collides
    # with real bucket num_buckets-1.
    nb_pad = round_up(max(num_buckets + (1 if n_pad != n else 0), 128), 128)
    d = dest
    if n_pad != n:
        d = jnp.concatenate(
            [d, jnp.full((n_pad - n,), nb_pad - 1, dest.dtype)])
    ranks, hist = radix_partition_pallas(
        d, nb_pad, block_rows=block_rows, interpret=interpret)
    return ranks[:n], hist[:num_buckets]

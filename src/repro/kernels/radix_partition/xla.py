"""Sort-free XLA formulation of radix partition (the shuffle's hot path).

``radix_partition_ref`` is the sort-based oracle (two O(n log n) passes —
exactly the cost the sort-free shuffle removes).  This module computes the
same (rank-in-bucket, histogram) pair as a *segment cumsum*: the stable
rank of row ``i`` is the running count of earlier rows with the same
destination, i.e. an exclusive prefix sum segmented by destination over an
unsorted segment vector.

Two regimes, both free of any sort and of a full ``(n, nb)`` one-hot
materialisation at scale:

* **dense** (``n * nb`` small): one exclusive cumsum over the one-hot
  matrix — a single fused elementwise+scan program, fastest for the
  shuffle's case where ``nb = p + 1`` is tiny;
* **blocked** (``n * nb`` large): ``lax.scan`` over row blocks carrying
  the running per-bucket histogram — the same structure as the Pallas TPU
  kernel, with peak memory O(block_rows · nb) instead of O(n · nb).

Used by ``ops.radix_partition(impl="auto")`` on every backend, and so by
the dataframe shuffle's scatter (it is pure ``jnp``, so it is safe under
``shard_map`` / ``vmap`` where an interpret-mode ``pallas_call`` is not).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..common import round_up

#: switch to the blocked scan above this many one-hot cells (~16 MiB i32)
_DENSE_CELLS = 1 << 22


def _dense(dest: jax.Array, num_buckets: int):
    n = dest.shape[0]
    onehot = (dest[:, None] == jnp.arange(num_buckets, dtype=dest.dtype)
              ).astype(jnp.int32)                       # (n, nb)
    excl = jnp.cumsum(onehot, axis=0) - onehot          # exclusive, per bucket
    safe = jnp.clip(dest, 0, num_buckets - 1).astype(jnp.int32)
    ranks = jnp.take_along_axis(excl, safe[:, None], axis=1)[:, 0]
    hist = jnp.sum(onehot, axis=0)
    return ranks, hist


def _blocked(dest: jax.Array, num_buckets: int, block_rows: int):
    n = dest.shape[0]
    n_pad = round_up(max(n, block_rows), block_rows)
    d = dest
    if n_pad != n:
        # pad bucket = num_buckets: one-hot all-zero, so the histogram and
        # the running counts never see the padding rows
        d = jnp.concatenate(
            [d, jnp.full((n_pad - n,), num_buckets, dest.dtype)])
    blocks = d.reshape(-1, block_rows)
    iota = jnp.arange(num_buckets, dtype=d.dtype)

    def step(running, db):
        onehot = (db[:, None] == iota).astype(jnp.int32)   # (R, nb)
        excl = jnp.cumsum(onehot, axis=0) - onehot
        safe = jnp.clip(db, 0, num_buckets - 1).astype(jnp.int32)
        in_block = jnp.take_along_axis(excl, safe[:, None], axis=1)[:, 0]
        ranks_b = jnp.take(running, safe) + in_block
        return running + jnp.sum(onehot, axis=0), ranks_b

    hist, ranks = jax.lax.scan(step, jnp.zeros((num_buckets,), jnp.int32),
                               blocks)
    return ranks.reshape(-1)[:n], hist


def radix_partition_xla(dest: jax.Array, num_buckets: int,
                        block_rows: Optional[int] = None):
    """Sort-free (ranks, hist): segment cumsum over destinations.

    ``dest``: (n,) int32 in [0, num_buckets); returns stable within-bucket
    ranks (n,) int32 and the bucket histogram (num_buckets,) int32.
    ``block_rows`` forces the blocked-scan regime (tests); ``None`` picks
    dense vs blocked from the one-hot cell count.
    """
    n = dest.shape[0]
    if block_rows is None:
        if n * num_buckets <= _DENSE_CELLS:
            return _dense(dest, num_buckets)
        block_rows = 4096
    return _blocked(dest, num_buckets, block_rows)

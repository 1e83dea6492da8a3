"""Distributed sort: sample sort (splitter-based range partition + local sort).

This is also the paper's §VI "sample-based repartitioning" for skew/straggler
mitigation: the splitters are sampled quantiles, so output partitions are
balanced even on skewed keys.  ``repartition_balanced`` exposes that use
directly (used by the training data pipeline for straggler mitigation).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..comm import Communicator
from ..nulls import mask_name
from .ops_local import descending_key, sort_local
from .shuffle import ShuffleStats, shuffle
from .table import Table, _sentinel_for


def _range_dest(table: Table, key_col: str, comm: Communicator,
                samples: int, descending: bool = False) -> jax.Array:
    """Destination ranks for a range partition on ``key_col``.

    Nulls-last semantics: null keys are excluded from the splitter sample
    (their canonical-zero values would skew the quantiles toward rank 0)
    and routed to the last rank, where the local sort puts them at the
    tail — the global order ends ... , max, null, null.  ``descending``
    partitions the key's ``descending_key``, so rank 0 holds the largest
    keys (nulls still last)."""
    p = comm.size()
    key = table.columns[key_col]
    if descending:
        key = descending_key(key)
    m = table.columns.get(mask_name(key_col))
    valid = table.valid_mask()
    if m is None:
        splitters = _sample_splitters(key, valid, comm, samples)
        return jnp.searchsorted(splitters, key, side="right").astype(jnp.int32)
    splitters = _sample_splitters(key, valid & m, comm, samples)
    dest = jnp.searchsorted(splitters, key, side="right").astype(jnp.int32)
    return jnp.where(m, dest, p - 1)


def _sample_splitters(key: jax.Array, valid: jax.Array,
                      comm: Communicator, samples: int) -> jax.Array:
    """Gather per-rank key samples and return p-1 global splitters.

    ``valid`` is a boolean participation mask (row-count prefix for plain
    sorts; additionally excluding null keys for nullable sort columns —
    their canonical-zero values would drag the quantiles toward rank 0).
    """
    p = comm.size()
    n_valid = jnp.sum(valid).astype(jnp.int32)
    skey = jnp.sort(jnp.where(valid, key, _sentinel_for(key.dtype)))
    # evenly spaced positions within the sorted valid prefix
    n_local = jnp.minimum(n_valid, samples)
    idx = (jnp.arange(samples) * jnp.maximum(n_valid, 1)) // jnp.maximum(samples, 1)
    idx = jnp.minimum(idx, jnp.maximum(n_valid - 1, 0)).astype(jnp.int32)
    local = jnp.where(jnp.arange(samples) < n_local, jnp.take(skey, idx),
                      _sentinel_for(key.dtype))
    allsamp = comm.all_gather(local).reshape(-1)          # (p*samples,)
    total_valid = jax.lax.psum(n_local, comm.axis)
    ssorted = jnp.sort(allsamp)
    qpos = ((jnp.arange(1, p) * total_valid) // p).astype(jnp.int32)
    qpos = jnp.minimum(qpos, p * samples - 1)
    return jnp.take(ssorted, qpos)                        # (p-1,)


def sort(
    table: Table,
    comm: Communicator,
    by: Sequence[str],
    samples: int = 64,
    ascending: Optional[Sequence[bool]] = None,
    **shuffle_kw,
) -> Tuple[Table, ShuffleStats]:
    """Globally sort by ``by`` across ranks (full lexsort within rank).

    Rank r holds the r-th contiguous range of ``by[0]``, every copy of a
    key on one rank; within a rank rows are lex-sorted by all of ``by``, so
    the concatenation of the ranks is in global order.  ``ascending`` gives
    each key's direction (default: all ascending).
    """
    dest = _range_dest(table, by[0], comm, samples,
                       descending=ascending is not None and not ascending[0])
    shuffled, stats = shuffle(table, comm, dest=dest, **shuffle_kw)
    return sort_local(shuffled, by, ascending), stats


def limit(table: Table, comm: Communicator, n: int) -> Table:
    """The first ``n`` rows in global order: rank order, then row order
    within each rank (the order ``sort`` leaves).  Each rank keeps what is
    left of ``n`` after the rows of the ranks before it, the exclusive
    prefix of the row counts; no row moves."""
    counts = comm.all_gather(table.row_count[None]).reshape(-1)
    before = jnp.sum(jnp.where(jnp.arange(counts.shape[0]) < comm.rank(),
                               counts, 0))
    keep = jnp.clip(n - before, 0, table.row_count).astype(jnp.int32)
    return Table(table.columns, keep).mask_padding()


def repartition_balanced(
    table: Table,
    comm: Communicator,
    key_col: str,
    samples: int = 64,
    **shuffle_kw,
) -> Tuple[Table, ShuffleStats]:
    """Sample-based repartition (paper §VI): balance rows across ranks.

    Range-partitions on sampled quantiles of ``key_col`` without the final
    local sort — used for skew/straggler mitigation in long pipelines.
    """
    dest = _range_dest(table, key_col, comm, samples)
    return shuffle(table, comm, dest=dest, **shuffle_kw)

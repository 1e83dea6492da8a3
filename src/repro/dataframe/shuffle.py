"""Distributed shuffle: capacity-based all-to-all (the paper's core comm op).

MPI AllToAllv sends exact per-destination byte counts; XLA collectives are
static-shape.  The adaptation (DESIGN.md §2) is the MoE-capacity idiom:

  1. hash keys -> destination rank (or take explicit destinations),
  2. counts exchange (tiny all_to_all) for observability + receive counts,
  3. rows are bucketed into a ``(p, bucket_capacity)`` send buffer
     (overflow rows are dropped and *counted* —
     ``ShuffleStats.send_dropped``),
  4. data all_to_all per packed buffer (4-byte columns are bitcast and
     packed into a single ``(p, cap, ncols)`` uint32 buffer so the shuffle
     issues one large collective — the "fewer, larger messages"
     optimization the paper attributes to tuned MPI algorithms), optionally
     *chunked* along the capacity axis (``a2a_chunks``) into k pipelined
     collectives (``Communicator.all_to_all_chunked``),
  5. receive-side compaction back to a fixed-capacity ``Table``.

Two bucketize/compaction implementations (``impl``):

* ``"radix"`` (default) — sort-free hot path.  Send side: the
  ``kernels.radix_partition`` (rank-in-bucket, histogram) pair drives a
  direct scatter of the u32-packed rows — each row is touched exactly once,
  no ``argsort``/gather.  Receive side: exclusive prefix sums over
  ``recv_counts`` give every received row its output slot, so compaction
  is a single O(n) masked scatter.  The partition is the segment-cumsum
  XLA path on every backend (``kernels/radix_partition/ops.py``).
* ``"sorted"`` — the original two-``argsort`` implementation
  (O(n log n) send-side bucketize + O(n log n) receive-side compaction),
  kept as the parity oracle and benchmark baseline.

Both produce **bit-identical** outputs (same rows in the same slots): the
radix ranks are stable, so overflow drops the same rows, and the prefix-sum
compaction enumerates valid rows in the same (source-rank, slot) order as
the stable sort did.

The sample-based repartitioner (``sort.py`` splitters, paper §VI future
work) exists to keep bucket skew bounded so capacity factors stay small.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..comm import Communicator
from ..kernels import radix_partition
from .ops_local import hash_columns
from .table import Table


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShuffleStats:
    """Per-rank observability for one shuffle (traced arrays + static tags)."""

    sent_counts: jax.Array   # (p,) rows sent to each rank (post-capacity)
    recv_counts: jax.Array   # (p,) rows received from each rank
    send_dropped: jax.Array  # () rows dropped by send-bucket capacity
    recv_dropped: jax.Array  # () rows dropped by receive-table capacity
    shuffle_impl: str = "radix"   # static: which bucketize path ran
    a2a_chunks: int = 1           # static: all-to-all pipeline depth

    def tree_flatten(self):
        return (self.sent_counts, self.recv_counts, self.send_dropped,
                self.recv_dropped), (self.shuffle_impl, self.a2a_chunks)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def default_bucket_capacity(capacity: int, p: int, factor: float = 2.0) -> int:
    """Per-destination bucket size: balanced share × skew headroom, 8-aligned."""
    return max(8, _round_up(int(-(-capacity // p) * factor), 8))


def _pack_u32(cols: Dict[str, jax.Array], names) -> jax.Array:
    """Bitcast 4-byte columns to uint32 and stack: (cap,) xN -> (cap, N).

    Bool columns (validity masks) widen to uint32 lanes: wasteful per bit,
    but it keeps the whole row — masks included — in the one large packed
    collective instead of issuing a separate small all_to_all per mask."""
    parts = []
    for n in names:
        v = cols[n]
        if v.dtype == jnp.float32:
            v = jax.lax.bitcast_convert_type(v, jnp.uint32)
        elif v.dtype == jnp.bool_:
            v = v.astype(jnp.uint32)
        elif v.dtype in (jnp.int32, jnp.uint32):
            v = v.view(jnp.uint32) if hasattr(v, "view") else jax.lax.bitcast_convert_type(v, jnp.uint32)
        else:
            raise TypeError(n)
        parts.append(v)
    return jnp.stack(parts, axis=-1)


def _unpack_u32(buf: jax.Array, names, dtypes) -> Dict[str, jax.Array]:
    out = {}
    for i, n in enumerate(names):
        v = buf[..., i]
        if dtypes[n] == jnp.float32:
            v = jax.lax.bitcast_convert_type(v, jnp.float32)
        else:
            v = v.astype(dtypes[n])
        out[n] = v
    return out


#: (label, rank) pairs that already warned since the last query start —
#: the morsel executor runs one callback per shuffle PER MORSEL per rank,
#: so without dedupe a streaming run spams hundreds of identical warnings.
#: The executors reset this at query start; totals stay exactly attributed
#: via the end-of-query ``describe_drops`` summary.
_warned_overflow: set = set()


def reset_overflow_warnings() -> None:
    """Start a fresh warn-once-per-(op label, rank) window (called by the
    executors at query start)."""
    _warned_overflow.clear()


def _overflow_warn(rank, send_dropped, recv_dropped, label=""):
    """Host-side overflow check (``debug_overflow=True``): warn, don't drop
    silently — and say *which* op and rank overflowed.  Runs as a debug
    callback so it works under jit/shard_map (one callback per rank);
    deduplicated to once per (op label, rank) per query."""
    import warnings
    sd, rd = int(send_dropped), int(recv_dropped)
    if sd or rd:
        key = (label or "shuffle", int(rank))
        if key in _warned_overflow:
            return
        _warned_overflow.add(key)
        where = f"{key[0]} @ rank {key[1]}"
        warnings.warn(
            f"{where} dropped rows: send_dropped={sd} recv_dropped={rd} "
            f"(raise bucket_capacity / out_capacity or capacity_factor; "
            f"per-query totals are attributed in the end-of-query "
            f"summary)",
            RuntimeWarning, stacklevel=2)


@jax.named_scope("shuffle")
def shuffle(
    table: Table,
    comm: Communicator,
    key_cols: Optional[Sequence[str]] = None,
    dest: Optional[jax.Array] = None,
    bucket_capacity: Optional[int] = None,
    out_capacity: Optional[int] = None,
    capacity_factor: float = 2.0,
    pack: bool = True,
    impl: str = "radix",
    a2a_chunks: int = 1,
    debug_overflow: bool = False,
    label: str = "",
) -> Tuple[Table, ShuffleStats]:
    """Repartition rows across the comm axis by key hash or explicit dest.

    Must run inside a shard_map region over ``comm.axis``.  ``impl`` selects
    the sort-free ``"radix"`` hot path or the ``"sorted"`` baseline (module
    docstring); ``a2a_chunks`` splits the data collective into k pipelined
    pieces; ``debug_overflow`` emits a host-side warning whenever capacity
    pressure drops rows (they are always *counted* in the stats).
    ``label`` is a static plan-level tag (e.g. ``"join(k):left"``) used only
    to attribute overflow warnings — it never affects the computation.
    The whole body runs under ``jax.named_scope("shuffle")``, metadata that
    names its device ops (partition, all-to-all, compaction) in a profile.
    """
    if impl not in ("radix", "sorted"):
        raise ValueError(f"unknown shuffle impl {impl!r}")
    p = comm.size()
    cap = table.capacity
    bucket_cap = bucket_capacity or default_bucket_capacity(cap, p, capacity_factor)
    out_cap = out_capacity or cap
    valid = table.valid_mask()

    if dest is None:
        if not key_cols:
            raise ValueError("need key_cols or dest")
        h = hash_columns(table, key_cols)
        dest = (h % jnp.uint32(p)).astype(jnp.int32)
    dest = jnp.where(valid, dest, p)  # invalid rows -> overflow bin p

    # --- bucketize: per-row send-buffer slot ----------------------------- #
    if impl == "radix":
        # sort-free: stable rank within destination bucket + histogram in
        # one pass of the segment-cumsum XLA path
        ranks, hist = radix_partition(dest, p + 1)
        raw_counts = hist[:p]
        row_rank = ranks
        row_dest = dest
        order = None
    else:
        # the PR-1 two-argsort baseline: stable sort by destination, rank =
        # position - bucket start (kept as oracle + benchmark column)
        order = jnp.argsort(dest, stable=True)
        sorted_dest = jnp.take(dest, order)
        pos = jnp.arange(cap, dtype=jnp.int32)
        bucket_start = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
        row_rank = pos - bucket_start
        row_dest = sorted_dest
        raw_counts = jax.ops.segment_sum(
            jnp.ones((cap,), jnp.int32), dest, num_segments=p + 1)[:p]

    sent_counts = jnp.minimum(raw_counts, bucket_cap)
    send_dropped = jnp.sum(raw_counts - sent_counts)

    in_bucket = (row_dest < p) & (row_rank < bucket_cap)
    slot = jnp.where(in_bucket, row_dest * bucket_cap + row_rank,
                     p * bucket_cap)  # out-of-range -> dropped by mode="drop"

    names = table.column_names
    dtypes = {n: table.columns[n].dtype for n in names}
    four_byte = [n for n in names
                 if dtypes[n] in (jnp.float32, jnp.int32, jnp.uint32,
                                  jnp.bool_)
                 and table.columns[n].ndim == 1]
    packables = four_byte if pack else []
    singles = [n for n in names if n not in packables]

    recv_cols: Dict[str, jax.Array] = {}

    def _scatter(col: jax.Array) -> jax.Array:
        # radix: direct scatter by original row (each row touched once);
        # sorted: rows were gathered into destination order first.
        buf = jnp.zeros((p * bucket_cap,) + col.shape[1:], col.dtype)
        return buf.at[slot].set(col, mode="drop")

    if packables:
        packed = _pack_u32(table.columns, packables)          # (cap, N)
        if order is not None:
            packed = jnp.take(packed, order, axis=0)
        buf = _scatter(packed).reshape(p, bucket_cap, len(packables))
        got = comm.all_to_all_chunked(buf, chunks=a2a_chunks)
        recv_cols.update(_unpack_u32(
            got.reshape(p * bucket_cap, len(packables)), packables, dtypes))
    for n in singles:
        col = table.columns[n]
        if order is not None:
            col = jnp.take(col, order, axis=0)
        buf = _scatter(col).reshape((p, bucket_cap) + col.shape[1:])
        got = comm.all_to_all_chunked(buf, chunks=a2a_chunks)
        recv_cols[n] = got.reshape((p * bucket_cap,) + col.shape[1:])

    recv_counts = comm.exchange_counts(sent_counts)
    total_recv = jnp.sum(recv_counts)
    new_count = jnp.minimum(total_recv, out_cap).astype(jnp.int32)

    # --- receive-side compaction ----------------------------------------- #
    ridx = jnp.arange(p * bucket_cap, dtype=jnp.int32)
    blk, q = ridx // bucket_cap, ridx % bucket_cap
    r_valid = q < jnp.take(recv_counts, blk)
    out_size = min(p * bucket_cap, out_cap)  # what the argsort slice produced
    if impl == "radix":
        # sort-free: slot of a valid row (blk, q) is its rank in the
        # (source-rank, slot) enumeration = exclusive prefix over recv_counts
        offsets = jnp.cumsum(recv_counts) - recv_counts     # exclusive
        out_pos = jnp.where(r_valid, jnp.take(offsets, blk) + q, out_size)
        out_cols = {}
        for n, v in recv_cols.items():
            out = jnp.zeros((out_size,) + v.shape[1:], v.dtype)
            out_cols[n] = out.at[out_pos].set(v, mode="drop")
    else:
        order2 = jnp.argsort(jnp.where(r_valid, 0, 1), stable=True)[:out_cap]
        out_cols = {n: jnp.take(v, order2, axis=0) for n, v in recv_cols.items()}

    recv_dropped = jnp.maximum(total_recv - out_cap, 0)
    if debug_overflow:
        jax.debug.callback(_overflow_warn, comm.rank(), send_dropped,
                           recv_dropped, label=label)

    out = Table(out_cols, new_count).mask_padding()
    stats = ShuffleStats(sent_counts, recv_counts, send_dropped,
                         recv_dropped, shuffle_impl=impl,
                         a2a_chunks=a2a_chunks)
    return out, stats


def replicate_hot_rows(
    table: Table,
    comm: Communicator,
    is_hot: jax.Array,
    hot_cap: int,
    base: Table,
    pack: bool = True,
) -> Tuple[Table, ShuffleStats]:
    """Broadcast each rank's ``is_hot`` rows to every rank, appended to
    ``base`` (the skew-mitigated build side of a broadcast join).

    The salted join path excludes hot build rows from the hash shuffle
    (they route to the overflow bin ``p``, uncounted) and replicates them
    here instead: a stable compaction into ``(hot_cap,)`` slots, one
    packed ``all_gather``, then a prefix-sum append onto ``base`` past its
    ``row_count``.  Output capacity is the static
    ``base.capacity + p * hot_cap``; rows beyond ``hot_cap`` on one rank
    ARE counted as ``send_dropped`` (the decision layer sizes ``hot_cap``
    from an exact host count precisely so this stays zero).

    Must run inside a shard_map region over ``comm.axis``.
    """
    p = comm.size()
    cap = table.capacity
    k = min(int(hot_cap), cap)  # per-rank slots; static + rank-uniform
    hot = is_hot & table.valid_mask()
    n_hot = jnp.sum(hot.astype(jnp.int32))
    sent = jnp.minimum(n_hot, k)
    dropped = (n_hot - sent).astype(jnp.int32)

    order = jnp.argsort(jnp.where(hot, 0, 1), stable=True)[:k]
    counts = comm.all_gather(sent).reshape(p)           # (p,) everywhere
    offsets = jnp.cumsum(counts) - counts               # exclusive
    total = jnp.sum(counts)

    base_cap = base.capacity
    new_cap = base_cap + p * k
    start = base.row_count
    # start <= base_cap and total <= p*k, so the append never overflows
    idx = jnp.arange(p * k, dtype=jnp.int32)
    blk, q = idx // k, idx % k
    g_valid = q < jnp.take(counts, blk)
    pos = jnp.where(g_valid, start + jnp.take(offsets, blk) + q, new_cap)

    names = base.column_names
    dtypes = {n: table.columns[n].dtype for n in names}
    packables = [n for n in names
                 if dtypes[n] in (jnp.float32, jnp.int32, jnp.uint32,
                                  jnp.bool_)
                 and table.columns[n].ndim == 1] if pack else []
    singles = [n for n in names if n not in packables]

    def _append(n: str, flat: jax.Array) -> jax.Array:
        out = jnp.zeros((new_cap,) + flat.shape[1:], flat.dtype)
        out = out.at[:base_cap].set(base.columns[n])
        return out.at[pos].set(flat, mode="drop")

    out_cols: Dict[str, jax.Array] = {}
    if packables:
        packed = jnp.take(_pack_u32(table.columns, packables), order, axis=0)
        got = comm.all_gather(packed).reshape(p * k, len(packables))
        for n, v in _unpack_u32(got, packables, dtypes).items():
            out_cols[n] = _append(n, v)
    for n in singles:
        col = jnp.take(table.columns[n], order, axis=0)
        got = comm.all_gather(col).reshape((p * k,) + col.shape[1:])
        out_cols[n] = _append(n, got)

    new_count = (start + total).astype(jnp.int32)
    out = Table(out_cols, new_count).mask_padding()
    # this rank sends its ``sent`` hot rows to every rank and receives
    # each rank's contribution once — the honest wire accounting
    stats = ShuffleStats(jnp.full((p,), sent, jnp.int32), counts, dropped,
                         jnp.zeros((), jnp.int32))
    return out, stats

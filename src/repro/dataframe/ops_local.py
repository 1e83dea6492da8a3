"""Local (per-partition) DDF sub-operators.

These are the "core local operator" / "auxiliary local operators" of the
paper's sub-operator decomposition (§III-B, Fig 2).  All are pure jnp and
static-shape; the TPU adaptation replaces C++ hash tables with sort-based
vectorized algorithms (see DESIGN.md §2).  The compute hot spots have Pallas
kernel twins in ``repro.kernels`` selected via ``repro.dataframe.ops`` — the
jnp versions here double as their oracles.
"""

from __future__ import annotations

from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nulls import mask_name
from .table import Table, _sentinel_for

# ---------------------------------------------------------------------- #
# Hashing (murmur3-style finalizer) — used for shuffle partitioning
# ---------------------------------------------------------------------- #


def _mix32(h: jax.Array) -> jax.Array:
    h = h.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hash_columns(table: Table, key_cols: Sequence[str]) -> jax.Array:
    """Combined 32-bit hash of the key columns (row-wise).

    Dictionary-encoded string columns hash their int32 *codes* directly:
    the planner recodes join inputs onto a shared dictionary first
    (``planner.dictionary``), so equal strings always carry equal codes
    gang-wide and the hash placement stays consistent — no string-aware
    hashing is ever needed on device."""
    h = jnp.full((table.capacity,), 0x9E3779B9, jnp.uint32)
    for name in key_cols:
        v = table.columns[name]
        if jnp.issubdtype(v.dtype, jnp.floating):
            bits = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
        else:
            bits = v.astype(jnp.uint32)
        h = _mix32(h ^ _mix32(bits) + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))
    return h


def _mix32_np(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


def hash_columns_np(columns, key_cols: Sequence[str]) -> np.ndarray:
    """Driver-side numpy mirror of ``hash_columns`` (bit-identical).

    Used by the out-of-core executor to sub-bucket host-spilled rows by key
    without a device round-trip; parity with the jnp version is what makes
    host buckets agree with device rank placement."""
    n = len(next(iter(columns.values())))
    h = np.full((n,), 0x9E3779B9, np.uint32)
    for name in key_cols:
        v = np.asarray(columns[name])
        if np.issubdtype(v.dtype, np.floating):
            bits = v.astype(np.float32).view(np.uint32)
        else:
            bits = v.astype(np.uint32)
        # same precedence as the jnp expression: ^ binds looser than +
        h = _mix32_np(h ^ (_mix32_np(bits) + np.uint32(0x9E3779B9)
                           + (h << np.uint32(6)) + (h >> np.uint32(2))))
    return h


# ---------------------------------------------------------------------- #
# Sort keys with invalid rows pushed to the end
# ---------------------------------------------------------------------- #


def descending_key(v: jax.Array) -> jax.Array:
    """An order-reversing image of ``v``: sorting it ascending sorts ``v``
    descending.  Floats negate (the sort canonicalizes NaN, so NaN stays
    last, and -0.0 to 0.0); integers and booleans take the bitwise
    complement, which reverses their order and cannot overflow."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.negative(v)
    return jnp.invert(v)


def _order_keys(table: Table, by: Sequence[str],
                ascending: Optional[Sequence[bool]] = None
                ) -> Tuple[jax.Array, ...]:
    """Key arrays for lexsort, with padding rows forced to sort last.

    Nullable sort columns contribute a null flag *more major* than their
    value key, so nulls sort last within each column (pandas
    ``na_position="last"``) in either direction; ties among nulls resolve
    stably because null slots hold the canonical zero.  A column whose
    ``ascending`` flag is False sorts by its ``descending_key``; with no
    flags (or all True) the keys are exactly the ascending ones."""
    valid = table.valid_mask()
    keys = []
    # jnp.lexsort sorts by the LAST key first; build minor -> major.
    for i in reversed(range(len(by))):
        name = by[i]
        v = table.columns[name]
        if ascending is not None and not ascending[i]:
            v = descending_key(v)
        keys.append(jnp.where(valid, v, _sentinel_for(v.dtype)))
        m = table.columns.get(mask_name(name))
        if m is not None:
            keys.append(jnp.where(valid & ~m, 1, 0).astype(jnp.int32))
    return tuple(keys) + (jnp.where(valid, 0, 1).astype(jnp.int32),)


def sort_local(table: Table, by: Sequence[str],
               ascending: Optional[Sequence[bool]] = None) -> Table:
    """Stable multi-key sort of the valid prefix (padding stays at the
    end); ``ascending`` gives each key's direction (default: all
    ascending)."""
    keys = _order_keys(table, by, ascending)
    # validity flag is the most-major key so padding sorts last.
    order = jnp.lexsort(keys[:-1] + (keys[-1],))
    return table.take(order, table.row_count)


def drop_null_keys(table: Table, keys: Sequence[str]) -> Table:
    """Drop rows whose value in any of ``keys`` is null, and retire the
    now-all-True key masks.  Pandas ``merge`` / ``groupby`` semantics: a
    null key never matches and never forms a group.  No-op (compiles to
    nothing) when no key carries a mask."""
    masks = [table.columns[m]
             for m in (mask_name(k) for k in keys) if m in table.columns]
    if not masks:
        return table
    keep = masks[0]
    for m in masks[1:]:
        keep = keep & m
    keep = keep & table.valid_mask()
    order = jnp.argsort(jnp.where(keep, 0, 1), stable=True)
    t = table.take(order, jnp.sum(keep).astype(jnp.int32))
    dead = {mask_name(k) for k in keys}
    return Table({n: v for n, v in t.columns.items() if n not in dead},
                 t.row_count).mask_padding()


# ---------------------------------------------------------------------- #
# Filter / projection / elementwise
# ---------------------------------------------------------------------- #


def filter_rows(table: Table, pred: Callable[[Table], jax.Array]) -> Table:
    """Keep rows where ``pred`` is True; recompact."""
    keep = pred(table) & table.valid_mask()
    # stable compaction: order by (!keep)
    order = jnp.argsort(jnp.where(keep, 0, 1), stable=True)
    return table.take(order, jnp.sum(keep).astype(jnp.int32))


def filter_expr(table: Table, expr) -> Table:
    """Keep rows where the boolean ``repro.expr`` expression holds.

    Three-valued semantics: a predicate that evaluates to null keeps
    nothing (SQL ``WHERE``) — the Kleene canonical-zero invariant already
    makes null predicate slots read False, and the validity conjunction
    below makes the intent explicit."""
    keep, pvalid = expr.evaluate_masked(table)
    keep = jnp.asarray(keep)
    if keep.dtype != jnp.bool_:
        raise TypeError(
            f"filter expression must be boolean, got {keep.dtype}: {expr!r}")
    keep = jnp.broadcast_to(keep, (table.capacity,))
    if pvalid is not None:
        keep = keep & jnp.broadcast_to(pvalid, (table.capacity,))
    keep = keep & table.valid_mask()
    order = jnp.argsort(jnp.where(keep, 0, 1), stable=True)
    return table.take(order, jnp.sum(keep).astype(jnp.int32))


def with_columns(table: Table, exprs: Mapping[str, "object"]) -> Table:
    """Add/replace columns from ``{name: Expr}``; every expression reads
    the *input* table (simultaneous assignment).  Scalar results (pure
    literals) broadcast to full columns.

    A nullable result materializes its validity mask as the companion
    ``__m_<name>`` column; a provably non-null result retires any stale
    mask the assignment overwrites (e.g. ``fillna``)."""
    out = dict(table.columns)
    for name, e in exprs.items():
        v, valid = e.evaluate_masked(table)
        v = jnp.asarray(v)
        if v.ndim == 0:
            v = jnp.broadcast_to(v, (table.capacity,))
        out[name] = v
        if valid is not None:
            out[mask_name(name)] = jnp.broadcast_to(
                valid, (table.capacity,))
        else:
            out.pop(mask_name(name), None)
    return Table(out, table.row_count)


def recode(table: Table, mappings: Mapping[str, "np.ndarray"]) -> Table:
    """Remap dictionary codes: ``new = mapping[old]`` per recoded column.

    ``mappings`` maps column name -> static int32 gather table
    (``dataframe.schema.recode_mapping``), baked into the compiled program
    by the planner's ``recode`` node.  Padding rows gather garbage (their
    codes are not meaningful), exactly like every other operator here.
    """
    out = dict(table.columns)
    for name, mapping in mappings.items():
        m = jnp.asarray(np.asarray(mapping), jnp.int32)
        out[name] = jnp.take(m, table.columns[name], axis=0, mode="clip")
    return Table(out, table.row_count)


def add_scalar(table: Table, value, cols: Optional[Sequence[str]] = None) -> Table:
    """The paper's pipeline terminal op: add a scalar to value columns."""
    names = cols or table.column_names
    out = dict(table.columns)
    for n in names:
        out[n] = table.columns[n] + jnp.asarray(value, table.columns[n].dtype)
    return Table(out, table.row_count)


def map_columns(table: Table, fn: Callable[[jax.Array], jax.Array],
                cols: Sequence[str]) -> Table:
    out = dict(table.columns)
    for n in cols:
        out[n] = fn(table.columns[n])
    return Table(out, table.row_count)


# ---------------------------------------------------------------------- #
# Local groupby: sort + segment reduce
# ---------------------------------------------------------------------- #

_AGG_INIT = {
    "sum": lambda d: jnp.zeros((), d),
    "count": lambda d: jnp.zeros((), jnp.int32),
    "size": lambda d: jnp.zeros((), jnp.int32),
    "min": lambda d: _sentinel_for(d),
    "max": lambda d: (-_sentinel_for(d) if jnp.issubdtype(d, jnp.floating)
                      else jnp.asarray(jnp.iinfo(d).min, d)),
}


def groupby_local(table: Table, keys: Sequence[str],
                  aggs: Mapping[str, Sequence[str]]) -> Table:
    """Group by ``keys``; ``aggs`` maps value column -> list of agg names.

    Output columns: keys plus ``f"{col}_{agg}"``.  Mean is decomposed into
    sum+count by the distributed layer so partial aggregates compose.

    Null semantics (pandas): rows with a null key are dropped; sum/count/
    min/max skip null values (``count`` counts non-null, ``size`` counts
    rows); min/max over an all-null group are null, so those outputs carry
    a ``__m_`` mask when their input does.  Because null value slots hold
    the column's sentinel-free canonical zero, the masked reductions below
    stay mergeable across morsels: an all-null partial emits its agg
    identity plus a False mask, and re-aggregating partials (whose masks
    make them nullable inputs) composes correctly.
    """
    table = drop_null_keys(table, keys)
    sorted_t = sort_local(table, keys)
    valid = sorted_t.valid_mask()
    # segment ids: new segment where any key changes (within valid prefix)
    change = jnp.zeros((table.capacity,), bool)
    for name in keys:
        v = sorted_t.columns[name]
        change = change | jnp.concatenate([jnp.ones((1,), bool), v[1:] != v[:-1]])
    change = change & valid
    seg_ids = jnp.cumsum(change.astype(jnp.int32)) - 1  # 0-based, padding -> last
    seg_ids = jnp.where(valid, seg_ids, table.capacity - 1)
    num_groups = jnp.sum(change).astype(jnp.int32)

    out_cols: Dict[str, jax.Array] = {}
    cap = table.capacity
    for name in keys:
        v = sorted_t.columns[name]
        # first row of each segment carries the key
        out_cols[name] = jnp.zeros((cap,), v.dtype).at[seg_ids].set(
            jnp.where(valid, v, jnp.zeros((), v.dtype)), mode="drop")
    for col, agg_names in aggs.items():
        v = sorted_t.columns[col]
        cmask = sorted_t.columns.get(mask_name(col))
        # effective = rows that contribute to null-skipping aggregates
        eff = valid if cmask is None else (valid & cmask)
        for agg in agg_names:
            out_mask = None
            if agg == "sum":
                vv = jnp.where(eff, v, jnp.zeros((), v.dtype))
                r = jax.ops.segment_sum(vv, seg_ids, num_segments=cap)
            elif agg == "count":
                r = jax.ops.segment_sum(eff.astype(jnp.int32), seg_ids,
                                        num_segments=cap)
            elif agg == "size":
                r = jax.ops.segment_sum(valid.astype(jnp.int32), seg_ids,
                                        num_segments=cap)
            elif agg == "min":
                vv = jnp.where(eff, v, _sentinel_for(v.dtype))
                r = jax.ops.segment_min(vv, seg_ids, num_segments=cap)
                if cmask is not None:
                    out_mask = jax.ops.segment_max(
                        eff.astype(jnp.int32), seg_ids,
                        num_segments=cap) > 0
            elif agg == "max":
                lo = _AGG_INIT["max"](v.dtype)
                vv = jnp.where(eff, v, lo)
                r = jax.ops.segment_max(vv, seg_ids, num_segments=cap)
                if cmask is not None:
                    out_mask = jax.ops.segment_max(
                        eff.astype(jnp.int32), seg_ids,
                        num_segments=cap) > 0
            else:
                raise ValueError(f"unsupported agg {agg!r}")
            if out_mask is not None:
                # canonical zero where the whole group was null
                r = jnp.where(out_mask, r, jnp.zeros((), r.dtype))
                out_cols[mask_name(f"{col}_{agg}")] = out_mask
            out_cols[f"{col}_{agg}"] = r
    out = Table(out_cols, num_groups)
    return out.mask_padding()


# ---------------------------------------------------------------------- #
# Local join: sort-merge with bounded output capacity
# ---------------------------------------------------------------------- #


def _merge_rank(sorted_arr: jax.Array, sorted_query: jax.Array,
                sides: Sequence[str] = ("left", "right")) -> Tuple[jax.Array, ...]:
    """``jnp.searchsorted(sorted_arr, sorted_query, side=s)`` for each
    ``s`` in ``sides``, for a query that is itself sorted.

    Both inputs sorted makes each rank a merge: one sort of the query
    (tag 0, before equal keys of the array), the array (tag 1) and the
    query again (tag 2, after them), and a running count of tag 1 then
    reads, at a tag-0 entry, the keys below the query and, at a tag-2
    entry, the keys at or below it.  A stable sort on the tag alone brings
    the counts back into query order.  Two sorts and a cumsum, where a
    binary search lowers to a ``while`` loop of full-width gathers.  The
    order is ``lax.sort``'s, which ``jnp.searchsorted`` shares: NaN last,
    signed zeros equal.
    """
    tag = {"left": 0, "right": 2}
    blocks = [(sorted_arr, 1)] + [(sorted_query, tag[s]) for s in sides]
    keys = jnp.concatenate([b for b, _ in blocks])
    tags = jnp.concatenate([jnp.full(b.shape, t, jnp.int32) for b, t in blocks])
    # (key, tag) order; entries equal in both carry equal ranks, so the
    # merge needs no stability.
    _, tags = jax.lax.sort((keys, tags), num_keys=2)
    below = jnp.cumsum(tags == 1, dtype=jnp.int32)
    _, below = jax.lax.sort((tags, below), num_keys=1, is_stable=True)
    n = sorted_query.shape[0]
    return tuple(below[:n] if s == "left" else below[below.shape[0] - n:]
                 for s in sides)


def join_local(left: Table, right: Table, on: str,
               out_capacity: Optional[int] = None,
               suffix: str = "_r", with_overflow: bool = False):
    """Inner equi-join via sort + merge rank (vectorized merge).

    Both sides are sorted on ``on``; each left row's range of matches in
    the right side, and the left row that owns each output slot (a rank of
    the slot in the cumulative match counts), come from ``_merge_rank``:
    sorts and a cumsum, O(cap log cap), no loop and no data-dependent
    shapes.  Output capacity is static: ``out_capacity`` (default:
    left.capacity).

    ``with_overflow=True`` additionally returns the number of result rows
    dropped by the static capacity (free here — the total match count is a
    byproduct of the merge — whereas ``join_overflow`` re-sorts both sides).

    Null keys never match (pandas ``merge``): rows with a null ``on`` value
    are dropped from both sides first.  Nullable payload columns keep their
    masks; a right-side mask follows its base column through the collision
    suffix (``v`` -> ``v_r`` implies ``__m_v`` -> ``__m_v_r``).
    """
    out_cap = out_capacity or left.capacity
    left = drop_null_keys(left, [on])
    right = drop_null_keys(right, [on])
    ls = sort_local(left, [on])
    rs = sort_local(right, [on])
    lvalid = ls.valid_mask()
    lkey = jnp.where(lvalid, ls.columns[on], _sentinel_for(ls.columns[on].dtype))
    rkey_raw = rs.columns[on]
    rvalid = rs.valid_mask()
    rkey = jnp.where(rvalid, rkey_raw, _sentinel_for(rkey_raw.dtype))

    # For each left row: range of matches in right.
    lo, hi = _merge_rank(rkey, lkey)
    hi = jnp.minimum(hi, right.row_count)  # sentinel rows never match
    counts = jnp.where(lvalid, jnp.maximum(hi - lo, 0), 0)
    cum = jnp.cumsum(counts)
    total = cum[-1] if counts.shape[0] else jnp.asarray(0, jnp.int32)

    out_idx = jnp.arange(out_cap, dtype=jnp.int32)
    # left row owning output slot o: first l with cum[l] > o
    (l_row,) = _merge_rank(cum, out_idx, sides=("right",))
    l_row_c = jnp.minimum(l_row, left.capacity - 1)
    start = jnp.where(l_row_c > 0, cum[l_row_c - 1], 0)
    k = out_idx - start
    r_row = jnp.minimum(lo[l_row_c] + k, right.capacity - 1)
    valid_out = out_idx < jnp.minimum(total, out_cap)

    cols: Dict[str, jax.Array] = {}
    for name in ls.column_names:
        cols[name] = jnp.take(ls.columns[name], l_row_c, axis=0)
    for name in rs.column_names:
        if name == on or name.startswith(mask_name("")):
            continue
        tgt = name if name not in cols else name + suffix
        cols[tgt] = jnp.take(rs.columns[name], r_row, axis=0)
        rmask = rs.columns.get(mask_name(name))
        if rmask is not None:
            cols[mask_name(tgt)] = jnp.take(rmask, r_row, axis=0)
    out = Table(cols, jnp.minimum(total, out_cap).astype(jnp.int32))
    out = out.mask_padding()
    if with_overflow:
        return out, jnp.maximum(total - out_cap, 0).astype(jnp.int32)
    return out


def join_overflow(left: Table, right: Table, on: str, out_capacity: int) -> jax.Array:
    """Number of join result rows dropped by the static output capacity:
    both sides sorted, each left row's match count from ``_merge_rank``."""
    ls = sort_local(drop_null_keys(left, [on]), [on])
    rs = sort_local(drop_null_keys(right, [on]), [on])
    lvalid = ls.valid_mask()
    lkey = jnp.where(lvalid, ls.columns[on], _sentinel_for(ls.columns[on].dtype))
    rkey = jnp.where(rs.valid_mask(), rs.columns[on],
                     _sentinel_for(rs.columns[on].dtype))
    lo, hi = _merge_rank(rkey, lkey)
    hi = jnp.minimum(hi, rs.row_count)
    total = jnp.sum(jnp.where(lvalid, jnp.maximum(hi - lo, 0), 0))
    return jnp.maximum(total - out_capacity, 0)

"""Lowering: logical DAG -> staged physical plan -> CylonEnv execution.

A *stage* is a maximal set of operators executable in one BSP program
without crossing a communication boundary (the paper's §III-B coalescing,
made explicit).  Elided shuffles do not open a boundary, so optimization
shrinks both the stage count (fewer dispatches in ``bsp_staged``) and the
shuffle count (fewer collectives in every mode).

The compile cache is keyed by a **structural fingerprint** of the plan
(op/param/topology hash, independent of node identity), so two separately
built but identical plans share one compiled program per env.

Execution modes (same contract as the original ``core.plan.execute``):

* ``bsp``        — entire plan in ONE ``env.run`` dispatch,
* ``bsp_staged`` — one dispatch per stage (driver round-trip at every
                   communication boundary),
* ``amt``        — one dispatch per operator, shuffles implemented as
                   allgather-then-select (the Dask/Ray object-store
                   pattern, O(p·data)).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..comm import Communicator
from ..faults import (CapacityOverflow, OverflowPolicy, resolve_faults,
                      resolve_overflow, resolve_retry, resolve_token,
                      run_with_retries)
from ..obs.metrics import record_exec
from ..obs.trace import NULL_TRACER
from ..dataframe import ops_local
from ..expr import token as expr_token
from ..dataframe.groupby import (_normalize, finalize_groupby,
                                 nullable_agg_cols)
from ..dataframe.groupby import groupby as df_groupby
from ..dataframe.ops_local import hash_columns
from ..dataframe.shuffle import ShuffleStats, _round_up
from ..dataframe.shuffle import shuffle as df_shuffle
from ..dataframe.sort import _range_dest
from ..dataframe.sort import limit as df_limit
from ..dataframe.sort import sort as df_sort
from ..nulls import mask_name
from ..dataframe.table import Table
from .logical import LogicalNode, topo

#: param keys that are operator semantics, not shuffle kwargs
_SEMANTIC = {
    "join": ("on", "out_capacity", "shuffle_out_capacity", "elide_left",
             "elide_right", "side_selected", "morsel_out_capacity"),
    "groupby": ("keys", "aggs", "elide_shuffle", "pre_aggregate"),
    "sort": ("by", "elide_shuffle", "ascending"),
    "shuffle": ("key_cols",),
}


# ---------------------------------------------------------------------- #
# Structural fingerprint
# ---------------------------------------------------------------------- #
# Canonical value tokens live in ``repro.expr`` (expressions fingerprint by
# VALUE — two structurally equal expression trees share a token however
# they were built — while legacy callables hash bytecode + captured
# closure values, the best a callable allows).
_token = expr_token


def fingerprint(root: LogicalNode) -> str:
    """Structural hash: equal for identically-shaped plans regardless of
    node identity / construction order (fixes nid-keyed cache misses)."""
    idx: Dict[int, int] = {}
    parts: List[str] = []
    for n in topo(root):
        idx[n.nid] = len(idx)
        params = ",".join(f"{k}={_token(v)}" for k, v in sorted(n.params.items()))
        parts.append(f"{n.op}({params})<-{[idx[i.nid] for i in n.inputs]}")
    return hashlib.sha1("\n".join(parts).encode()).hexdigest()


# ---------------------------------------------------------------------- #
# Physical plan
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class PhysicalPlan:
    root: LogicalNode
    order: List[LogicalNode]              # full topological order
    stage_of: Dict[int, int]              # nid -> stage index
    num_stages: int
    num_shuffles: int
    fingerprint: str
    fired: Tuple[str, ...] = ()           # optimizer rules that fired

    @property
    def scan_names(self) -> List[str]:
        return sorted({n.params["name"] for n in self.order
                       if n.op == "scan"})

    def shuffle_labels(self) -> List[str]:
        """Static labels for every shuffle executed, in topo order."""
        labels: List[str] = []
        for n in self.order:
            p = n.params
            if n.op == "shuffle":
                labels.append(f"shuffle({','.join(p['key_cols'])})")
            elif n.op == "join":
                if not p.get("elide_left"):
                    labels.append(f"join({p['on']}):left")
                if not p.get("elide_right"):
                    labels.append(f"join({p['on']}):right")
            elif n.op == "groupby" and not p.get("elide_shuffle"):
                labels.append(f"groupby({','.join(p['keys'])})")
            elif n.op == "sort" and not p.get("elide_shuffle"):
                labels.append(f"sort({','.join(p['by'])})")
        return labels


def lower(root: LogicalNode, fired: Sequence[str] = ()) -> PhysicalPlan:
    order = topo(root)
    stage_of: Dict[int, int] = {}
    for n in order:
        stage_of[n.nid] = max(
            (stage_of[i.nid] + (1 if i.is_comm() else 0) for i in n.inputs),
            default=0)
    num_stages = max(stage_of.values(), default=0) + 1
    num_shuffles = sum(n.shuffle_count() for n in order)
    return PhysicalPlan(root, order, stage_of, num_stages, num_shuffles,
                        fingerprint(root), tuple(fired))


# ---------------------------------------------------------------------- #
# Shuffle implementations (direct vs the AMT object-store baseline)
# ---------------------------------------------------------------------- #
@jax.named_scope("shuffle")
def shuffle_allgather(table: Table, comm: Communicator,
                      key_cols=None, dest=None, out_capacity=None, **_):
    """Every rank receives ALL rows and keeps those hashed to it.

    Models Dask partd / Ray object-store data sharing: data is published
    globally rather than routed, costing O(p·rows) bandwidth per rank.
    """
    p = comm.size()
    rank = comm.rank()
    cap = table.capacity
    out_cap = out_capacity or cap
    valid = table.valid_mask()
    if dest is None:
        h = hash_columns(table, key_cols)
        dest = (h % jnp.uint32(p)).astype(jnp.int32)
    dest = jnp.where(valid, dest, p)

    gathered_dest = comm.all_gather(dest).reshape(-1)            # (p*cap,)
    keep = gathered_dest == rank
    order = jnp.argsort(jnp.where(keep, 0, 1), stable=True)[:out_cap]
    new_count = jnp.minimum(jnp.sum(keep), out_cap).astype(jnp.int32)
    cols = {}
    for name, col in table.columns.items():
        g = comm.all_gather(col).reshape((-1,) + col.shape[1:])
        cols[name] = jnp.take(g, order, axis=0)
    sent = jax.ops.segment_sum(jnp.ones((cap,), jnp.int32), dest,
                               num_segments=p + 1)[:p]
    stats = ShuffleStats(sent, sent, jnp.asarray(0, jnp.int32),
                         jnp.maximum(jnp.sum(keep) - out_cap, 0)
                         .astype(jnp.int32),
                         shuffle_impl="allgather")
    return Table(cols, new_count).mask_padding(), stats


def _row_bytes(table: Table) -> int:
    return sum(int(v.dtype.itemsize) * math.prod(v.shape[1:])
               for v in table.columns.values())


def _stat_vec(st: ShuffleStats, width: int) -> jax.Array:
    """(rows sent, bytes sent, rows dropped) — the per-shuffle stats triple
    collected inside the program and summed driver-side."""
    rows = jnp.sum(st.sent_counts)
    dropped = (st.send_dropped + st.recv_dropped).astype(jnp.int32)
    return jnp.stack([rows, rows * width, dropped])


# ---------------------------------------------------------------------- #
# Per-shuffle stat attribution (driver-side labels for the in-program
# stats triples; the compiled programs return arrays only, so the label
# sequence is reconstructed from the static plan in dispatch order)
# ---------------------------------------------------------------------- #
def node_stat_labels(node: LogicalNode, salt=None) -> List[str]:
    """Stat labels ``eval_node`` appends for one node, in append order.

    Mirrors ``eval_node`` exactly: shuffle-executing ops contribute one
    label per shuffle; joins additionally contribute an ``:overflow``
    entry (local join output capacity pressure, zero wire bytes).  With a
    fired salting decision (``salt`` maps nid -> SaltDecision) a groupby
    additionally appends its ``:remerge`` partial shuffle and a join its
    ``:broadcast`` hot-row replication (before ``:overflow``)."""
    p = node.params
    salted = salt is not None and node.nid in salt
    if node.op == "shuffle":
        return [f"shuffle({','.join(p['key_cols'])})"]
    if node.op == "join":
        labels = []
        if not p.get("elide_left"):
            labels.append(f"join({p['on']}):left")
        if not p.get("elide_right"):
            labels.append(f"join({p['on']}):right")
        if salted:
            labels.append(f"join({p['on']}):broadcast")
        labels.append(f"join({p['on']}):overflow")
        return labels
    if node.op == "groupby" and not p.get("elide_shuffle"):
        label = f"groupby({','.join(p['keys'])})"
        return [label, f"{label}:remerge"] if salted else [label]
    if node.op == "sort" and not p.get("elide_shuffle"):
        return [f"sort({','.join(p['by'])})"]
    return []


def plan_stat_labels(nodes: Sequence[LogicalNode], salt=None) -> List[str]:
    out: List[str] = []
    for n in nodes:
        out.extend(node_stat_labels(n, salt))
    return out


def pair_stat_labels(labels: Sequence[str], arrays: Sequence[Any]
                     ) -> List[Tuple[str, Any]]:
    """Zip driver-side labels with the in-program stat arrays; falls back
    to positional labels on a mismatch rather than mis-attributing."""
    if len(labels) != len(arrays):
        labels = [f"stats[{i}]" for i in range(len(arrays))]
    return list(zip(labels, arrays))


@dataclasses.dataclass
class ShuffleRecord:
    """Aggregated per-label shuffle accounting with per-rank attribution.

    ``per_rank_rows[r]`` — rows rank ``r`` sent through this shuffle;
    ``per_rank_dropped[r]`` — rows lost at rank ``r`` (send-bucket or
    receive/ join-output capacity pressure).  ``:overflow`` labels carry
    drops only (no wire traffic)."""

    label: str
    rows: int
    bytes: int
    dropped: int
    per_rank_rows: Tuple[int, ...]
    per_rank_dropped: Tuple[int, ...]
    #: out-of-core segment index the label executed in (None in-core).
    #: Keying records by (label, segment) keeps a plan that runs the same
    #: shuffle label in several segments — e.g. a groupby replayed after a
    #: degrade split — attributable per segment instead of smeared into
    #: one row, which is what the skew detector and EXPLAIN ANALYZE need.
    segment: Optional[int] = None


def build_shuffle_records(pairs: Sequence[Tuple]) -> List[ShuffleRecord]:
    """Aggregate labeled (p, 3) stat arrays by (label, segment) — summing
    across repeated executions of the same plan node, e.g. one per morsel.
    ``pairs`` entries are ``(label, array)`` (in-core; segment None) or
    ``(label, array, segment)`` (morsel executor)."""
    agg: Dict[Tuple[str, Optional[int]], np.ndarray] = {}
    order: List[Tuple[str, Optional[int]]] = []
    for pair in pairs:
        label, a = pair[0], pair[1]
        seg = pair[2] if len(pair) > 2 else None
        a = np.asarray(a).reshape(-1, 3).astype(np.int64)
        key = (label, seg)
        if key in agg:
            agg[key] = agg[key] + a
        else:
            agg[key] = a.copy()
            order.append(key)
    return [ShuffleRecord(
        label, int(agg[k][:, 0].sum()), int(agg[k][:, 1].sum()),
        int(agg[k][:, 2].sum()),
        tuple(int(x) for x in agg[k][:, 0]),
        tuple(int(x) for x in agg[k][:, 2]),
        segment=seg) for k in order for label, seg in [k]]


def describe_drops(records: Sequence[ShuffleRecord], limit: int = 6) -> str:
    """Name the op labels and ranks where capacity pressure dropped rows
    (the attribution the rows_dropped RuntimeWarning reports)."""
    offenders = [(r.label, rank, d)
                 for r in records
                 for rank, d in enumerate(r.per_rank_dropped) if d]
    parts = [f"{label} @ rank {rank}: {d} rows"
             for label, rank, d in offenders[:limit]]
    if len(offenders) > limit:
        parts.append(f"... {len(offenders) - limit} more")
    return "; ".join(parts)


def emit_shuffle_events(tracer, pairs: Sequence[Tuple[str, Any]],
                        a2a_chunks: int) -> None:
    """Per-shuffle (and per all-to-all chunk) instant events under the
    currently open stage span.  Device-side op timing is invisible to the
    driver, so these carry data volumes, not durations."""
    for pair in pairs:
        label, a = pair[0], pair[1]
        a = np.asarray(a).reshape(-1, 3)
        rows, byts, dropped = (int(a[:, 0].sum()), int(a[:, 1].sum()),
                               int(a[:, 2].sum()))
        with tracer.span(f"shuffle:{label}", "shuffle", rows=rows,
                         bytes=byts, dropped=dropped):
            if not label.endswith(":overflow"):
                for c in range(max(1, a2a_chunks)):
                    tracer.instant(f"a2a:{label}[chunk {c}]", "chunk",
                                   chunk=c, chunks=a2a_chunks,
                                   bytes=byts // max(1, a2a_chunks))


# ---------------------------------------------------------------------- #
# Node evaluation (runs inside shard_map; shared by all modes)
# ---------------------------------------------------------------------- #
def _shuffle_kw(node: LogicalNode) -> Dict[str, Any]:
    keep = _SEMANTIC.get(node.op, ())
    return {k: v for k, v in node.params.items()
            if k not in keep and k not in ("elided", "note", "expr", "exprs")}


def eval_node(node: LogicalNode, comm: Communicator,
              values: Dict[int, Table], tables: Dict[str, Table],
              shuffle_mode: str,
              stats_out: Optional[List[Tuple[str, jax.Array]]] = None,
              shuffle_impl: str = "radix", a2a_chunks: int = 1,
              salt=None) -> Table:
    """Evaluate one plan node inside the shard_map region, under
    ``jax.named_scope(node.op)``: trace-time metadata that names the
    node's device ops by operator (``repro.obs.hlo``) and changes nothing
    that runs."""
    with jax.named_scope(node.op):
        return _eval_node(node, comm, values, tables, shuffle_mode,
                          stats_out, shuffle_impl, a2a_chunks, salt)


def _eval_node(node, comm, values, tables, shuffle_mode, stats_out,
               shuffle_impl, a2a_chunks, salt) -> Table:
    p = node.params
    ins = [values[i.nid] for i in node.inputs]
    shuffle_fn = df_shuffle if shuffle_mode == "direct" else shuffle_allgather
    decision = salt.get(node.nid) if (salt and shuffle_mode == "direct") \
        else None

    def run_shuffle(label: str, table: Table, **kw) -> Table:
        out, st = shuffle_fn(table, comm, label=label, **kw)
        if stats_out is not None:
            stats_out.append((label, _stat_vec(st, _row_bytes(table))))
        return out

    if node.op == "scan":
        return tables[p["name"]]
    if node.op == "noop":
        return ins[0]
    if node.op == "project":
        # masks ride along with their base columns (never named explicitly)
        cols = list(p["cols"])
        cols += [mask_name(c) for c in p["cols"]
                 if mask_name(c) in ins[0].columns]
        return ins[0].select(cols)
    if node.op == "filter":
        return ops_local.filter_expr(ins[0], p["expr"])
    if node.op == "with_columns":
        return ops_local.with_columns(ins[0], p["exprs"])
    if node.op == "add_scalar":
        return ops_local.add_scalar(ins[0], p["value"], p.get("cols"))
    if node.op == "recode":
        return ops_local.recode(ins[0], p["cols"])
    if node.op == "limit":
        return df_limit(ins[0], comm, p["n"])

    kw = _shuffle_kw(node)
    if shuffle_mode == "direct":
        # plan-level defaults; per-node params (Plan.shuffle(impl=...,
        # a2a_chunks=...)) take precedence
        kw.setdefault("impl", shuffle_impl)
        kw.setdefault("a2a_chunks", a2a_chunks)
    else:
        kw.pop("impl", None)
        kw.pop("a2a_chunks", None)
        kw.pop("debug_overflow", None)
    if node.op == "shuffle":
        out_cap = kw.pop("out_capacity", None)
        return run_shuffle(f"shuffle({','.join(p['key_cols'])})", ins[0],
                           key_cols=p["key_cols"], out_capacity=out_cap, **kw)

    if node.op == "join":
        on = p["on"]
        l, r = ins
        jkw = {k: v for k, v in kw.items() if k != "out_capacity"}
        if "shuffle_out_capacity" in p:  # receive headroom for skewed keys
            jkw["out_capacity"] = p["shuffle_out_capacity"]
        if decision is not None and not p.get("elide_left") \
                and not p.get("elide_right"):
            return _eval_join_salted(node, comm, l, r, decision, jkw,
                                     stats_out)
        if not p.get("elide_left"):
            l = run_shuffle(f"join({on}):left", l, key_cols=[on], **jkw)
        if not p.get("elide_right"):
            r = run_shuffle(f"join({on}):right", r, key_cols=[on], **jkw)
        if stats_out is not None:
            out, ov = ops_local.join_local(l, r, on,
                                           out_capacity=p.get("out_capacity"),
                                           with_overflow=True)
            z = jnp.zeros((), jnp.int32)
            stats_out.append((f"join({on}):overflow", jnp.stack([z, z, ov])))
            return out
        return ops_local.join_local(l, r, on,
                                    out_capacity=p.get("out_capacity"))

    if node.op == "groupby":
        keys, aggs = p["keys"], p["aggs"]
        physical, post = _normalize(aggs)
        nullable = nullable_agg_cols(ins[0], physical)
        if p.get("elide_shuffle"):
            # input already co-partitioned on the keys: local-only groupby
            final = ops_local.groupby_local(ins[0], keys, physical)
            return finalize_groupby(final, keys, post, nullable)
        if (decision is not None and shuffle_mode == "direct"
                and not p.get("pre_aggregate")):
            return _eval_groupby_salted(node, comm, ins[0], decision, kw,
                                        stats_out)
        if shuffle_mode == "direct":
            pre = bool(p.get("pre_aggregate", False))
            out, st = df_groupby(ins[0], comm, keys, aggs,
                                 pre_aggregate=pre,
                                 label=f"groupby({','.join(keys)})", **kw)
            if stats_out is not None:
                if pre:
                    # the wire carries keys + stage-1 partial-agg columns
                    width = sum(ins[0].columns[k].dtype.itemsize for k in keys)
                    for col, names in physical.items():
                        width += sum(4 if a == "count"
                                     else ins[0].columns[col].dtype.itemsize
                                     for a in names)
                else:
                    width = _row_bytes(ins[0])
                stats_out.append((f"groupby({','.join(keys)})",
                                  _stat_vec(st, width)))
            return out
        # AMT path: ship raw rows (Dask-style task granularity, no pre-agg)
        shuffled = run_shuffle(f"groupby({','.join(keys)})", ins[0],
                               key_cols=list(keys),
                               **{k: v for k, v in kw.items()
                                  if k != "pre_aggregate"})
        final = ops_local.groupby_local(shuffled, keys, physical)
        return finalize_groupby(final, keys, post, nullable)

    if node.op == "sort":
        by, ascending = p["by"], p.get("ascending")
        if p.get("elide_shuffle"):
            return ops_local.sort_local(ins[0], by, ascending)
        if shuffle_mode == "direct":
            out, st = df_sort(ins[0], comm, by, ascending=ascending,
                              label=f"sort({','.join(by)})", **kw)
            if stats_out is not None:
                stats_out.append((f"sort({','.join(by)})",
                                  _stat_vec(st, _row_bytes(ins[0]))))
            return out
        dest = _range_dest(ins[0], by[0], comm, kw.pop("samples", 64),
                           descending=ascending is not None
                           and not ascending[0])
        shuffled = run_shuffle(f"sort({','.join(by)})", ins[0], dest=dest,
                               **kw)
        return ops_local.sort_local(shuffled, by, ascending)

    raise ValueError(node.op)


# ---------------------------------------------------------------------- #
# Salted evaluation (repro.adapt; in-core, inside shard_map)
# ---------------------------------------------------------------------- #
def _hot_mask(h: jax.Array, hot_hashes) -> jax.Array:
    """Rows whose key hash is one of the (static) hot constants."""
    hot = jnp.zeros(h.shape, jnp.bool_)
    for v in hot_hashes:
        hot = hot | (h == jnp.uint32(v))
    return hot


def _eval_groupby_salted(node: LogicalNode, comm: Communicator,
                         table: Table, decision, kw, stats_out) -> Table:
    """Two-shuffle salted groupby: salted row shuffle + stage-1 partials,
    then a tiny unsalted partial re-merge on each key's home rank.

    Both shuffles get full-table bucket/out capacities: the whole point of
    the decision is that one rank would otherwise receive ~everything, so
    per-destination "balanced share" sizing is exactly what we can't
    assume until the salt has done its job."""
    from ..dataframe.groupby import groupby_salted
    p = node.params
    keys = list(p["keys"])
    cap = table.capacity
    label = f"groupby({','.join(keys)})"
    skw = dict(kw, bucket_capacity=cap, label=label)
    skw["out_capacity"] = skw.get("out_capacity") or cap
    rkw = dict(kw, bucket_capacity=cap, out_capacity=cap,
               label=f"{label}:remerge")
    out, st1, st2 = groupby_salted(table, comm, keys, p["aggs"],
                                   decision.hot_hashes, decision.k,
                                   shuffle_kw=skw, remerge_kw=rkw)
    if stats_out is not None:
        physical, _ = _normalize(p["aggs"])
        width = sum(table.columns[k].dtype.itemsize for k in keys)
        for col, names in physical.items():
            width += sum(4 if a == "count"
                         else table.columns[col].dtype.itemsize
                         for a in names)
        stats_out.append((label, _stat_vec(st1, _row_bytes(table))))
        stats_out.append((f"{label}:remerge", _stat_vec(st2, width)))
    return out


def _eval_join_salted(node: LogicalNode, comm: Communicator,
                      l: Table, r: Table, decision, jkw, stats_out) -> Table:
    """Skew-mitigated hash join: hot probe rows stay on their source rank,
    hot build rows skip the hash shuffle (overflow bin, uncounted) and are
    broadcast-appended to every rank's build table instead — so each hot
    probe row meets every build row of its key locally, exactly once."""
    from ..dataframe.shuffle import replicate_hot_rows
    p = node.params
    on = p["on"]
    psize = comm.size()
    rank = comm.rank()

    hot_l = _hot_mask(hash_columns(l, [on]), decision.hot_hashes)
    hot_r = _hot_mask(hash_columns(r, [on]), decision.hot_hashes)
    base_l = (hash_columns(l, [on]) % jnp.uint32(psize)).astype(jnp.int32)
    base_r = (hash_columns(r, [on]) % jnp.uint32(psize)).astype(jnp.int32)
    dest_l = jnp.where(hot_l, jnp.asarray(rank, jnp.int32), base_l)
    dest_r = jnp.where(hot_r, jnp.int32(psize), base_r)  # excluded

    # probe: the self-bucket must hold every hot row this rank keeps, and
    # the output every kept-hot + received-cold row
    lkw = dict(jkw, bucket_capacity=l.capacity)
    lkw["out_capacity"] = (lkw.get("out_capacity")
                           or _round_up(2 * l.capacity, 8))
    rkw = dict(jkw)
    rkw["out_capacity"] = rkw.get("out_capacity") or r.capacity

    l2, st_l = df_shuffle(l, comm, dest=dest_l,
                          label=f"join({on}):left", **lkw)
    r2, st_r = df_shuffle(r, comm, dest=dest_r,
                          label=f"join({on}):right", **rkw)
    r2, st_b = replicate_hot_rows(r, comm, hot_r, decision.hot_cap, r2)
    if stats_out is not None:
        stats_out.append((f"join({on}):left", _stat_vec(st_l, _row_bytes(l))))
        stats_out.append((f"join({on}):right", _stat_vec(st_r, _row_bytes(r))))
        stats_out.append((f"join({on}):broadcast",
                          _stat_vec(st_b, _row_bytes(r))))
        out, ov = ops_local.join_local(l2, r2, on,
                                       out_capacity=p.get("out_capacity"),
                                       with_overflow=True)
        z = jnp.zeros((), jnp.int32)
        stats_out.append((f"join({on}):overflow", jnp.stack([z, z, ov])))
        return out
    return ops_local.join_local(l2, r2, on,
                                out_capacity=p.get("out_capacity"))


# ---------------------------------------------------------------------- #
# Driver-side execution
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class ExecStats:
    """Driver-side observability for one plan execution."""

    mode: str
    num_stages: int
    num_shuffles: int
    dispatches: int
    rows_shuffled: int
    bytes_shuffled: int
    shuffle_labels: List[str]
    fired: Tuple[str, ...]
    shuffle_impl: str = "radix"   # bucketize path: radix | sorted | allgather
    a2a_chunks: int = 1           # all-to-all pipeline depth
    #: rows lost to capacity pressure anywhere in the plan (send buckets,
    #: receive tables, join output) — deterministic post-hoc overflow check;
    #: 0 for a correctly-capacitated run
    rows_dropped: int = 0
    #: compile-cache traffic during this execution (CylonEnv counters delta)
    cache_hits: int = 0
    cache_misses: int = 0
    # -- ingest attribution (repro.io scans; docs/io.md) ------------------ #
    rows_read: int = 0        # rows entering the plan through its scans
    bytes_read: int = 0       # source bytes behind those scans (io ingest)
    # -- out-of-core morsel execution only (see docs/out_of_core.md) ----- #
    morsel_rows: Optional[int] = None  # per-rank morsel capacity, None=in-core
    morsels: int = 0                   # morsel program dispatches
    spill_bytes: int = 0               # valid rows written to host spill
    h2d_bytes: int = 0                 # host->device morsel transfer bytes
    d2h_bytes: int = 0                 # device->host spill transfer bytes
    # -- timing (populated on collect_stats=True / traced runs; fenced ---- #
    # -- with jax.block_until_ready so device execution is covered) ------- #
    wall_time_s: float = 0.0           # end-to-end dispatch+execute wall time
    #: per-dispatch-unit wall times: (unit label, seconds).  One entry per
    #: stage in bsp_staged, per operator in amt, per segment (plus resident
    #: builds / combines) out-of-core; a single "program" entry in bsp,
    #: where XLA fuses all stages into one dispatch.
    stage_times: List[Tuple[str, float]] = \
        dataclasses.field(default_factory=list)
    #: per-shuffle-label accounting with per-rank attribution (aggregated
    #: across morsels); rows/bytes sum to rows_shuffled/bytes_shuffled
    shuffle_records: List["ShuffleRecord"] = \
        dataclasses.field(default_factory=list)
    # -- fault tolerance (repro.faults; docs/fault_tolerance.md) ---------- #
    retries: int = 0           # dispatch units replayed after a fault
    degraded: int = 0          # capacity-degrade re-executions (overflow)
    faults_injected: int = 0   # faults the active FaultPlan fired this query
    # -- runtime skew mitigation (repro.adapt; docs/adaptive.md) ---------- #
    adaptive: bool = False         # was the adaptive layer enabled
    salted_shuffles: int = 0       # shuffle boundaries that got salted
    splitter_refreshes: int = 0    # sort splitter re-samples that fired
    autotune_steps: int = 0        # tuner-chosen degrade replans
    #: one dict per fired mitigation ({"kind": "salted" | ...}) — the
    #: machine-readable trail EXPLAIN ANALYZE renders as annotations
    adapt_events: List[Dict[str, Any]] = \
        dataclasses.field(default_factory=list)
    #: the finished ``repro.obs.QueryTrace`` when the execution was traced
    trace: Optional[Any] = dataclasses.field(default=None, repr=False,
                                             compare=False)


def check_scan_dictionaries(order: Sequence[LogicalNode],
                            tables: Dict[str, Any]) -> None:
    """Reject runtime tables whose dictionaries differ from compile time.

    Recode gather tables and lowered string literals are baked into the
    compiled plan from the *compile-time* catalog; running that plan
    against a table with a different dictionary would silently decode
    fabricated strings.  Tables without a ``dictionaries`` attribute (raw
    numpy dicts) were encoded by ``build_catalog`` at compile time and are
    re-encoded identically at ingest, so only holder mismatches can occur.
    """
    for n in order:
        if n.op != "scan":
            continue
        t = tables.get(n.params["name"])
        got = getattr(t, "dictionaries", None)
        if got is None:
            continue
        want = {c: d for c, d in n.dicts.items() if c in n.schema}
        if dict(got) != want:
            diff = sorted(set(got) ^ set(want)
                          | {c for c in set(got) & set(want)
                             if tuple(got[c]) != want[c]})
            raise ValueError(
                f"scan {n.params['name']!r}: table dictionaries for "
                f"{diff} differ from the ones this plan was compiled "
                f"against — re-run compile_plan/execute with the current "
                f"tables (recode tables and lowered string literals are "
                f"baked in at compile time)")


def attach_dictionaries(out, root: LogicalNode):
    """Re-attach driver-side dictionaries to an execution result.

    The compiled programs move int32 codes only; the annotated root knows
    which output columns are dictionary-encoded and by what dictionary
    (``LogicalNode.dicts``), so the driver restores the metadata here, and
    likewise which hold dates (``LogicalNode.dates``).
    """
    if root.dicts and hasattr(out, "dictionaries"):
        live = set(getattr(out, "column_names", ()) or root.dicts)
        out.dictionaries = {c: d for c, d in root.dicts.items() if c in live}
    if root.dates and hasattr(out, "dates"):
        live = set(getattr(out, "column_names", ()) or root.dates)
        out.dates = tuple(sorted(root.dates & live))
    return out


def scan_read_stats(names: Sequence[str], tables: Dict[str, Any]
                    ) -> Tuple[int, int]:
    """(rows_read, bytes_read) across a plan's scan tables.

    Rows come from the holder's ``total_rows``; bytes from the ``repro.io``
    ingest provenance (``IngestInfo.bytes_read``) when the table was read
    from Parquet/CSV, 0 for tables built in memory."""
    rows = byts = 0
    for n in names:
        t = tables.get(n)
        if t is None:
            continue
        total = getattr(t, "total_rows", None)
        if callable(total):
            try:
                rows += int(total())
            except Exception:
                pass
        prov = getattr(t, "provenance", None)
        if prov is not None:
            byts += int(getattr(prov, "bytes_read", 0))
    return rows, byts


def default_scan_capacity(rows: int, parallelism: int) -> int:
    """Per-rank slots for a host table placed for in-core execution: 2x a
    balanced share of its rows (downstream shuffles inherit scan capacity,
    and hash placement skews), rounded up to a multiple of 1/16 of its
    power of two, which adds at most 1/8.  The rounding gives tables of
    nearly the same size, such as one dataset drawn from other seeds, one
    compiled program instead of one each."""
    need = 2 * -(-max(rows, 1) // parallelism)
    step = max(8, 1 << max(0, (need - 1).bit_length() - 4))
    return -(-need // step) * step


def _sum_stats(collected) -> Tuple[int, int, int]:
    """``collected``: (p, 3) arrays -> (rows sent, bytes sent, rows dropped)."""
    tot = np.zeros((3,), np.int64)
    for a in collected:
        tot += np.asarray(a).reshape(-1, 3).sum(axis=0)
    return int(tot[0]), int(tot[1]), int(tot[2])


def run_physical(pplan: PhysicalPlan, env, tables: Dict[str, Any],
                 mode: str = "bsp", collect_stats: bool = False,
                 shuffle_impl: str = "radix", a2a_chunks: int = 1,
                 morsel_rows: Optional[int] = None, tracer=None,
                 retries=None, timeout=None, overflow=None, faults=None,
                 scan_capacity: Optional[int] = None, adaptive=None,
                 **morsel_kw):
    """Execute a lowered plan against DistTables on a ``CylonEnv``.

    Returns a DistTable, or ``(DistTable, ExecStats)`` with
    ``collect_stats=True``.  ``shuffle_impl``/``a2a_chunks`` set the
    plan-wide shuffle defaults (per-node params override); both are part of
    the compile-cache key and recorded in the stats so benchmark output can
    attribute wins.

    ``tracer`` (a ``repro.obs.Tracer``) records per-dispatch stage spans —
    fenced with ``jax.block_until_ready`` so durations cover device
    execution — plus per-shuffle data-volume events when stats are
    collected.  Tracing is purely driver-side: it is NOT part of any
    compile-cache key and cannot change what gets compiled.  With
    ``collect_stats=True`` (tracer or not), ``ExecStats`` additionally
    carries ``wall_time_s`` / per-unit ``stage_times`` / per-label
    ``shuffle_records``, and the execution is folded into the process-global
    ``repro.obs.METRICS`` registry.

    ``morsel_rows`` switches to the out-of-core morsel executor
    (``planner.morsel.run_morsel``): the input is streamed through the
    compiled stage DAG in fixed-capacity morsels and the result is returned
    as a host-resident ``core.store.SpillTable``.  Extra ``morsel_kw``
    (``capacity_factor``, ``samples``, ``debug_overflow``) are forwarded.

    Fault tolerance (``repro.faults``, ``docs/fault_tolerance.md``):
    ``retries`` (None | int | ``RetryPolicy``) replays failed dispatch
    units with exponential backoff; ``timeout`` (seconds or a
    ``CancellationToken``) fences every dispatch and backoff sleep;
    ``overflow`` (``raise | warn | degrade``, default ``degrade``) decides
    what to do when capacity pressure drops rows — ``degrade`` re-executes
    out-of-core until every row fits (observable drops require
    ``collect_stats=True`` in-core; the morsel executor always counts).
    ``faults`` arms a deterministic ``FaultPlan`` (None consults
    ``REPRO_FAULTS``).  All of this is driver-side: with injection
    disabled, compile-cache keys are identical to a run without the
    harness.

    ``adaptive`` (None | bool | dict | ``AdaptiveConfig``) gates runtime
    skew mitigation (``repro.adapt``, ``docs/adaptive.md``): hot-key
    salting at shuffle boundaries here, splitter refresh + morsel
    autotuning in the out-of-core executor.  Default on; a run where no
    mitigation fires uses exactly the ``adaptive=False`` cache keys.
    """
    if morsel_rows is not None:
        from .morsel import run_morsel
        return run_morsel(pplan, env, tables, morsel_rows, mode=mode,
                          collect_stats=collect_stats,
                          shuffle_impl=shuffle_impl, a2a_chunks=a2a_chunks,
                          tracer=tracer, retries=retries, timeout=timeout,
                          overflow=overflow, faults=faults,
                          adaptive=adaptive, **morsel_kw)
    if morsel_kw:
        raise TypeError(f"unexpected kwargs without morsel_rows: "
                        f"{sorted(morsel_kw)}")
    from ..dataframe.shuffle import reset_overflow_warnings
    reset_overflow_warnings()
    fr = resolve_faults(faults)
    policy = resolve_retry(retries)
    token = resolve_token(timeout)
    ovf = resolve_overflow(overflow)
    counters = {"retries": 0}

    def _count_retry(attempt, exc):
        counters["retries"] += 1

    tr = tracer if tracer is not None else NULL_TRACER
    names = pplan.scan_names
    missing = [n for n in names if n not in tables]
    if missing:
        raise KeyError(f"plan scans missing from tables: {missing}")
    check_scan_dictionaries(pplan.order, tables)
    # host-resident ingest sources (repro.io SpillTables) scatter onto the
    # gang for in-core execution at ``default_scan_capacity``;
    # ``scan_capacity`` overrides.  Provenance rides along for the scan
    # read stats.
    from ..core.store import SpillTable
    from ..core.store import rescatter as _rescatter
    spills = {n: tables[n] for n in names
              if isinstance(tables[n], SpillTable)}
    if spills:
        def _cap(s):
            if scan_capacity is not None:
                return scan_capacity
            return default_scan_capacity(s.total_rows(), env.parallelism)
        def _place(n, s):
            # a table read from files names the date columns its ingest
            # decoded (``IngestInfo.dates``)
            dates = {"dates": ",".join(s.dates)} if s.dates else {}
            with tr.span(f"place:{n}", "transfer", to_p=env.parallelism,
                         rows=s.total_rows(), bytes=s.nbytes(), **dates):
                return _rescatter(s, env.parallelism, capacity=_cap(s),
                                  mesh=env.mesh)
        tables = {**tables, **{n: _place(n, s) for n, s in spills.items()}}
    root = pplan.root
    order = pplan.order
    fp = pplan.fingerprint
    shuffle_mode = "allgather" if mode == "amt" else "direct"
    # -- runtime skew detection (repro.adapt) -- driver-side sampling of
    # the (now device-resident) scan tables; an empty decision set leaves
    # every compile-cache key below exactly as adaptive=False would.
    # AMT shuffles are allgather-based (every rank sees all rows), which
    # is skew-immune by construction, so salting is direct-mode only.
    from ..adapt import resolve_adaptive
    from ..adapt.hotkeys import plan_salt_decisions, salt_cache_token
    acfg = resolve_adaptive(adaptive)
    adapt_events: List[Dict[str, Any]] = []
    salt = (plan_salt_decisions(order, tables, env.parallelism, acfg,
                                adapt_events, tracer=tr)
            if shuffle_mode == "direct" else {})
    eval_kw = dict(shuffle_impl=shuffle_impl, a2a_chunks=a2a_chunks,
                   salt=salt)
    hits0, misses0 = env.cache_hits, env.cache_misses
    timing = collect_stats or tr.enabled
    stage_times: List[Tuple[str, float]] = []
    t_query0 = time.perf_counter() if timing else 0.0

    def mk_stats(dispatches: int, pairs) -> ExecStats:
        with tr.span("readback", "readback") as sp:
            stats = _mk_stats(dispatches, pairs)
            sp.set(rows_shuffled=stats.rows_shuffled)
        return stats

    def _mk_stats(dispatches: int, pairs) -> ExecStats:
        rows, byts, dropped = _sum_stats([pr[1] for pr in pairs])
        rows_read, bytes_read = scan_read_stats(names, tables)
        stats = ExecStats(mode, pplan.num_stages, pplan.num_shuffles,
                          dispatches, rows, byts, pplan.shuffle_labels(),
                          pplan.fired,
                          shuffle_impl=("allgather" if mode == "amt"
                                        else shuffle_impl),
                          a2a_chunks=a2a_chunks, rows_dropped=dropped,
                          cache_hits=env.cache_hits - hits0,
                          cache_misses=env.cache_misses - misses0,
                          rows_read=rows_read, bytes_read=bytes_read,
                          wall_time_s=time.perf_counter() - t_query0,
                          stage_times=stage_times,
                          shuffle_records=build_shuffle_records(pairs),
                          retries=counters["retries"],
                          faults_injected=fr.injected,
                          adaptive=acfg.enabled,
                          salted_shuffles=len(salt),
                          adapt_events=list(adapt_events))
        record_exec(stats, fp, stats.wall_time_s)
        return stats

    def finish(result, stats):
        """Apply the overflow policy to a finished stats run: raise, warn
        once (attributed), or degrade — replay the whole plan out-of-core
        (drops are counted unconditionally there, and the morsel executor's
        own degrade loop shrinks morsels until everything fits), then
        re-scatter the spill back to a device-resident ``DistTable``."""
        if not stats.rows_dropped or ovf == OverflowPolicy.WARN:
            if stats.rows_dropped:
                warnings.warn(
                    f"capacity pressure dropped {stats.rows_dropped} rows "
                    f"({describe_drops(stats.shuffle_records)}) — raise "
                    f"capacities or use overflow='degrade'",
                    RuntimeWarning, stacklevel=3)
            return result, stats
        if ovf == OverflowPolicy.RAISE:
            raise CapacityOverflow(
                f"capacity pressure dropped {stats.rows_dropped} rows "
                f"({describe_drops(stats.shuffle_records)}); raise "
                f"bucket/out capacities or use overflow='degrade'")
        # degrade: the in-core capacities were wrong, so in-core replay
        # cannot help — stream the plan out-of-core instead, starting at
        # the scan tables' own per-rank capacity
        from ..core.store import rescatter
        from .morsel import run_morsel
        caps = [t.capacity for t in (tables[n] for n in names)
                if hasattr(t, "capacity")]
        m0 = max(caps) if caps else 128
        try:
            spill, d_stats = run_morsel(
                pplan, env, tables, m0, mode="bsp", collect_stats=True,
                shuffle_impl=shuffle_impl, a2a_chunks=a2a_chunks, tracer=tr,
                retries=policy, timeout=token,
                overflow=OverflowPolicy.DEGRADE, faults=fr, adaptive=acfg)
        except (ValueError, NotImplementedError) as e:
            raise CapacityOverflow(
                f"capacity pressure dropped {stats.rows_dropped} rows "
                f"({describe_drops(stats.shuffle_records)}) and the plan "
                f"cannot degrade to out-of-core execution ({e}); raise "
                f"capacities or handle overflow='raise'") from e
        out = attach_dictionaries(
            rescatter(spill, env.parallelism, mesh=env.mesh), root)
        d_stats.degraded += 1
        d_stats.retries += stats.retries
        d_stats.dispatches += stats.dispatches
        return out, d_stats

    if mode == "bsp":
        def prog(ctx, *local_tables):
            tmap = dict(zip(names, local_tables))
            values: Dict[int, Table] = {}
            stats: List[Tuple[str, jax.Array]] = []
            for node in order:
                values[node.nid] = eval_node(
                    node, ctx.comm, values, tmap, "direct",
                    stats if collect_stats else None, **eval_kw)
            out = values[root.nid]
            if collect_stats:
                return out, tuple(a for _, a in stats)
            return out

        with tr.span("stage:program", "stage", mode=mode,
                     stages=pplan.num_stages, dispatch=0) as sp:
            t0 = time.perf_counter() if timing else 0.0

            def dispatch():
                token.check("stage:program")
                fr.check("stage:launch", token=token, stage=0)
                if pplan.num_shuffles:
                    for c in range(max(1, a2a_chunks)):
                        fr.check("a2a:chunk", token=token, stage=0, chunk=c)
                return env.run(prog, *[tables[n] for n in names],
                               key=("bsp", fp, env.communicator_name,
                                    collect_stats, shuffle_impl, a2a_chunks)
                                   + salt_cache_token(salt), tracer=tr)

            res = run_with_retries(dispatch, policy=policy, token=token,
                                   tracer=tr, label="stage:program",
                                   on_retry=_count_retry)
            sp.set(compiled=env.cache_misses > misses0)
            out = res[0] if collect_stats else res
            if timing:
                with tr.span("wait", "wait"):
                    jax.block_until_ready(
                        (out.row_counts,) + (res[1] if collect_stats else ()))
                stage_times.append(("program", time.perf_counter() - t0))
            if collect_stats:
                # read the counters back (in ``readback``) before the
                # shuffle events reuse the host copies
                pairs = pair_stat_labels(plan_stat_labels(order, salt),
                                         res[1])
                stats = mk_stats(1, pairs)
                if tr.enabled:
                    emit_shuffle_events(tr, pairs, a2a_chunks)
        if collect_stats:
            return finish(attach_dictionaries(out, root), stats)
        return attach_dictionaries(out, root)

    if mode in ("bsp_staged", "amt"):
        values: Dict[int, Any] = {}
        collected: List[Tuple[str, Any]] = []
        dispatches = 0

        if mode == "bsp_staged":
            groups: Dict[int, List[LogicalNode]] = {}
            for node in order:
                groups.setdefault(pplan.stage_of[node.nid], []).append(node)
            units = [groups[s] for s in sorted(groups)]
            unit_names = [f"stage:{s}" for s in sorted(groups)]
        else:
            units = [[node] for node in order]
            unit_names = [f"op:{i}:{n.op}" for i, n in enumerate(order)]

        for uidx, unit in enumerate(units):
            unit_ids = {n.nid for n in unit}
            ext: List[LogicalNode] = []
            for n in unit:
                for i in n.inputs:
                    if i.nid not in unit_ids and i.nid not in {e.nid for e in ext}:
                        ext.append(i)
            scans = [n for n in unit if n.op == "scan"]
            later = set()
            for other in order:
                if other.nid in unit_ids:
                    continue
                later.update(i.nid for i in other.inputs)
            outs = [n for n in unit
                    if n.nid == root.nid or n.nid in later]

            def prog(ctx, *local_ins, _unit=unit, _ext=ext, _scans=scans,
                     _outs=outs):
                vals = {e.nid: t for e, t in zip(_ext, local_ins)}
                tmap = dict(zip([s.params["name"] for s in _scans],
                                local_ins[len(_ext):]))
                stats: List[Tuple[str, jax.Array]] = []
                for node in _unit:
                    vals[node.nid] = eval_node(
                        node, ctx.comm, vals, tmap, shuffle_mode,
                        stats if collect_stats else None, **eval_kw)
                out = tuple(vals[n.nid] for n in _outs)
                if collect_stats:
                    return out, tuple(a for _, a in stats)
                return out

            args = [values[e.nid] for e in ext] + \
                   [tables[s.params["name"]] for s in scans]
            with tr.span(unit_names[uidx], "stage", mode=mode,
                         dispatch=uidx,
                         ops=",".join(n.op for n in unit)) as sp:
                t0 = time.perf_counter() if timing else 0.0
                m0 = env.cache_misses
                has_comm = any(n.is_comm() for n in unit)

                unit_salt = salt_cache_token(salt, [n.nid for n in unit])

                def dispatch(_uidx=uidx, _args=args, _prog=prog,
                             _has_comm=has_comm, _usalt=unit_salt):
                    token.check(unit_names[_uidx])
                    fr.check("stage:launch", token=token, stage=_uidx)
                    if _has_comm:
                        for c in range(max(1, a2a_chunks)):
                            fr.check("a2a:chunk", token=token, stage=_uidx,
                                     chunk=c)
                    return env.run(
                        _prog, *_args,
                        key=(mode, fp, _uidx, env.communicator_name,
                             collect_stats, shuffle_impl, a2a_chunks)
                            + _usalt, tracer=tr)

                res = run_with_retries(dispatch, policy=policy, token=token,
                                       tracer=tr, label=unit_names[uidx],
                                       on_retry=_count_retry)
                sp.set(compiled=env.cache_misses > m0)
                if collect_stats:
                    out_tuple, unit_stats = res
                    unit_pairs = pair_stat_labels(
                        plan_stat_labels(unit, salt), unit_stats)
                    collected.extend(unit_pairs)
                else:
                    out_tuple = res
                dispatches += 1
                with tr.span("wait", "wait"):
                    for n, val in zip(outs, out_tuple):
                        jax.block_until_ready(val.row_counts)  # barrier
                        values[n.nid] = val
                    if timing and collect_stats:
                        jax.block_until_ready(unit_stats)
                if timing:
                    stage_times.append(
                        (unit_names[uidx], time.perf_counter() - t0))
                if collect_stats and tr.enabled:
                    emit_shuffle_events(tr, unit_pairs, a2a_chunks)

        result = attach_dictionaries(values[root.nid], root)
        if collect_stats:
            return finish(result, mk_stats(dispatches, collected))
        return result

    raise ValueError(f"unknown mode {mode!r}")

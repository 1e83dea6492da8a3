"""Stateful pseudo-BSP execution environment (the paper's §IV-A).

``CylonEnv`` is the JAX analogue of the paper's ``Cylon_env`` actor state: it
pins a partition of the device mesh, keeps the communicator alive across
operators, and caches compiled programs so repeated submissions pay zero
re-initialization cost (the paper's motivation for stateful actors).

Driver/shard boundary convention
--------------------------------
Driver-side distributed tables (``DistTable``) hold global arrays of shape
``(p * capacity, ...)`` sharded over the env axis plus per-rank row counts
``(p,)``.  Inside the shard_map region user functions see a plain
``dataframe.Table`` with local ``(capacity, ...)`` columns and a scalar
``row_count`` — i.e. the BSP/SPMD view, exactly like a Cylon worker owning
its partition.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..comm import Communicator, get_communicator
from ..dataframe.table import Table
from ..obs.trace import NULL_TRACER

AXIS = "df"  # default dataframe axis name


def _program_id(key: Any) -> str:
    """A short, stable name for a program cache key (trace attrs)."""
    return hashlib.sha1(repr(key).encode()).hexdigest()[:16]


def _text_source(env: "CylonEnv", key: Any) -> Callable[[], Optional[str]]:
    """``env.program_text(key)`` later, without keeping ``env`` alive."""
    ref = weakref.ref(env)

    def text() -> Optional[str]:
        e = ref()
        return e.program_text(key) if e is not None else None
    return text


def _spec_of(x: Any) -> Any:
    """What lowering a program needs of one argument: its shape, dtype and
    sharding, and never the array itself."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


def put_rows(rows: np.ndarray,
             mesh: Optional[jax.sharding.Mesh]) -> jax.Array:
    """Host rows ``(p * capacity, ...)`` -> a device array.

    With the gang's ``mesh``, rank r's block is copied straight to device
    r.  Without one (the gang is not known yet) the array lands on the
    default device and the first program that takes it moves the blocks,
    so that device briefly holds the whole table."""
    if mesh is None:
        return jnp.asarray(rows)
    return jax.device_put(rows, NamedSharding(mesh, P(mesh.axis_names[0])))


# ---------------------------------------------------------------------- #
# Driver-side distributed table
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class DistTable:
    """Global view of a distributed Table: (p*cap,) columns + (p,) counts.

    ``dictionaries`` maps each dictionary-encoded string column to its
    sorted dictionary (``dataframe.schema``); the device columns for those
    names hold int32 codes.  ``dates`` names the date columns, held as
    int32 days since 1970-01-01 and returned as ``datetime64[D]`` by
    ``to_numpy``.  Purely driver-side metadata — neither enters the
    compiled programs.
    """

    columns: Dict[str, jax.Array]
    row_counts: jax.Array  # (p,) int32
    capacity: int          # per-shard capacity
    dictionaries: Dict[str, Tuple[str, ...]] = \
        dataclasses.field(default_factory=dict)
    #: ``repro.io.IngestInfo`` when this table was read from Parquet/CSV
    #: (files, rows, source bytes); None for tables built in memory.
    #: Driver-side only — EXPLAIN ANALYZE attributes scan work from it.
    provenance: Optional[Any] = None
    dates: Tuple[str, ...] = ()

    @property
    def parallelism(self) -> int:
        return self.row_counts.shape[0]

    @property
    def column_names(self) -> Tuple[str, ...]:
        return tuple(sorted(self.columns))

    @classmethod
    def from_numpy(cls, data: Dict[str, np.ndarray], parallelism: int,
                   capacity: Optional[int] = None,
                   mesh: Optional[jax.sharding.Mesh] = None) -> "DistTable":
        """Block-distribute host rows over ``parallelism`` shards (placed
        on ``mesh``'s devices when given, see ``put_rows``).

        String columns (object / unicode numpy arrays) are dictionary-
        encoded host-side: the device gets int32 codes, the sorted
        dictionary lands in ``dictionaries``.  ``datetime64`` columns hold
        whole dates and become int32 days (named in ``dates``).  NaN / ``None`` values (or
        explicit ``__m_*`` companions) become validity-mask columns with
        canonical-zero data slots (``repro.nulls``).  An explicit
        ``capacity`` — including ``0`` — is honored verbatim and validated
        against the per-shard row count."""
        from ..dataframe.schema import encode_columns, encode_dates
        from ..nulls import extract_null_columns
        data = extract_null_columns(
            {k: np.asarray(v) for k, v in data.items()})
        data, dates = encode_dates(data)
        data, dicts = encode_columns(data)
        n = len(next(iter(data.values())))
        per = -(-n // parallelism)
        if capacity is None:
            capacity = max(8, -(-per // 8) * 8)
        if per > capacity:
            raise ValueError(f"rows/shard {per} exceeds capacity {capacity}")
        cols = {}
        counts = np.zeros((parallelism,), np.int32)
        for name, arr in data.items():
            arr = np.asarray(arr)
            buf = np.zeros((parallelism, capacity) + arr.shape[1:], arr.dtype)
            for r in range(parallelism):
                chunk = arr[r * per:(r + 1) * per]
                buf[r, :len(chunk)] = chunk
                counts[r] = len(chunk)
            cols[name] = put_rows(
                buf.reshape((parallelism * capacity,) + arr.shape[1:]), mesh)
        return cls(cols, put_rows(counts, mesh), capacity, dicts,
                   dates=dates)

    def to_numpy(self, decode: bool = True, nulls: str = "pandas"
                 ) -> Dict[str, np.ndarray]:
        """Gather valid rows from every shard (driver side, not jitted).

        ``decode=True`` (default) maps dictionary-encoded columns back to
        numpy string arrays and date columns to ``datetime64[D]``;
        ``decode=False`` returns the raw int32 codes and days.
        ``nulls="pandas"`` (default) re-materializes validity masks as
        NaN / ``None`` (consuming the ``__m_*`` columns);
        ``nulls="mask"`` returns the raw physical layout — canonical-zero
        data plus the bool mask columns — for bit-identity checks.
        """
        if nulls not in ("pandas", "mask"):
            raise ValueError(f"nulls must be 'pandas' or 'mask', got {nulls!r}")
        p, cap = self.parallelism, self.capacity
        counts = np.asarray(self.row_counts)
        out = {}
        for name, arr in self.columns.items():
            a = np.asarray(arr).reshape((p, cap) + arr.shape[1:])
            out[name] = np.concatenate([a[r, :counts[r]] for r in range(p)], axis=0)
        if decode:
            from ..dataframe.schema import decode_columns, decode_dates
            out = decode_dates(decode_columns(out, self.dictionaries),
                               self.dates)
        if nulls == "pandas":
            from ..nulls import apply_null_columns
            out = apply_null_columns(out)
        return out

    def total_rows(self) -> int:
        return int(np.asarray(self.row_counts).sum())


# ---------------------------------------------------------------------- #
# Morsel streaming: host spill -> fixed-capacity device batches
# ---------------------------------------------------------------------- #
class MorselSource:
    """Streams a host-resident table as fixed-capacity device ``DistTable``
    morsels (the out-of-core input path, ``docs/out_of_core.md``).

    ``source`` may be a ``core.store.SpillTable``, a device ``DistTable``
    (spilled first), or a dict of host numpy columns (block-distributed over
    ``parallelism`` ranks).  Every yielded morsel has the same per-rank
    capacity (``morsel_rows`` rounded up to 8), so one compiled program —
    a single structural-fingerprint cache entry — processes every morsel.

    Transfers are **double-buffered**: morsel ``m+1``'s host->device copy is
    enqueued (asynchronously, like a pinned-staging H2D DMA) before morsel
    ``m`` is handed to the consumer, overlapping transfer with compute.
    ``h2d_bytes`` accumulates the bytes shipped to devices.
    """

    def __init__(self, source, morsel_rows: int,
                 env: Optional["CylonEnv"] = None,
                 parallelism: Optional[int] = None, tracer=None,
                 faults=None, token=None):
        from .store import SpillTable  # deferred: store imports env
        if isinstance(source, DistTable):
            source = SpillTable.from_dist(source)
        elif isinstance(source, dict):
            p = parallelism or (env.parallelism if env is not None else 1)
            source = SpillTable.from_numpy(source, p)
        self.spill = source
        self.parallelism = source.parallelism
        if morsel_rows < 1:
            raise ValueError(f"morsel_rows must be >= 1, got {morsel_rows}")
        self.capacity = max(8, -(-int(morsel_rows) // 8) * 8)
        self.num_morsels = source.num_morsels(self.capacity)
        self.h2d_bytes = 0
        # one host-contiguous view per rank; a production backend would walk
        # the pinned chunks with a cursor instead of concatenating
        self._rank_cols = [source.rank_concat(r)
                           for r in range(self.parallelism)]
        self._names = source.column_names
        self._tracer = tracer if tracer is not None else NULL_TRACER
        # fault-injection hooks (repro.faults): the H2D staging of each
        # morsel is a registered hazard point; both default to no-ops
        if faults is None:
            from ..faults import NULL_FAULTS
            faults = NULL_FAULTS
        self._faults = faults
        self._token = token
        self._mesh = env.mesh if env is not None else None

    def _build(self, m: int) -> Optional[DistTable]:
        if m >= self.num_morsels:
            return None
        self._faults.check("transfer:h2d", token=self._token, morsel=m)
        b0 = self.h2d_bytes
        p, cap = self.parallelism, self.capacity
        lo, hi = m * cap, (m + 1) * cap
        counts = np.zeros((p,), np.int32)
        cols = {}
        for name in self._names:
            ref = self._rank_cols[0][name]
            buf = np.zeros((p, cap) + ref.shape[1:], ref.dtype)
            for r in range(p):
                piece = self._rank_cols[r][name][lo:hi]
                buf[r, :len(piece)] = piece
                counts[r] = len(piece)
            self.h2d_bytes += buf.nbytes
            cols[name] = put_rows(buf.reshape((p * cap,) + ref.shape[1:]),
                                  self._mesh)
        self.h2d_bytes += counts.nbytes
        self._tracer.instant(f"h2d:morsel[{m}]", "transfer", morsel=m,
                             bytes=self.h2d_bytes - b0)
        return DistTable(cols, put_rows(counts, self._mesh), cap,
                         dict(self.spill.dictionaries),
                         dates=self.spill.dates)

    def __iter__(self):
        nxt = self._build(0)
        m = 1
        while nxt is not None:
            cur = nxt
            nxt = self._build(m)  # prefetch: H2D for m enqueued before m-1 runs
            m += 1
            yield cur


# ---------------------------------------------------------------------- #
# The stateful environment
# ---------------------------------------------------------------------- #
class CylonEnv:
    """A pseudo-BSP environment pinned to a device partition.

    Parameters
    ----------
    devices:       explicit device list (a partition of the cluster, e.g. a
                   ``DevicePool`` lease), or None for all local devices.
    communicator:  registry name ("xla" | "ring" | "bruck").
    program_cache: a ``repro.serve.cache.ProgramCache`` to share compiled
                   programs with other envs (the serving scheduler passes
                   one per process so a freshly carved gang reuses every
                   program any earlier gang over the same devices built).
                   Default: a private cache, preserving single-env
                   semantics.

    Thread safety: ``run`` may be called from many threads.  Program
    lookups/builds go through the (locked, single-flight) program cache, so
    two threads racing the same key compile once; the per-env hit/miss
    counters are updated under a lock.
    """

    def __init__(self, devices: Optional[Sequence[jax.Device]] = None,
                 communicator: str = "xla", axis: str = AXIS,
                 program_cache: Optional[Any] = None):
        # deferred import: repro.serve.cache is standalone, but its package
        # __init__ must not be entered while core.env is still importing
        from ..serve.cache import ProgramCache
        self.devices = list(devices if devices is not None else jax.devices())
        self.axis = axis
        self.mesh = jax.sharding.Mesh(np.asarray(self.devices), (axis,))
        self.comm: Communicator = get_communicator(communicator, axis)
        self.communicator_name = communicator
        self.programs = (program_cache if program_cache is not None
                         else ProgramCache())
        #: compiled shard_map programs are mesh-bound, so the shared-cache
        #: key pins the gang's placement: platform + device ids + axis +
        #: communicator.  The DevicePool free-list hands out lowest ids
        #: first, so a released-and-recarved gang hits these entries.
        self._gang_key = (self.devices[0].platform if self.devices else "cpu",
                          tuple(d.id for d in self.devices), axis,
                          communicator)
        #: env-local memo in front of the shared cache (also the
        #: introspection surface tests use: ``set(env._cache)``)
        self._cache: Dict[Any, Callable] = {}
        self._lock = threading.Lock()
        #: compile-cache observability: a miss builds (traces + compiles) a
        #: program; a hit reuses one — whether it was compiled by this env
        #: or found in a shared program cache.  The morsel executor's
        #: per-morsel zero-recompile invariant is asserted against these
        #: counters.
        self.cache_hits = 0
        self.cache_misses = 0
        #: per key: the boundary arguments' shapes, dtypes and shardings at
        #: its first call here, and the program's HLO text once asked for
        #: (``program_text``)
        self._arg_specs: Dict[Any, Any] = {}
        self._hlo_text: Dict[Any, str] = {}

    @property
    def parallelism(self) -> int:
        return len(self.devices)

    def close(self) -> None:
        """Drop this env's local program memo (shared ``programs`` entries
        persist for the next gang carved over these devices)."""
        with self._lock:
            self._cache.clear()
            self._arg_specs.clear()
            self._hlo_text.clear()

    # ------------------------------------------------------------------ #
    # Table conversion at the shard_map boundary
    # ------------------------------------------------------------------ #
    def _in_spec_for(self, x):
        if isinstance(x, DistTable):
            return ({n: P(self.axis) for n in x.column_names}, P(self.axis))
        return P()  # replicated scalar/array argument

    @staticmethod
    def _to_boundary(x):
        if isinstance(x, DistTable):
            return ({n: x.columns[n] for n in x.column_names}, x.row_counts)
        return x

    # ------------------------------------------------------------------ #
    # Submission API (the paper's run_cylon / execute_cylon)
    # ------------------------------------------------------------------ #
    def run(self, fn: Callable, *args, static_kwargs: Optional[dict] = None,
            key: Any = None, tracer=NULL_TRACER):
        """Run ``fn(ctx, *local_args, **static_kwargs)`` under shard_map.

        ``fn`` receives this env's communicator-bearing context and local
        ``Table`` views of any ``DistTable`` args; it may return an arbitrary
        pytree of ``Table`` / arrays.  Returned Tables become ``DistTable``;
        returned arrays come back per-rank with a leading ``(p,)`` axis.
        Compiled programs are cached on the env (stateful reuse).

        ``tracer`` records a ``dispatch`` span until the call returns, with
        a ``compile`` span inside it on a cache miss (build and first,
        tracing call), and registers the program's HLO text with the
        tracer (``program_text``).
        """
        static_kwargs = static_kwargs or {}
        cache_key = key if key is not None else (
            fn, tuple(sorted(static_kwargs)),
            tuple(self._arg_sig(a) for a in args))
        boundary_args = tuple(self._to_boundary(a) for a in args)
        program = _program_id(cache_key) if tracer else ""
        with tracer.span("dispatch", "dispatch", program=program) as sp:
            with self._lock:
                compiled = self._cache.get(cache_key)
            sp.set(cache_hit=compiled is not None)
            if compiled is None:
                with self._lock:
                    self._arg_specs[cache_key] = jax.tree_util.tree_map(
                        _spec_of, boundary_args)
                with tracer.span("compile", "compile", program=program):
                    # shared-cache path: single-flight build keyed by
                    # (program, gang placement).  A hit here — the program
                    # was compiled by an earlier env over the same devices,
                    # or by a racing thread — counts as a hit, so a freshly
                    # carved gang that reuses every program reports
                    # cache_misses == 0.
                    compiled, built = self.programs.get_or_build(
                        (cache_key, self._gang_key),
                        lambda: self._build(fn, args, static_kwargs))
                    with self._lock:
                        self._cache[cache_key] = compiled
                        if built:
                            self.cache_misses += 1
                        else:
                            self.cache_hits += 1
                    out_tree, caps = compiled(*boundary_args)
            else:
                with self._lock:
                    self.cache_hits += 1
                out_tree, caps = compiled(*boundary_args)
            if tracer:
                tracer.programs[program] = _text_source(self, cache_key)
        return self._from_boundary(out_tree, caps)

    def program_text(self, key: Any) -> Optional[str]:
        """The optimized HLO text of the program cached under ``key``,
        lowered and compiled again from the argument shapes of its first
        call (the compile caches make that cheap); None for a key this env
        never built.  Memoized."""
        with self._lock:
            text = self._hlo_text.get(key)
            compiled = self._cache.get(key)
            specs = self._arg_specs.get(key)
        if text is None and compiled is not None and specs is not None:
            text = compiled.jitted.lower(*specs).compile().as_text()
            with self._lock:
                self._hlo_text[key] = text
        return text

    def _arg_sig(self, a):
        if isinstance(a, DistTable):
            return ("T", a.capacity,
                    tuple((n, str(a.columns[n].dtype), a.columns[n].shape[1:])
                          for n in a.column_names))
        x = jnp.asarray(a)
        return ("A", str(x.dtype), x.shape)

    def _build(self, fn, args, static_kwargs):
        env = self
        ctx = EnvContext(self.comm, self.axis)
        # capture only the arg KINDS: closing over `args` would pin the
        # first call's device arrays in the compile cache for the env's
        # lifetime (the morsel executor reuses programs across many inputs)
        is_dist = tuple(isinstance(a, DistTable) for a in args)

        def local_fn(*boundary_args):
            local_args = []
            for d, b in zip(is_dist, boundary_args):
                if d:
                    cols, counts = b
                    local_args.append(Table(dict(cols), counts[0]))
                else:
                    local_args.append(b)
            out = fn(ctx, *local_args, **static_kwargs)
            # normalize outputs: Table -> (cols, count[None]); array -> arr[None]
            def conv(x):
                if isinstance(x, Table):
                    return (dict(x.columns), x.row_count[None])
                x = jnp.asarray(x)
                return x[None]
            leaves, treedef = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, Table))
            return treedef, tuple(conv(l) for l in leaves)

        in_specs = tuple(self._in_spec_for(a) for a in args)

        treedef_box = {}

        # The function's name is the XLA module's name, which prefixes
        # JAX's persistent-cache key.  That key leaves out metadata, so
        # without the name an executable compiled before the operator
        # scopes (``planner.physical.eval_node``) existed would be loaded
        # with op names that carry none.
        def df_program(*bargs):
            treedef, converted = local_fn(*bargs)
            treedef_box["treedef"] = treedef
            return converted

        # out_specs is a tree *prefix*: every boundary leaf has a leading
        # per-shard axis (columns (cap,...), counts (1,), arrays (1,...)), so
        # a single P(axis) applies to the whole output tree and no separate
        # structure-discovery trace is needed.
        mapped = jax.jit(jax.shard_map(
            df_program, mesh=self.mesh, in_specs=in_specs,
            out_specs=P(self.axis), check_vma=False))

        # serialize the first invocation: tracing fills treedef_box, and
        # concurrent submitters sharing a just-built program must not race
        # the trace (jit retraces for new shapes stay lock-free)
        first_call = threading.Lock()

        def runner(*bargs):
            if "treedef" not in treedef_box:
                with first_call:
                    out = mapped(*bargs)  # traces & fills treedef_box
            else:
                out = mapped(*bargs)
            return (treedef_box["treedef"], out), None
        runner.jitted = mapped
        return runner

    def _from_boundary(self, out_tree, caps):
        treedef, leaves = out_tree

        def unconv(x):
            if isinstance(x, tuple):  # (cols, counts)
                cols, counts = x
                cap = next(iter(cols.values())).shape[0] // self.parallelism
                return DistTable(dict(cols), counts[:, 0] if counts.ndim > 1
                                 else counts, cap)
            return x
        leaves = [unconv(l) for l in leaves]
        return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclasses.dataclass
class EnvContext:
    """What user functions see inside the BSP region (the Cylon_env arg)."""

    comm: Communicator
    axis: str

    def rank(self):
        return jax.lax.axis_index(self.axis)

    def size(self):
        return jax.lax.axis_size(self.axis)


# ---------------------------------------------------------------------- #
# Device pool: resource partitioning for independent applications (§IV-A)
# ---------------------------------------------------------------------- #
class PoolExhausted(RuntimeError):
    """``DevicePool.reserve`` could not satisfy the request."""


class Lease(Sequence):
    """A disjoint device partition handed out by ``DevicePool.reserve``.

    Behaves as a sequence of devices (so ``CylonEnv(lease)`` and existing
    ``pool.reserve(n)[0]``-style code keep working) and carries its own
    ``release()``; it is also a context manager::

        with pool.reserve(2) as gang:
            env = CylonEnv(gang)
            ...
        # devices returned to the free list here
    """

    __slots__ = ("_pool", "_indices", "devices", "_released")

    def __init__(self, pool: "DevicePool", indices: Tuple[int, ...],
                 devices: Tuple[jax.Device, ...]):
        self._pool = pool
        self._indices = indices
        self.devices = devices
        self._released = False

    @property
    def indices(self) -> Tuple[int, ...]:
        return self._indices

    @property
    def released(self) -> bool:
        return self._released

    def release(self) -> None:
        """Return the partition to the pool (idempotent)."""
        self._pool.release(self)

    def __len__(self) -> int:
        return len(self.devices)

    def __getitem__(self, i):
        return self.devices[i]

    def __iter__(self):
        return iter(self.devices)

    def __enter__(self) -> "Lease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        state = "released" if self._released else "held"
        return f"<Lease devices={[d.id for d in self.devices]} {state}>"


class DevicePool:
    """Carves the device list into disjoint partitions (gang scheduling).

    A locked free-list replaces the old non-thread-safe bump pointer:
    ``reserve(n)`` hands out the ``n`` lowest-indexed free devices as a
    ``Lease`` that can be returned individually (``lease.release()`` /
    ``pool.release(lease)``) — two threads can never be handed overlapping
    partitions, and released partitions are re-carved lowest-ids-first so
    a re-carved gang matches its predecessor's placement (which is what
    lets the shared ``ProgramCache`` skip recompilation).  ``release_all``
    is kept for tests and whole-epoch resets.

    ``reserve(n, block=True)`` waits (optionally fenced by a
    ``CancellationToken``) until ``n`` devices free up — the serving
    scheduler's admission path.
    """

    def __init__(self, devices: Optional[Sequence[jax.Device]] = None):
        self._devices = list(devices if devices is not None else jax.devices())
        self._cond = threading.Condition(threading.Lock())
        self._free = list(range(len(self._devices)))  # kept sorted
        self._leases: Dict[int, Lease] = {}           # id(lease) -> lease

    @property
    def size(self) -> int:
        return len(self._devices)

    @property
    def available(self) -> int:
        with self._cond:
            return len(self._free)

    @property
    def devices(self) -> List[jax.Device]:
        return list(self._devices)

    def _try_reserve_locked(self, n: int) -> Optional[Lease]:
        if n > len(self._free):
            return None
        take = tuple(self._free[:n])
        del self._free[:n]
        lease = Lease(self, take, tuple(self._devices[i] for i in take))
        self._leases[id(lease)] = lease
        return lease

    def reserve(self, n: int, *, block: bool = False, token: Any = None,
                poll_s: float = 0.05) -> Lease:
        """Reserve the ``n`` lowest-indexed free devices.

        Non-blocking by default: raises ``PoolExhausted`` when fewer than
        ``n`` devices are free.  ``block=True`` waits for releases,
        polling ``token.check()`` (a ``repro.faults.CancellationToken``)
        so a queued reservation honors deadlines and cancellation.
        """
        if n < 1:
            raise ValueError(f"reserve needs n >= 1, got {n}")
        if n > len(self._devices):
            raise PoolExhausted(
                f"pool exhausted: want {n}, pool only has "
                f"{len(self._devices)} devices")
        with self._cond:
            while True:
                lease = self._try_reserve_locked(n)
                if lease is not None:
                    return lease
                if not block:
                    raise PoolExhausted(
                        f"pool exhausted: want {n}, have {len(self._free)} "
                        f"free of {len(self._devices)}")
                self._cond.wait(timeout=poll_s)
                if token is not None:
                    token.check("DevicePool.reserve")

    def try_reserve(self, n: int) -> Optional[Lease]:
        """``reserve`` that returns None instead of raising on exhaustion."""
        with self._cond:
            return self._try_reserve_locked(n) if n >= 1 else None

    def release(self, lease: Lease) -> None:
        """Return one lease's devices to the free list (idempotent)."""
        with self._cond:
            if lease._released or id(lease) not in self._leases:
                return
            lease._released = True
            del self._leases[id(lease)]
            self._free = sorted(self._free + list(lease._indices))
            self._cond.notify_all()

    def release_all(self) -> None:
        """Reclaim every outstanding lease (tests / epoch reset)."""
        with self._cond:
            for lease in list(self._leases.values()):
                lease._released = True
            self._leases.clear()
            self._free = list(range(len(self._devices)))
            self._cond.notify_all()

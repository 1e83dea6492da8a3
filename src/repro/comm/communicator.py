"""Modular communicator abstraction (the paper's §IV-B, adapted to JAX).

CylonFlow's second pillar is a *modularized communicator*: DDF communication
routines are written against an abstract interface, and concrete
high-performance backends (OpenMPI / Gloo / UCX+UCC in the paper) are plugged
in underneath.  On TPU the transport is fixed (ICI/XLA), but the *collective
schedule* is not — so the swappable dimension here is the algorithm:

  * ``xla``   — native ``jax.lax`` collectives (XLA's vendor-tuned schedules;
                the analogue of a tuned MPI implementation).
  * ``ring``  — (p-1)-step ring schedules built from ``ppermute``
                (bandwidth-optimal, latency O(p); the analogue of Gloo).
  * ``bruck`` — ⌈log₂p⌉-step Bruck all-to-all built from ``ppermute``
                (latency-optimal for small payloads; the analogue of UCC's
                algorithm selection).

All methods must be called *inside* a ``jax.shard_map`` region over ``axis``.

Block-major convention: ``all_to_all`` takes a local array of shape
``(p, m, ...)`` where block ``j`` is destined to rank ``j``; the output block
``j`` is the block received from rank ``j`` (MPI semantics).

NOTE ``ring``/``bruck`` unroll ``ppermute`` steps into the HLO; they are meant
for modest axis sizes (the paper benchmarks 1..512 processes; we benchmark
1..8 measured on CPU and 16 structurally).  The default for production meshes
is ``xla``.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, Type

import jax
import jax.numpy as jnp



class Communicator(abc.ABC):
    """Abstract DDF communicator bound to one mesh axis."""

    #: registry key, set by subclasses
    name: str = "abstract"

    def __init__(self, axis: str):
        self.axis = axis

    # ------------------------------------------------------------------ #
    # Introspection (valid inside shard_map only)
    # ------------------------------------------------------------------ #
    def size(self) -> int:
        return jax.lax.axis_size(self.axis)

    def rank(self):
        return jax.lax.axis_index(self.axis)

    # ------------------------------------------------------------------ #
    # Collective routines (the set identified in the paper §III-B2)
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def all_to_all(self, x: jax.Array) -> jax.Array:
        """x: (p, m, ...) block-major -> (p, m, ...); out[j] = block from rank j."""

    @abc.abstractmethod
    def all_gather(self, x: jax.Array) -> jax.Array:
        """x: (m, ...) -> (p, m, ...) stacked by rank."""

    @abc.abstractmethod
    def all_reduce(self, x: jax.Array) -> jax.Array:
        """Sum across the axis."""

    @abc.abstractmethod
    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        """x: (p, m, ...) block-major -> (m, ...): sum over ranks of block[rank]."""

    # Non-abstract conveniences -----------------------------------------#
    def all_to_all_chunked(self, x: jax.Array, chunks: int = 1) -> jax.Array:
        """All-to-all pipelined as ``chunks`` smaller collectives.

        ``x``: (p, m, ...) block-major; the capacity axis (axis 1) is split
        into ``chunks`` slices and one ``all_to_all`` is issued per slice
        (the AllToAllv chunking knob from tuned MPI: smaller in-flight
        messages, and independent collectives the scheduler may overlap
        with each other and with compute).  ``m`` is padded up to a
        multiple of ``chunks`` and the pad sliced back off.  Subclasses
        may override with a schedule-aware pipeline (see ``ring``).

        ``chunks`` must be a positive integer no larger than the capacity
        axis; invalid values raise ``ValueError`` up front (naming the
        axis and chunk count) instead of failing deep inside a reshape.
        """
        x, m, csz = self._chunk_split(x, chunks)
        if csz is None:
            return self.all_to_all(x)
        outs = [self.all_to_all(
            jax.lax.slice_in_dim(x, c * csz, (c + 1) * csz, axis=1))
            for c in range(chunks)]
        return jnp.concatenate(outs, axis=1)[:, :m]

    def _chunk_split(self, x: jax.Array, chunks: int):
        """Pad axis 1 to a multiple of ``chunks``; (x, orig_m, chunk_size).

        ``chunk_size`` is None when chunking degenerates to one collective.
        Validates ``chunks`` up front: a zero/negative/non-integer count or
        more chunks than capacity-axis rows would otherwise surface as an
        opaque division/reshape error deep inside the collective.
        """
        if x.ndim < 2:
            raise ValueError(
                f"all_to_all_chunked needs a (p, m, ...) block-major array "
                f"with a capacity axis to chunk; got shape {x.shape}")
        m = x.shape[1]
        if not isinstance(chunks, int) or isinstance(chunks, bool) \
                or chunks < 1:
            raise ValueError(
                f"all_to_all_chunked: chunks must be a positive int, got "
                f"{chunks!r} (capacity axis 1 has {m} rows)")
        if chunks > max(m, 1):
            raise ValueError(
                f"all_to_all_chunked: cannot split the capacity axis "
                f"(axis 1, {m} rows) into {chunks} chunks — chunks must "
                f"be <= rows; rows not divisible by chunks are padded")
        if chunks <= 1:
            return x, x.shape[1], None
        mp = -(-m // chunks) * chunks
        if mp != m:
            pad = jnp.zeros((x.shape[0], mp - m) + x.shape[2:], x.dtype)
            x = jnp.concatenate([x, pad], axis=1)
        return x, m, mp // chunks

    def broadcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        """Broadcast rank ``root``'s value to every rank."""
        sel = jnp.where(self.rank() == root, 1, 0).astype(x.dtype)
        return self.all_reduce(x * sel)

    def all_reduce_max(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmax(x, self.axis)

    def all_reduce_min(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmin(x, self.axis)

    def exchange_counts(self, counts: jax.Array) -> jax.Array:
        """AllToAll of per-destination row counts (the AllToAllv counts round).

        counts: (p,) int32, counts[j] = rows this rank will send to rank j.
        Returns (p,) int32, recv[j] = rows rank j will send to this rank.
        """
        return self.all_to_all(counts.reshape(-1, 1))[:, 0]

    def ppermute(self, x: jax.Array, perm) -> jax.Array:
        return jax.lax.ppermute(x, self.axis, perm)


# ---------------------------------------------------------------------- #
# Registry
# ---------------------------------------------------------------------- #
_REGISTRY: Dict[str, Type[Communicator]] = {}


def register_communicator(cls: Type[Communicator]) -> Type[Communicator]:
    _REGISTRY[cls.name] = cls
    return cls


def get_communicator(name: str, axis: str) -> Communicator:
    """Instantiate a communicator by registry name, bound to ``axis``."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown communicator {name!r}; available: {sorted(_REGISTRY)}"
        ) from None
    return cls(axis)


def available_communicators():
    return sorted(_REGISTRY)

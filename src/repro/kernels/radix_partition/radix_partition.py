"""Pallas TPU kernel: stable radix partition (shuffle's bucketize hot spot).

Computes, for every row's destination bucket, its stable rank *within* that
bucket plus the global bucket histogram — exactly what the capacity-based
shuffle needs to scatter rows into its ``(p, bucket_cap)`` send buffer
(`repro.dataframe.shuffle`).  A GPU implementation would use atomics; the
TPU formulation exploits the *sequential* grid: a VMEM scratch carries the
running per-bucket counts across row blocks (a scan over blocks), and ranks
inside a block come from an exclusive cumsum over the block's one-hot
destination matrix — all VPU/MXU-friendly dense ops.

  rank[i]  = running[dest_i] + (# earlier rows in this block with dest_i)
  hist     = running counts after the last block

Mosaic has no lowering for ``cumsum``, so the in-block exclusive prefix is
a matmul with a strictly-lower-triangular 0/1 matrix: the 0/1 operands are
exact in bf16 and the counts (at most R) exact in the f32 accumulator.

Layout: the one-hot block (R × NB) sits in VMEM, but the ``(n, 1)`` int32
input and rank arrays are padded to 128 lanes in HBM — 512 bytes per row.
At 2^20 rows that is a 1 GiB temporary against a 4 MiB input, and at 2^24
rows the program no longer fits a 16 GB v5e.  ``ops.radix_partition``
therefore sends the shuffle to the XLA path; this kernel stays as
``impl="pallas"`` until a lane-dense layout is measured against it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import default_interpret


def _kernel(dest_ref, rank_ref, hist_ref, running_ref):
    rb = pl.program_id(0)

    @pl.when(rb == 0)
    def _init():
        running_ref[...] = jnp.zeros_like(running_ref)

    dest = dest_ref[...]                      # (R, 1) int32
    r, nb = dest.shape[0], running_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, nb), 1)
    onehot = (cols == dest).astype(jnp.int32)  # (R, NB)
    # stable rank within block: exclusive prefix sum down the rows, as
    # strictly-lower-triangular (R, R) @ one-hot (R, NB) on the MXU
    row = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    lower = (col < row).astype(jnp.bfloat16)
    excl = jnp.dot(lower, onehot.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    in_block = jnp.sum(excl * onehot, axis=1, keepdims=True)       # (R, 1)
    carried = jnp.sum(running_ref[...] * onehot, axis=1, keepdims=True)
    rank_ref[...] = carried + in_block
    running_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)

    @pl.when(rb == pl.num_programs(0) - 1)
    def _fin():
        hist_ref[...] = running_ref[...]


@functools.partial(jax.jit, static_argnames=("num_buckets", "block_rows",
                                             "interpret"))
def radix_partition_pallas(dest: jax.Array, num_buckets: int,
                           block_rows: int = 256,
                           interpret: Optional[bool] = None):
    """dest: (n,) int32 in [0, num_buckets) -> (ranks (n,), hist (num_buckets,)).

    n must be a multiple of block_rows and num_buckets of 128 (ops.py pads;
    padded rows use bucket num_buckets-1 and their ranks are discarded).
    ``interpret=None`` selects from the backend: the real Mosaic kernel on
    TPU, interpret mode elsewhere (it used to default to ``interpret=True``,
    silently skipping the compiled kernel even on TPU).
    """
    interpret = default_interpret(interpret)
    n = dest.shape[0]
    assert n % block_rows == 0 and num_buckets % 128 == 0
    grid = (n // block_rows,)
    ranks, hist = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, 1), lambda rb: (rb, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda rb: (rb, 0)),
            pl.BlockSpec((1, num_buckets), lambda rb: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, num_buckets), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, num_buckets), jnp.int32)],
        interpret=interpret,
    )(dest.reshape(-1, 1))
    return ranks[:, 0], hist[0]

"""Compile the shuffle's kernels and programs for a described TPU v5e.

Nothing runs: each test lowers and compiles at the engine's real sizes for
a ``v5e:2x2`` topology that is described, not attached, so the TPU
compiler refuses here what it would refuse on the chip (a primitive with
no Mosaic lowering, a program that does not fit HBM).  The topology is
described inside a module-scoped fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.comm import get_communicator
from repro.dataframe.shuffle import shuffle
from repro.dataframe.table import Table
from repro.kernels.radix_partition import (radix_partition_pallas,
                                           radix_partition_xla)
from repro.kernels.segmented_reduce.segmented_reduce import \
    segmented_sum_pallas

AXIS = "df"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_radix_partition_pallas_compiles(one_chip):
    dest = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=one_chip)
    compiled = radix_partition_pallas.lower(
        dest, num_buckets=128, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_radix_partition_xla_compiles_at_2p24(one_chip):
    dest = jax.ShapeDtypeStruct((1 << 24,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(radix_partition_xla, static_argnums=1).lower(
        dest, 2).compile()
    assert compiled.memory_analysis() is not None


def test_segmented_sum_pallas_compiles(one_chip):
    n = 1 << 20
    seg = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=one_chip)
    compiled = segmented_sum_pallas.lower(
        seg, vals, num_segments=1024, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_shuffle_compiles_at_2p24_rows_per_chip(topo, chips):
    """The dataframe shuffle (hash -> radix partition -> all-to-all ->
    compaction) at a 2^24-row capacity per chip, int32 key + f32 payload."""
    mesh = Mesh(topo.devices[:chips], (AXIS,))
    comm = get_communicator("xla", AXIS)

    def body(k, v, n):
        out, stats = shuffle(Table({"k": k, "v": v}, n[0]), comm,
                             key_cols=["k"])
        return (out.columns["k"], out.columns["v"], out.row_count[None],
                stats.send_dropped[None])

    prog = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(AXIS),
                                 out_specs=P(AXIS), check_vma=False))
    rows = NamedSharding(mesh, P(AXIS))
    cap = 1 << 24
    compiled = prog.lower(
        jax.ShapeDtypeStruct((chips * cap,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((chips * cap,), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((chips,), jnp.int32, sharding=rows)).compile()
    if chips > 1:
        assert "all-to-all" in compiled.as_text()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Communicator parity: ring/bruck vs xla for every collective, p in {6, 8}
(6 exercises the non-power-of-two ring fallback in bruck)."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm import get_communicator

rng = np.random.default_rng(0)

for p in (6, 8):
    mesh = Mesh(np.asarray(jax.devices()[:p]), ("df",))
    x_blocks = jnp.asarray(rng.standard_normal((p, p, 4, 3)), jnp.float32)
    x_flat = jnp.asarray(rng.standard_normal((p, 10)), jnp.float32)

    def run(comm_name, method, x):
        comm = get_communicator(comm_name, "df")

        def body(xl):
            return getattr(comm, method)(xl[0])[None]
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("df"), out_specs=P("df"),
            check_vma=False))(x)

    for method, x in (("all_to_all", x_blocks), ("all_gather", x_flat),
                      ("all_reduce", x_flat), ("reduce_scatter", x_blocks)):
        ref = run("xla", method, x)
        for name in ("ring", "bruck"):
            got = run(name, method, x)
            assert np.allclose(got, ref, atol=1e-5), (p, name, method)

    # chunked all-to-all == monolithic, every backend, including a chunk
    # count (3) that does not divide the capacity axis (4 -> pad+slice)
    ref = run("xla", "all_to_all", x_blocks)
    for name in ("xla", "ring", "bruck"):
        comm = get_communicator(name, "df")
        for chunks in (1, 2, 3, 4):
            got = jax.jit(jax.shard_map(
                lambda xl, c=comm, k=chunks: c.all_to_all_chunked(
                    xl[0], chunks=k)[None],
                mesh=mesh, in_specs=P("df"), out_specs=P("df"),
                check_vma=False))(x_blocks)
            assert np.allclose(got, ref, atol=1e-5), (p, name, chunks)
    # broadcast + counts exchange
    for name in ("xla", "ring", "bruck"):
        comm = get_communicator(name, "df")
        out = jax.jit(jax.shard_map(
            lambda xl: comm.broadcast(xl[0], root=2)[None],
            mesh=mesh, in_specs=P("df"), out_specs=P("df"),
            check_vma=False))(x_flat)
        assert np.allclose(np.asarray(out),
                           np.asarray(x_flat)[2][None].repeat(p, 0),
                           atol=1e-6), (p, name, "broadcast")

print("comm_collectives OK")

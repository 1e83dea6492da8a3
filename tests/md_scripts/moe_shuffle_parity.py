import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""MoE dispatch parity: the shard_map dataframe-shuffle path must equal the
grouped GSPMD path (ample capacity) on a (4 data x 2 model) mesh — forward
values, aux loss, and gradients."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_local_mesh, rules_for_mesh
from repro.models.config import ModelConfig, MoEConfig
from repro.models.moe import moe_apply_grouped, moe_apply_shuffle, moe_init

cfg = ModelConfig(
    name="parity-moe", family="moe", num_layers=1, d_model=64,
    num_heads=4, num_kv_heads=4, d_ff=96, vocab_size=256,
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=96, num_shared=1,
                  capacity_factor=8.0))

mesh = make_local_mesh(8, model=2)
rules = rules_for_mesh(mesh)
rng = np.random.default_rng(0)
params = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jnp.asarray(rng.standard_normal((8, 32, 64)), jnp.float32)

with jax.set_mesh(mesh):
    def f_shuffle(p, xx):
        y, aux = moe_apply_shuffle(p, xx, cfg, rules)
        return y, aux

    def f_grouped(p, xx):
        y, aux = moe_apply_grouped(p, xx, cfg, rules)
        return y, aux

    y1, a1 = jax.jit(f_shuffle)(params, x)
    y2, a2 = jax.jit(f_grouped)(params, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-4)

    # gradient parity through both dispatch paths
    def loss_s(p, xx):
        y, aux = moe_apply_shuffle(p, xx, cfg, rules)
        return jnp.sum(y ** 2) + aux

    def loss_g(p, xx):
        y, aux = moe_apply_grouped(p, xx, cfg, rules)
        return jnp.sum(y ** 2) + aux

    g1 = jax.jit(jax.grad(loss_s))(params, x)
    g2 = jax.jit(jax.grad(loss_g))(params, x)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)

    # modular communicator (paper §IV-B) on the dispatch: ring/bruck
    # schedules must produce identical results to the native xla path
    import dataclasses
    for name in ("ring", "bruck"):
        cfg_c = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, communicator=name))
        yc, ac = jax.jit(
            lambda p, xx: moe_apply_shuffle(p, xx, cfg_c, rules))(params, x)
        np.testing.assert_allclose(np.asarray(yc), np.asarray(y1),
                                   atol=2e-4, rtol=1e-3)

print(f"moe_shuffle_parity OK (y diff {float(jnp.abs(y1 - y2).max()):.2e}, "
      f"ring/bruck schedules verified)")

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Sharded-vs-unsharded training parity on a (4, 2) data x model mesh:
identical params + batch must give identical loss and matching updates."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.launch.mesh import make_local_mesh, rules_for_mesh
from repro.models.layers import NO_SHARDING
from repro.train import AdamWConfig, init_train_state, make_train_step
from repro.train.step import state_specs

rng = np.random.default_rng(0)

for arch in ("llama3.2-3b", "olmoe-1b-7b", "mamba2-780m", "jamba-v0.1-52b",
             "deepseek-v2-lite-16b"):
    cfg = get_smoke_config(arch)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32),
    }
    opt = AdamWConfig(warmup_steps=1, total_steps=4)
    state = init_train_state(jax.random.PRNGKey(0), cfg, jnp.float32)

    # single-device reference
    s1, m1 = jax.jit(make_train_step(cfg, opt, NO_SHARDING, ce_chunk=16))(
        state, batch)

    # sharded
    mesh = make_local_mesh(8, model=2)
    rules = rules_for_mesh(mesh)
    specs = state_specs(cfg, rules)
    sharded = jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        state, specs, is_leaf=lambda x: isinstance(x, P))
    with jax.set_mesh(mesh):
        s2, m2 = jax.jit(make_train_step(cfg, opt, rules, ce_chunk=16))(
            sharded, batch)

    l1, l2 = float(m1["loss"]), float(m2["loss"])
    assert np.isclose(l1, l2, rtol=2e-3), (arch, l1, l2)
    g1, g2 = float(m1["grad_norm"]), float(m2["grad_norm"])
    assert np.isclose(g1, g2, rtol=2e-2), (arch, g1, g2)
    # one representative param leaf identical after update
    p1 = jax.tree_util.tree_leaves(s1["params"])[0]
    p2 = jax.tree_util.tree_leaves(s2["params"])[0]
    assert np.allclose(np.asarray(p1), np.asarray(p2), atol=2e-4), arch
    print(f"{arch}: sharded loss {l2:.4f} == single {l1:.4f}")

print("sharded_train OK")

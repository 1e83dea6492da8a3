import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Elastic checkpoint/restart: save from an 8-device (4x2) mesh, restore
onto a 4-device (2x2) mesh (simulated node loss), losses keep decreasing."""

import tempfile

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.launch.mesh import rules_for_mesh
from repro.train import AdamWConfig, init_train_state, make_train_step, \
    restore, save
from repro.train.step import state_specs

cfg = get_smoke_config("llama3.2-3b")
rng = np.random.default_rng(0)
opt = AdamWConfig(warmup_steps=2, total_steps=20)


def mk_batch():
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                                  jnp.int32)}


def put(state, mesh, rules):
    specs = state_specs(cfg, rules)
    return jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        state, specs, is_leaf=lambda x: isinstance(x, P))


# phase 1: 8 devices (4x2)
mesh8 = jax.make_mesh((4, 2), ("data", "model"))
rules8 = rules_for_mesh(mesh8)
state = put(init_train_state(jax.random.PRNGKey(0), cfg, jnp.float32),
            mesh8, rules8)
step8 = jax.jit(make_train_step(cfg, opt, rules8, ce_chunk=16))
losses = []
with jax.set_mesh(mesh8):
    for _ in range(6):
        state, m = step8(state, mk_batch())
        losses.append(float(m["loss"]))

tmp = tempfile.mkdtemp()
save(f"{tmp}/ckpt_6", state, 6)

# phase 2: "node failure" -> restart on 4 devices (2x2)
mesh4 = jax.make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
rules4 = rules_for_mesh(mesh4)
specs4 = state_specs(cfg, rules4)
shardings4 = jax.tree_util.tree_map(
    lambda sp: NamedSharding(mesh4, sp), specs4,
    is_leaf=lambda x: isinstance(x, P))
like = init_train_state(jax.random.PRNGKey(0), cfg, jnp.float32)
state4 = restore(f"{tmp}/ckpt_6", like, shardings4)
assert int(state4["opt"]["step"]) == 6

step4 = jax.jit(make_train_step(cfg, opt, rules4, ce_chunk=16))
with jax.set_mesh(mesh4):
    for _ in range(6):
        state4, m = step4(state4, mk_batch())
        losses.append(float(m["loss"]))

assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
print(f"elastic_checkpoint OK: losses {losses[0]:.3f} -> {losses[-1]:.3f} "
      f"across a 8->4 device restart")

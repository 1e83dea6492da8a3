import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""End-to-end §IV-C: DDF preprocessing application -> CylonStore ->
distributed training application (~100M-param llama-family model).

Two "applications" on separate gang reservations of the same pool:
  1. preprocessing: dedup -> quality filter -> weights join -> sample-based
     balance, producing the training corpus into the CylonStore,
  2. training: gets the corpus (repartitioning to its own parallelism),
     packs batches, and trains a ~100M-param model for a few hundred steps
     under FSDP+SP sharding with checkpointing.

  PYTHONPATH=src python examples/train_e2e.py [--steps 300]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import CylonExecutor, CylonStore, DevicePool
from repro.data import (CorpusConfig, batches_from_table, preprocess,
                        source_weights, synth_corpus)
from repro.launch.mesh import make_local_mesh, rules_for_mesh
from repro.models.config import ModelConfig
from repro.train import AdamWConfig, init_train_state, make_train_step
from repro.train.step import state_specs

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=256)
args = ap.parse_args()

# ~100M params: 8L x 768d, llama-style
CFG = ModelConfig(name="llama-100m", family="dense", num_layers=8,
                  d_model=768, num_heads=12, num_kv_heads=4, d_ff=2048,
                  vocab_size=32000, head_dim=64, tie_embeddings=True)

pool = DevicePool()
prep_gang = CylonExecutor(parallelism=4, pool=pool)
store = CylonStore()

t0 = time.time()
corpus = synth_corpus(CorpusConfig(num_docs=8192, payload_tokens=args.seq,
                                   vocab_size=CFG.vocab_size),
                      prep_gang.parallelism)
weights = source_weights(8, prep_gang.parallelism)
preprocess(prep_gang, corpus, weights, store=store)
print(f"[prep] gang={prep_gang.parallelism} done in {time.time() - t0:.1f}s")

# training application on the full mesh (8 devices, data x model = 4 x 2)
table = store.get("train_corpus", target_parallelism=8)
mesh = make_local_mesh(8, model=2)
rules = rules_for_mesh(mesh)
batches = batches_from_table(table, args.batch, args.seq)

opt = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
state = init_train_state(jax.random.PRNGKey(0), CFG, jnp.bfloat16)
specs = state_specs(CFG, rules)
state = jax.tree_util.tree_map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs,
    is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P))

n_params = sum(x.size for x in jax.tree_util.tree_leaves(state["params"]))
print(f"[train] params={n_params / 1e6:.1f}M mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

step_fn = jax.jit(make_train_step(CFG, opt, rules, ce_chunk=128))
losses = []
with jax.set_mesh(mesh):
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {losses[-1]:8.4f} "
                  f"({time.time() - t0:.2f}s/step)", flush=True)

first, last = np.mean(losses[:10]), np.mean(losses[-10:])
print(f"[result] loss {first:.3f} -> {last:.3f} "
      f"({'OK: improved' if last < first - 0.5 else 'WARN: flat'})")

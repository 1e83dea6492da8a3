"""End-to-end training driver (CPU-scale models, production-shaped code).

Wires every substrate layer together: DDF data pipeline (on a CylonExecutor
gang) → CylonStore hand-off → sharded train step → async checkpointing with
``--resume`` elastic restart.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_config, get_smoke_config
from ..core import CylonExecutor, CylonStore, DevicePool
from ..data import (CorpusConfig, batches_from_table, preprocess,
                    source_weights, synth_corpus)
from ..models.layers import NO_SHARDING
from ..train import (AdamWConfig, AsyncCheckpointer, init_train_state,
                     latest_step, make_train_step, restore)
from ..train.step import batch_specs, state_specs
from .mesh import make_local_mesh, rules_for_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data-parallelism", type=int, default=None,
                    help="gang size for the DDF preprocessing application")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("train driver covers token-LM archs; see the "
                         "smoke tests for vlm/audio steps")

    n_dev = len(jax.devices())
    mesh = make_local_mesh(n_dev, model=args.model_axis)
    rules = rules_for_mesh(mesh) if n_dev > 1 else NO_SHARDING

    # ---- DDF preprocessing application (paper §IV-C) -------------------- #
    pool = DevicePool()
    gang = CylonExecutor(parallelism=args.data_parallelism or n_dev,
                         pool=pool)
    store = CylonStore()
    corpus = synth_corpus(CorpusConfig(num_docs=2048, payload_tokens=args.seq,
                                       vocab_size=cfg.vocab_size,
                                       seed=args.seed),
                          gang.parallelism)
    weights = source_weights(8, gang.parallelism)
    t0 = time.time()
    preprocess(gang, corpus, weights, store=store)
    table = store.get("train_corpus")
    print(f"[data] preprocessed {table.total_rows()} docs "
          f"on gang={gang.parallelism} in {time.time() - t0:.2f}s")
    batches = batches_from_table(table, args.batch, args.seq, seed=args.seed)

    # ---- training application ------------------------------------------ #
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    key = jax.random.PRNGKey(args.seed)
    state = init_train_state(key, cfg, jnp.float32)
    start_step = 0
    ckpt = AsyncCheckpointer()
    shardings = None
    if n_dev > 1:
        sp = state_specs(cfg, rules)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), sp,
            is_leaf=lambda x: isinstance(x, P))
        state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), state, shardings)

    if args.resume and args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state = restore(f"{args.ckpt_dir}/ckpt_{last}", state, shardings)
            start_step = last
            print(f"[ckpt] resumed from step {last} "
                  f"(mesh-elastic restore onto {n_dev} devices)")

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, rules, ce_chunk=64))
    losses = []
    with jax.set_mesh(mesh) if n_dev > 1 else _nullcontext():
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"dt {time.time() - t0:.3f}s", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(f"{args.ckpt_dir}/ckpt_{step + 1}", state,
                          step + 1)
    ckpt.wait()
    if len(losses) > 10:
        a, b = np.mean(losses[:5]), np.mean(losses[-5:])
        print(f"[loss] first5={a:.3f} last5={b:.3f} "
              f"({'improved' if b < a else 'NOT improved'})")


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()

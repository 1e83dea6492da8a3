import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init, and the production meshes need 512
placeholder host devices.  (Do not import this module from tests/benches;
they want the real 1-device CPU backend.)

For every eligible cell this driver:
  1. builds the step function (train_step / prefill / decode_step) and
     ``ShapeDtypeStruct`` stand-ins for state + inputs (zero allocation),
  2. ``jax.jit(...).lower(...)`` with explicit NamedSharding in/out trees
     on the production mesh (16×16 single pod, 2×16×16 multi-pod),
  3. ``.compile()`` — proving the sharding is coherent and the collectives
     lower,
  4. records ``memory_analysis()`` / ``cost_analysis()`` / the parsed
     collective schedule + roofline terms to a JSON under
     ``experiments/dryrun/<mesh>/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""

import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import flags
from ..configs import ARCHS, get_config
from ..models import transformer
from ..models.config import SHAPES
from ..train import AdamWConfig, make_train_step
from ..train.optim import init_opt_state, opt_specs
from ..train.step import batch_specs as batch_spec_tree, state_specs
from . import roofline
from .mesh import make_production_mesh, rules_for_mesh, serve_rules_for_mesh
from .shapes import (Cell, all_cells, cell, decode_token_specs,
                     prefill_batch_specs, train_batch_specs)


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _params_shapes(cfg):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(partial(transformer.init_params, cfg=cfg), key)


def build_lowered(c: Cell, mesh, ce_chunk: int = 512,
                  rules_override=None, extra: Optional[Dict] = None,
                  cfg_override=None):
    """Returns (lowered, meta) for one cell on ``mesh``."""
    cfg = cfg_override or get_config(c.arch)
    if rules_override is not None:
        rules = rules_override
    elif c.kind == "decode":
        rules = serve_rules_for_mesh(mesh)   # pure TP: no per-token gathers
    else:
        rules = rules_for_mesh(mesh)
    # batch=1 long-context cells cannot shard the batch dim; the KV cache
    # sequence sharding over 'model' carries the parallelism instead.
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_axes = rules.batch if isinstance(rules.batch, tuple) else (rules.batch,)
    divisor = 1
    for a in b_axes:
        divisor *= sizes.get(a, 1) if a else 1
    if c.global_batch % divisor:
        rules = dataclasses.replace(rules, batch=None)
    extra = extra or {}

    if c.kind == "train":
        step = make_train_step(cfg, AdamWConfig(), rules,
                               ce_chunk=ce_chunk, **extra)
        params_sh = _params_shapes(cfg)
        state_shapes = {"params": params_sh,
                        "opt": jax.eval_shape(init_opt_state, params_sh)}
        batch_shapes = train_batch_specs(cfg, c.global_batch, c.seq_len)
        st_specs = state_specs(cfg, rules)
        b_specs = batch_spec_tree(cfg, rules)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(_named(mesh, st_specs), _named(mesh, b_specs)),
                out_shardings=(_named(mesh, st_specs), None),
            ).lower(state_shapes, batch_shapes)
        return lowered, {"cfg": cfg}

    params_sh = _params_shapes(cfg)
    p_specs = transformer.param_specs(cfg, rules)
    c_specs = transformer.cache_specs(cfg, rules)
    cache_shapes = jax.eval_shape(
        partial(transformer.init_caches, cfg, c.global_batch, c.seq_len))

    if c.kind == "prefill":
        def fn(params, batch):
            return transformer.prefill(params, cfg, batch, c.seq_len, rules,
                                       **extra)
        batch_shapes = prefill_batch_specs(cfg, c.global_batch, c.seq_len)
        b_specs = {k: v for k, v in batch_spec_tree(cfg, rules).items()
                   if k in batch_shapes}
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                fn,
                in_shardings=(_named(mesh, p_specs), _named(mesh, b_specs)),
                out_shardings=(None, _named(mesh, c_specs)),
            ).lower(params_sh, batch_shapes)
        return lowered, {"cfg": cfg}

    # decode
    def fn(params, caches, tokens, pos):
        return transformer.decode_step(params, cfg, caches, tokens, pos,
                                       rules)
    tok_sh, pos_sh = decode_token_specs(cfg, c.global_batch)
    tok_spec = P(rules.batch, None, None) if cfg.family == "audio" \
        else P(rules.batch, None)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            fn,
            in_shardings=(_named(mesh, p_specs), _named(mesh, c_specs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, P())),
            out_shardings=(None, _named(mesh, c_specs)),
            donate_argnums=(1,),   # serving updates KV caches in place
        ).lower(params_sh, cache_shapes, tok_sh, pos_sh)
    return lowered, {"cfg": cfg}


def counting_costs(c: Cell, mesh, ce_chunk, rules_override, extra
                   ) -> Dict[str, Any]:
    """Loop-corrected per-device costs via two-point depth extrapolation.

    ``HloCostAnalysis`` counts while-loop bodies ONCE (no trip-count
    multiplication), so the scanned full-depth build under-reports.  We
    compile the same cell at ``prefix + 1·period`` and ``prefix + 2·period``
    layers with **every scan unrolled** (layer scan, CE chunks, attention kv
    blocks, SSD state carries) and extrapolate linearly in period count —
    exact because body periods are homogeneous by construction.
    """
    cfg = get_config(c.arch)
    n_prefix, period, n_periods = transformer.layer_layout(cfg)
    two = {}
    for k in (1, 2):
        cfg_k = dataclasses.replace(cfg, num_layers=n_prefix + k * period)
        with flags.unrolled_scans():
            lowered, _ = build_lowered(c, mesh, ce_chunk, rules_override,
                                       extra, cfg_override=cfg_k)
            compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        coll = roofline.parse_collectives(compiled.as_text())
        two[k] = {"flops": float(ca.get("flops", 0.0)),
                  "bytes": float(ca.get("bytes accessed", 0.0)),
                  "wire": float(coll["total_wire_bytes"]),
                  "collectives": coll}

    def extrap(key):
        per = two[2][key] - two[1][key]
        return two[1][key] + (n_periods - 1) * per

    coll_full = {}
    for op in roofline._COLLECTIVES:
        coll_full[op] = {}
        for field in ("count", "result_bytes", "wire_bytes"):
            v1 = two[1]["collectives"][op][field]
            v2 = two[2]["collectives"][op][field]
            coll_full[op][field] = v1 + (n_periods - 1) * (v2 - v1)
    coll_full["total_wire_bytes"] = extrap("wire")
    return {
        "flops": extrap("flops"),
        "bytes accessed": extrap("bytes"),
        "collectives": coll_full,
        "two_point": {str(k): {kk: vv for kk, vv in v.items()
                               if kk != "collectives"}
                      for k, v in two.items()},
        "n_periods": n_periods,
    }


def run_cell(c: Cell, mesh, mesh_name: str, out_dir: str,
             ce_chunk: int = 512, rules_override=None,
             extra: Optional[Dict] = None, tag: str = "",
             counting: bool = True) -> Dict[str, Any]:
    chips = mesh.devices.size
    result: Dict[str, Any] = {
        "arch": c.arch, "shape": c.shape, "kind": c.kind,
        "global_batch": c.global_batch, "seq_len": c.seq_len,
        "mesh": mesh_name, "chips": chips, "eligible": c.eligible,
    }
    if not c.eligible:
        result["skipped"] = c.skip_reason
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            name = f"{c.arch}__{c.shape}{('__' + tag) if tag else ''}.json"
            with open(os.path.join(out_dir, name), "w") as f:
                json.dump(result, f, indent=1)
        return result
    cfg = get_config(c.arch)

    # ---- the artifact: full-depth scanned build must lower AND compile ----
    t0 = time.time()
    lowered, meta = build_lowered(c, mesh, ce_chunk, rules_override, extra)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    result["lower_s"] = round(t1 - t0, 2)
    result["compile_s"] = round(t2 - t1, 2)

    ca = compiled.cost_analysis() or {}
    result["raw_cost_analysis"] = {k: float(v) for k, v in ca.items()
                                   if isinstance(v, (int, float))}
    try:
        ma = compiled.memory_analysis()
        result["memory_analysis"] = {
            a: int(getattr(ma, a)) for a in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
            if hasattr(ma, a)}
    except Exception as e:  # backend-dependent
        result["memory_analysis"] = {"error": str(e)}
    result["raw_collectives"] = roofline.parse_collectives(compiled.as_text())

    # ---- loop-corrected costs (two-point unrolled counting builds) --------
    if counting:
        t3 = time.time()
        corrected = counting_costs(c, mesh, ce_chunk, rules_override, extra)
        result["counting_s"] = round(time.time() - t3, 2)
        result["cost_analysis"] = {
            "flops": corrected["flops"],
            "bytes accessed": corrected["bytes accessed"]}
        result["collectives"] = corrected["collectives"]
        result["two_point"] = corrected["two_point"]
    else:
        result["cost_analysis"] = result["raw_cost_analysis"]
        result["collectives"] = result["raw_collectives"]

    result["roofline"] = roofline.analyze(result, cfg, chips)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{c.arch}__{c.shape}{('__' + tag) if tag else ''}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(result, f, indent=1)
    return result


def summarize(result: Dict[str, Any]) -> str:
    if result.get("skipped"):
        return (f"SKIP  {result['arch']:22s} {result['shape']:12s} "
                f"({result['skipped'][:40]}...)")
    t = result["roofline"]
    return (f"OK    {result['arch']:22s} {result['shape']:12s} "
            f"lower={result['lower_s']:6.1f}s compile={result['compile_s']:6.1f}s "
            f"comp={t['compute_s']:.3f}s mem={t['memory_s']:.3f}s "
            f"coll={t['collective_s']:.3f}s dom={t['dominant']:10s} "
            f"frac={t['roofline_fraction']:.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--ce-chunk", type=int, default=512)
    ap.add_argument("--no-counting", action="store_true",
                    help="skip the two-point unrolled counting builds "
                         "(compile-proof only; multi-pod pass uses this — "
                         "the roofline table is single-pod per the brief)")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "multi_pod" if args.multi_pod else "single_pod"
    out_dir = os.path.join(args.out, mesh_name)

    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch and --shape, or --all"
        cells = [cell(args.arch, args.shape)]

    failures = []
    for c in cells:
        done = os.path.join(out_dir, f"{c.arch}__{c.shape}.json")
        if args.skip_done and os.path.exists(done):
            print(f"done  {c.arch:22s} {c.shape}")
            continue
        try:
            result = run_cell(c, mesh, mesh_name, out_dir,
                              ce_chunk=args.ce_chunk,
                              counting=not args.no_counting)
            print(summarize(result), flush=True)
            if result.get("memory_analysis"):
                tmp = result["memory_analysis"].get("temp_size_in_bytes")
                arg = result["memory_analysis"].get("argument_size_in_bytes")
                if tmp is not None:
                    print(f"      memory: args={arg} temp={tmp}", flush=True)
        except Exception as e:
            failures.append((c.arch, c.shape, repr(e)))
            print(f"FAIL  {c.arch:22s} {c.shape:12s} {e!r}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cell(s) failed: {failures}")


if __name__ == "__main__":
    main()

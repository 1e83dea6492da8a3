"""Shared benchmark utilities (timing, data generation, CSV output).

Benchmarks run on the CPU backend with 8 placeholder devices (set by
``benchmarks.run`` before jax initializes).  Wall times on CPU measure
*relative* behaviour (scaling shape, schedule overheads, dispatch counts)
— the TPU roofline numbers live in the dry-run, not here.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

RESULTS: List[Dict] = []

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` set by the caller is JAX's own setting
    and is left alone.  Otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache``: the directory is part of the cache key, so a
    path that changed from run to run would never hit.  Entry points call
    this; importing the library never does.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            **kwargs) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def record(bench: str, case: str, seconds: float, **extra) -> None:
    row = {"bench": bench, "case": case, "seconds": round(seconds, 6),
           **extra}
    RESULTS.append(row)
    extras = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"{bench:24s} {case:32s} {seconds * 1e3:10.2f} ms  {extras}",
          flush=True)


def make_table_data(rows: int, cardinality: float = 0.9, seed: int = 0,
                    value_cols: int = 1,
                    exact_values: bool = False) -> Dict[str, np.ndarray]:
    """Paper §V data recipe: uniform int64->int32 keys, 90% cardinality.

    ``exact_values`` draws integer-valued float32 payloads, making float
    aggregation exact (and therefore order-insensitive) — used by the
    out-of-core bench to assert bit-identity across morsel splits."""
    rng = np.random.default_rng(seed)
    n_unique = max(1, int(rows * cardinality))
    data = {"k": rng.integers(0, n_unique, rows).astype(np.int32)}
    for i in range(value_cols):
        data[f"v{i}"] = (rng.integers(0, 256, rows).astype(np.float32)
                         if exact_values
                         else rng.random(rows).astype(np.float32))
    return data


def dump_json(path: str, meta: Optional[Dict] = None) -> str:
    """Write RESULTS (plus run metadata) as a ``BENCH_*.json`` artifact.

    CI uploads these so the perf trajectory accumulates across PRs; the
    ``meta`` block records enough context (backend, device count, scale)
    to compare runs.
    """
    import json
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            **(meta or {}),
        },
        "results": RESULTS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return path


def dump_csv(path: Optional[str] = None) -> str:
    keys = ["bench", "case", "seconds"]
    extra_keys = sorted({k for r in RESULTS for k in r} - set(keys))
    lines = [",".join(keys + extra_keys)]

    def cell(v) -> str:  # quote compound values (e.g. stage_times lists)
        s = str(v)
        return '"' + s.replace('"', '""') + '"' if "," in s else s

    for r in RESULTS:
        lines.append(",".join(cell(r.get(k, "")) for k in keys + extra_keys))
    out = "\n".join(lines)
    if path:
        with open(path, "w") as f:
            f.write(out + "\n")
    return out

"""Run the dataframe engine's main path once on a TPU and check every answer.

  python chip_smoke.py              # one chip: every phase below
  python chip_smoke.py --chips 4    # only the in-core query, over 4 chips

Phases, in one process.  Each checks its own answers and raises on any
mismatch, so the script exits 0 only if all of them passed.

  device       the first JAX device must be a TPU; anything else exits
               non-zero before any work is done.
  load         the paper's Fig-9 data from ``--seed``: two tables of
               2^24 rows per chip (uniform int32 keys at 90% cardinality,
               integer-valued float32 payloads, so every sum is exact),
               written as multi-file Parquet datasets and read back with
               ``rdf.read_parquet``.
  in-core      merge(on="k") -> groupby("k").agg(v0: sum, mean) ->
               sort_values("k") via ``collect(mode="bsp")``, twice, against
               a pandas reference; no row dropped, no out-of-core degrade,
               no retry, and no compile on the second call.
  out-of-core  the same query streamed in at least 8 morsels; its result
               must be bit-identical to the in-core one.
  serve        a one-chip-gang ``QueryScheduler`` answers 2 plans twice
               each; every answer matches pandas and no repeat compiles.

The times printed are those of a smoke run, not a benchmark.  The last
line of stdout is one JSON object naming the device the run used.
"""

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402

import repro.df as rdf  # noqa: E402
from benchmarks.common import enable_compile_cache, make_table_data  # noqa: E402
from repro.core import CylonEnv  # noqa: E402
from repro.serve import QueryScheduler  # noqa: E402

ROWS_PER_CHIP = 1 << 24
FILES_PER_TABLE = 4
DATA_DIR = os.path.join(HERE, ".smoke_data")
#: mean = f32 sum / count: a few ulps of float32
MEAN_RTOL = 1e-6


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require_tpu(chips: int):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{platform!r}; it does not run anywhere else")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    return devices[:chips]


# ---------------------------------------------------------------------- #
# load
# ---------------------------------------------------------------------- #
def _write_dataset(data, path: str) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(path)
    n = len(data["k"])
    step = -(-n // FILES_PER_TABLE)
    for i in range(FILES_PER_TABLE):
        part = {c: v[i * step:(i + 1) * step] for c, v in data.items()}
        pq.write_table(pa.table(part),
                       os.path.join(path, f"part{i}.parquet"))
    return os.path.join(path, "*.parquet")


def load(rows: int, seed: int, data_dir: str, env: CylonEnv):
    """Fig-9 tables -> Parquet -> ``read_parquet`` frames (+ host copies
    for the reference)."""
    t0 = time.perf_counter()
    left = make_table_data(rows, seed=seed, exact_values=True)
    right = make_table_data(rows, seed=seed + 1, exact_values=True)
    shutil.rmtree(data_dir, ignore_errors=True)
    frames = []
    for name, data in (("left", left), ("right", right)):
        frame = rdf.read_parquet(
            _write_dataset(data, os.path.join(data_dir, name)), env=env)
        (spill,) = frame.sources.values()
        if spill.total_rows() != rows:
            raise AssertionError(f"{name}: read {spill.total_rows()} rows "
                                 f"of {rows}")
        frames.append(frame)
    log(f"load: 2 tables x {rows} rows, {FILES_PER_TABLE} Parquet files "
        f"each, {time.perf_counter() - t0:.3f}s")
    return left, right, frames[0], frames[1]


# ---------------------------------------------------------------------- #
# queries and their pandas references
# ---------------------------------------------------------------------- #
def fig9_query(l, r, rows_per_rank: int):
    return (l.merge(r, on="k", out_capacity=4 * rows_per_rank)
            .groupby("k").agg({"v0": ["sum", "mean"]})
            .sort_values("k"))


def fig9_reference(left, right) -> pd.DataFrame:
    joined = pd.DataFrame(left).merge(pd.DataFrame(right), on="k",
                                      suffixes=("", "_r"))
    g = joined.groupby("k", sort=True)["v0"]
    return pd.DataFrame({"v0_sum": g.sum(), "v0_mean": g.mean()})


def filter_query(l):
    return (l[l.v0 >= 128].groupby("k").agg({"v0": ["sum", "max"]})
            .sort_values("k"))


def filter_reference(left) -> pd.DataFrame:
    df = pd.DataFrame(left)
    g = df[df.v0 >= 128].groupby("k", sort=True)["v0"]
    return pd.DataFrame({"v0_sum": g.sum(), "v0_max": g.max()})


def check(got, ref: pd.DataFrame, what: str) -> None:
    """Engine result (host columns) == pandas: keys, order and every sum
    and max exactly (payloads are integer-valued); means to MEAN_RTOL."""
    want_cols = {"k", *ref.columns}
    if set(got) != want_cols:
        raise AssertionError(f"{what}: columns {sorted(got)} != "
                             f"{sorted(want_cols)}")
    if not np.array_equal(got["k"], ref.index.to_numpy()):
        raise AssertionError(f"{what}: keys differ from pandas "
                             f"({len(got['k'])} vs {len(ref)} groups)")
    for c in ref.columns:
        want = ref[c].to_numpy(np.float64)
        have = np.asarray(got[c], np.float64)
        ok = (np.allclose(have, want, rtol=MEAN_RTOL, atol=0)
              if c.endswith("_mean") else np.array_equal(have, want))
        if not ok:
            bad = int(np.flatnonzero(~np.isclose(have, want, rtol=MEAN_RTOL,
                                                 atol=0))[0])
            raise AssertionError(f"{what}: {c}[{bad}] = {have[bad]} != "
                                 f"pandas {want[bad]}")
    log(f"{what}: matches pandas ({len(ref)} groups)")


def _same(a, b, what: str) -> None:
    if set(a) != set(b) or any(
            a[c].dtype != b[c].dtype or not np.array_equal(a[c], b[c])
            for c in a):
        raise AssertionError(f"{what}: results are not bit-identical")


def _clean(stats, what: str) -> None:
    for field in ("rows_dropped", "degraded", "retries"):
        if getattr(stats, field):
            raise AssertionError(f"{what}: {field} = "
                                 f"{getattr(stats, field)}, expected 0")


# ---------------------------------------------------------------------- #
# phases
# ---------------------------------------------------------------------- #
def in_core(q, env: CylonEnv, ref: pd.DataFrame) -> dict:
    """Run ``q`` in core twice; returns the result's host columns."""
    results = []
    for call in ("first", "second"):
        t0 = time.perf_counter()
        res, stats = q.collect(env=env, mode="bsp", collect_stats=True)
        jax.block_until_ready((res.columns, res.row_counts))
        wall = time.perf_counter() - t0
        _clean(stats, f"in-core {call} call")
        results.append(res.to_numpy(nulls="mask"))
        log(f"in-core {call} call: wall {wall:.6f}s, cache_misses "
            f"{stats.cache_misses}, rows_dropped {stats.rows_dropped}, "
            f"degraded {stats.degraded}, retries {stats.retries}")
    if stats.cache_misses:
        raise AssertionError(f"in-core second call compiled "
                             f"{stats.cache_misses} program(s)")
    _same(results[0], results[1], "in-core first vs second call")
    check(results[0], ref, "in-core")
    for d in env.devices:
        m = d.memory_stats() or {}  # the CPU backend reports none
        log(f"in-core memory {d}: peak_bytes_in_use "
            f"{m.get('peak_bytes_in_use', 'not reported')}, bytes_limit "
            f"{m.get('bytes_limit', 'not reported')}")
    return results[0]


def out_of_core(q, env: CylonEnv, rows_per_rank: int, in_core_out: dict):
    morsel_rows = rows_per_rank // 8
    t0 = time.perf_counter()
    spill, stats = q.collect(env=env, mode="bsp", morsel_rows=morsel_rows,
                             collect_stats=True)
    wall = time.perf_counter() - t0
    log(f"out-of-core: morsel_rows {morsel_rows}, morsels {stats.morsels}, "
        f"wall {wall:.6f}s, rows_dropped {stats.rows_dropped}, degraded "
        f"{stats.degraded}")
    if stats.morsels < 8:
        raise AssertionError(f"out-of-core streamed {stats.morsels} morsels, "
                             f"expected at least 8")
    _same(spill.to_numpy(nulls="mask"), in_core_out,
          "out-of-core vs in-core")
    log("out-of-core: bit-identical to in-core")


def serve(queries) -> None:
    """Each (name, frame, reference) twice through one scheduler."""
    with QueryScheduler(gang_size=1) as sched:
        handles = [(name, rep, sched.submit(q, mode="bsp"), ref)
                   for name, q, ref in queries for rep in (0, 1)]
        for name, rep, h, ref in handles:
            out = h.result(timeout=900)
            s = h.stats
            # wall_s ends when collect returns, before the device finishes
            log(f"serve {name}#{rep}: devices {s['devices']}, dispatch wall "
                f"{s['wall_s']:.6f}s, cache_misses {s['cache_misses']}")
            check(out.to_numpy(), ref, f"serve {name}#{rep}")
            if rep and s["cache_misses"]:
                raise AssertionError(f"serve {name}#{rep}: repeat compiled "
                                     f"{s['cache_misses']} program(s)")


def run_one_chip(devices, rows: int, seed: int, data_dir: str) -> None:
    env = CylonEnv(devices)
    try:
        left, right, l, r = load(rows, seed, data_dir, env=None)
        q = fig9_query(l, r, rows)
        ref = fig9_reference(left, right)
        out = in_core(q, env, ref)
        out_of_core(q, env, rows, out)
        serve([("fig9", q, ref),
               ("filter", filter_query(l), filter_reference(left))])
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def run_mesh(devices, rows_per_chip: int, seed: int, data_dir: str) -> None:
    """The in-core query over every chip in ``devices``, against pandas;
    fails if device 0 peaked well above the others (a table staged whole
    on one device before the shuffle spread it)."""
    env = CylonEnv(devices)
    try:
        left, right, l, r = load(rows_per_chip * len(devices), seed,
                                 data_dir, env=env)
        in_core(fig9_query(l, r, rows_per_chip), env,
                fig9_reference(left, right))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    mean = sum(peaks) / len(peaks)
    log(f"mesh: device 0 peak / mean peak = {peaks[0] / mean:.6f}")
    if peaks[0] > 1.5 * mean:
        raise AssertionError(f"device 0 peaked at {peaks[0]} bytes, more "
                             f"than 1.5x the mean {mean:.0f}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the in-core query, sharded over 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    enable_compile_cache()
    d = devices[0]
    log(f"device: {d.platform} {d.device_kind} x{len(jax.devices())}")
    if args.chips == 1:
        run_one_chip(devices, ROWS_PER_CHIP, args.seed, DATA_DIR)
    else:
        run_mesh(devices, ROWS_PER_CHIP, args.seed, DATA_DIR)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

"""Run one benchmark cell once on the chip and print its result.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``chipbench/spec.py``).  It runs only on a TPU
with at least the chips the cell asks for: on anything else it exits
non-zero and prints no result.  The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: every number
compared with the reference beside its limit); the same checks are the
last lines of stderr.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` set by the caller is JAX's own setting
    and is left alone.  Otherwise the cache lives at the fixed path
    ``chipbench/.jax_cache`` inside the checkout: the directory is part of
    the cache key, so a path that changed from run to run would never hit.
    Every program is cached, however fast it compiled, so that a second
    run compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    return path


def require_chips(chips: int):
    """The first ``chips`` TPU devices; exits non-zero on any other
    platform or with fewer chips."""
    import jax
    from chipbench.peaks import peaks
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chipbench: needs a TPU, but JAX found platform "
                 f"{platform!r}; it does not run anywhere else")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell needs {chips} TPU chips, JAX found "
                 f"{len(devices)}")
    peaks(devices[0].device_kind)      # an unknown chip is an error
    return devices[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.spec import resolve
    cell = resolve(args.workload)
    t0 = time.perf_counter()
    import jax  # noqa: F401
    t_jax = time.perf_counter()
    devices = require_chips(cell.chips)
    t_backend = time.perf_counter()
    enable_compile_cache()
    from chipbench.bench import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   T_START, HERE, init_parts={
                       "import_jax_s": t_jax - t0,
                       "backend_s": t_backend - t_jax})
    for name, c in out["checks"].items():
        print(f"chipbench: check {name} = {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Roofline-term derivation from compiled dry-run artifacts.

Per (arch × shape × mesh) cell:

  compute term    = HLO_FLOPs / (chips × peak bf16 FLOP/s)
  memory term     = HLO_bytes / (chips × peak HBM B/s)
  collective term = wire_bytes / (chips × ICI link B/s)

with the peaks of the chip's ``device_kind`` from ``PEAKS``.

``cost_analysis()`` runs on the *partitioned* (per-device SPMD) module, so
its flops/bytes are per-device; multiplying by chips gives the global
numbers the formulas above expect — the two conventions cancel and we
compute terms directly from per-device quantities.

collective_bytes is NOT in cost_analysis: ``parse_collectives`` scans the
optimized HLO for all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops, reads each result shape and replica-group size, and
applies a per-op wire model (ring-equivalent bytes actually serialized on a
link per device):

  all-reduce       2·b·(p-1)/p        (reduce-scatter + all-gather phases)
  all-gather       b_out·(p-1)/p
  reduce-scatter   b_out·(p-1)
  all-to-all       b·(p-1)/p
  collective-perm  b
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Optional, Tuple

#: Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s of inter-chip interconnect over 4 links (50 GB/s a link,
#: the collective model's per-link bandwidth).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
#: the dry-run's compile target
V5E = "TPU v5 lite"


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip; a kind missing from ``PEAKS`` is an error, never
    a default (a roofline against the wrong chip is a wrong number)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add it to PEAKS with its "
                         f"source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "bf16[8,4096,3072]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
_GROUP_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(type_str: str) -> int:
    """Bytes of one HLO type string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> Optional[int]:
    m = _GROUP_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_BRACE_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(1, len(ids))
    return None


def _wire_bytes(op: str, result_bytes: int, p: int) -> float:
    if p <= 1:
        return 0.0
    f = (p - 1) / p
    if op.startswith("all-reduce"):
        return 2.0 * result_bytes * f
    if op.startswith("all-gather"):
        return result_bytes * f
    if op == "reduce-scatter":
        return result_bytes * (p - 1)
    if op == "all-to-all":
        return result_bytes * f
    return float(result_bytes)  # collective-permute


def parse_collectives(hlo_text: str, default_p: int = 2) -> Dict[str, Any]:
    """Scan optimized HLO; returns per-op counts/bytes + total wire bytes."""
    stats = {op: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0}
             for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, op = m.groups()
        base = op.replace("-start", "")
        b = _shape_bytes(type_str)
        p = _group_size(line) or default_p
        stats[base]["count"] += 1
        stats[base]["result_bytes"] += b
        stats[base]["wire_bytes"] += _wire_bytes(base, b, p)
    total = sum(s["wire_bytes"] for s in stats.values())
    stats["total_wire_bytes"] = total
    return stats


# ---------------------------------------------------------------------- #
# Roofline terms
# ---------------------------------------------------------------------- #
def model_flops(cfg, kind: str, global_batch: int, seq_len: int) -> float:
    """6·N_active·tokens (train) or 2·N_active·tokens (inference)."""
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * global_batch * seq_len
    if kind == "prefill":
        return 2.0 * n * global_batch * seq_len
    return 2.0 * n * global_batch  # decode: one token per sequence


def roofline_terms(per_device_flops: float, per_device_bytes: float,
                   per_device_wire_bytes: float,
                   device_kind: str = V5E) -> Dict[str, float]:
    peak = device_peaks(device_kind)
    compute_s = per_device_flops / peak["flops"]
    memory_s = per_device_bytes / peak["hbm_bw"]
    collective_s = per_device_wire_bytes / peak["link_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    terms["dominant"] = dominant.replace("_s", "")
    terms["step_s_lower_bound"] = max(compute_s, memory_s, collective_s)
    return terms


def stage_roofline(wire_bytes: float, elapsed_s: Optional[float],
                   parallelism: int, device_kind: str,
                   hbm_bytes: Optional[float] = None) -> Dict[str, float]:
    """Roofline terms for one *measured* query stage (``repro.obs``) on a
    chip of ``device_kind``.

    ``wire_bytes`` is the stage's global shuffle volume (from
    ``ExecStats.shuffle_records``); ``hbm_bytes`` defaults to 2x wire (every
    shuffled byte is packed on the send side and unpacked on the receive
    side — a lower bound, ignoring the local operator work).  FLOPs are
    unknown for dataframe ops, so the compute term is 0 and the bound is
    memory/collective-only.  ``roofline_fraction`` compares that lower
    bound to the measured stage time: 1.0 means the stage ran at the
    modeled bandwidth limit, small values mean overhead (dispatch, compile,
    driver round-trips) dominates.
    """
    p = max(1, int(parallelism))
    wire_dev = float(wire_bytes) / p
    hbm_total = 2.0 * float(wire_bytes) if hbm_bytes is None else float(hbm_bytes)
    terms = roofline_terms(0.0, hbm_total / p, wire_dev, device_kind)
    terms["wire_bytes"] = float(wire_bytes)
    terms["hbm_bytes"] = hbm_total
    terms["elapsed_s"] = float(elapsed_s) if elapsed_s is not None else None
    terms["roofline_fraction"] = (
        terms["step_s_lower_bound"] / float(elapsed_s)
        if elapsed_s else 0.0)
    return terms


def analyze(cell_result: Dict[str, Any], cfg, chips: int) -> Dict[str, Any]:
    """Attach roofline terms to one dry-run cell result dict."""
    ca = cell_result["cost_analysis"]
    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    wire_dev = float(cell_result["collectives"]["total_wire_bytes"])
    terms = roofline_terms(flops_dev, bytes_dev, wire_dev)
    mf = model_flops(cfg, cell_result["kind"], cell_result["global_batch"],
                     cell_result["seq_len"])
    hlo_flops_global = flops_dev * chips
    terms["model_flops"] = mf
    terms["hlo_flops_global"] = hlo_flops_global
    terms["useful_flops_ratio"] = (mf / hlo_flops_global
                                   if hlo_flops_global else 0.0)
    # roofline fraction: useful FLOP rate at the step lower bound vs peak
    step = terms["step_s_lower_bound"]
    terms["roofline_fraction"] = (
        mf / (step * chips * device_peaks(V5E)["flops"])
        if step > 0 else 0.0)
    return terms


# ---------------------------------------------------------------------- #
# Report generation from dry-run JSONs
# ---------------------------------------------------------------------- #
def format_table(rows: List[Dict[str, Any]]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant "
           "| MODEL_FLOPS | useful/HLO | roofline frac |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in rows:
        t = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.4f} "
            f"| {t['memory_s']:.4f} | {t['collective_s']:.4f} "
            f"| **{t['dominant']}** | {t['model_flops']:.3e} "
            f"| {t['useful_flops_ratio']:.3f} | {t['roofline_fraction']:.3f} |")
    return "\n".join(lines)


def main() -> None:
    import argparse
    import glob
    import os
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun/single_pod")
    args = ap.parse_args()
    rows = []
    for path in sorted(glob.glob(os.path.join(args.dir, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    rows = [r for r in rows if "roofline" in r]
    print(format_table(rows))


if __name__ == "__main__":
    main()

"""Reduce a ``jax.profiler`` trace of a few whole jobs to what the
per-layer readers need.

The trace holds, per chip, the device operations (one event per HLO op
with its start and duration) and, on the host, the harness's own spans:
``job`` around each whole job and, inside it, ``collect`` and ``fence``.  Both are on one clock.  The traced window runs from the start
of the first ``job`` span to the end of the last.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: host spans the harness writes, innermost last
PHASES = ("job", "collect", "fence", "compare")
#: the line of a device plane that holds one event per HLO op
OPS_LINE = "XLA Ops"
#: HLO opcodes of the collectives, as they head an op's name
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")

Interval = Tuple[int, int]          # [start, end) in nanoseconds


@dataclasses.dataclass(frozen=True)
class Op:
    name: str       # HLO instruction name, e.g. "sort.12"
    kind: str       # its opcode, e.g. "sort", "while", "fusion"
    start: int
    end: int
    fusion: str = ""    # a fusion's kind, e.g. "kCustom"; "" otherwise
    nested: bool = False  # runs inside another op (a while loop's body)


def _skip_shape(text: str) -> str:
    """``text`` after the leading result shape, which may be a tuple."""
    if not text.startswith("("):
        return text.split(" ", 1)[1] if " " in text else ""
    depth = 0
    for i, ch in enumerate(text):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return text[i + 1:].lstrip()
    return ""


def parse_op(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, fusion kind)`` of one TPU op event.  The event
    carries the HLO instruction, ``%fusion.3 = f32[8]{0} fusion(...),
    kind=kCustom, ...``; a bare name such as ``sort.3`` stands for itself,
    its opcode being the name without the number."""
    head, sep, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    if sep:
        m = re.match(r"([a-z][a-z0-9-]*)\(", _skip_shape(rest.strip()))
        if m:
            k = re.search(r"\bkind=(k\w+)", rest)
            return name, m.group(1), (k.group(1) if k else "")
    return name, re.sub(r"(\.\d+)+$", "", name), ""


def mark_nested(ops: List[Op]) -> List[Op]:
    """``ops`` in start order, each marked ``nested`` if it runs inside an
    earlier op's interval (the iterations of a while loop's body)."""
    out: List[Op] = []
    outer_end = None
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        inside = outer_end is not None and o.end <= outer_end
        out.append(dataclasses.replace(o, nested=inside))
        if not inside:
            outer_end = o.end
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals into disjoint ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def clip_ops(ops: Iterable[Op], lo: int, hi: int) -> List[Op]:
    return [dataclasses.replace(o, start=max(o.start, lo), end=min(o.end, hi))
            for o in ops if min(o.end, hi) > max(o.start, lo)]


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, int]]:
    """Each op with its duration less the time of the ops nested in it."""
    out: List[List] = []
    stack: List[List] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= o.start:
            stack.pop()
        rec = [o, o.end - o.start]
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] -= o.end - o.start
        stack.append(rec)
        out.append(rec)
    return [(o, d) for o, d in out]


@dataclasses.dataclass
class TraceRun:
    """One traced run: device ops per chip, host spans, and the counts
    the harness kept for the same jobs."""

    ops: Dict[str, List[Op]]                 # chip plane name -> its ops
    spans: List[Tuple[str, int, int]]        # (phase, start, end)
    jobs: int
    stats: Sequence[object] = ()
    input_rows: int = 0

    # -- the traced window ------------------------------------------------ #
    @property
    def window(self) -> Interval:
        job = [(s, e) for n, s, e in self.spans if n == "job"]
        if not job:
            raise ValueError("the trace holds no 'job' span")
        return min(s for s, _ in job), max(e for _, e in job)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy(self, chip: str) -> List[Interval]:
        """Disjoint intervals of the window in which ``chip`` ran an op."""
        lo, hi = self.window
        return union(clip(((o.start, o.end) for o in self.ops[chip]),
                          lo, hi))

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the traced chips."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(c))
                   for c in self.ops) * 1e-9 / len(self.ops)

    # -- op time ---------------------------------------------------------- #
    def op_seconds(self, kinds: Tuple[str, ...], fusion: str = "",
                   top_level: bool = False) -> Optional[float]:
        """Device seconds of ops whose opcode starts with one of ``kinds``
        (so that ``all-to-all`` takes in an async ``all-to-all-start``),
        of this fusion kind if one is given and, with ``top_level``,
        outside any loop: in the window, summed over each chip and averaged
        over the chips; None if there is none."""
        lo, hi = self.window
        found = False
        total = 0
        for ops in self.ops.values():
            for o in ops:
                if (o.kind.startswith(kinds)
                        and (not fusion or o.fusion == fusion)
                        and not (top_level and o.nested)):
                    found = True
                    total += sum(e - s for s, e in clip([(o.start, o.end)],
                                                        lo, hi))
        return total * 1e-9 / len(self.ops) if found else None

    # -- what the ledger keeps -------------------------------------------- #
    def phase_at(self, t: int) -> str:
        """The innermost harness span that holds time ``t``."""
        best, width = "none", None
        for n, s, e in self.spans:
            if s <= t < e and (width is None or e - s < width):
                best, width = n, e - s
        return best

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The ops that took most device time (self time: a loop without
        the ops of its body; seconds per chip, summed over the window) and
        the longest idle gaps, each named by the harness phase the host
        was in at the gap's middle."""
        lo, hi = self.window
        per_op: Dict[str, int] = {}
        gaps: List[Tuple[int, int]] = []
        for chip, ops in self.ops.items():
            for o, d in self_times(clip_ops(ops, lo, hi)):
                if d:
                    per_op[o.name] = per_op.get(o.name, 0) + d
            t = lo
            for s, e in self.busy(chip) + [(hi, hi)]:
                if s > t:
                    gaps.append((s - t, t))
                t = max(t, e)
        n = max(len(self.ops), 1)
        device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps.sort(reverse=True)
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in device_ops],
                "idle_gaps": [[self.phase_at(t + g // 2), g * 1e-9]
                              for g, t in gaps[:top]]}


def load_profile(path: str):
    """``(ops per device plane, harness spans)`` of one ``.xplane.pb``,
    or of one compressed as ``.xplane.pb.gz``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            prof = ProfileData.from_serialized_xspace(f.read())
    else:
        prof = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                lst = []
                for ev in line.events:
                    s = int(ev.start_ns)
                    name, kind, fusion = parse_op(ev.name)
                    lst.append(Op(name, kind, s, s + int(ev.duration_ns),
                                  fusion))
                ops[plane.name] = mark_nested(lst)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PHASES:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return ops, spans


def read_trace(trace_dir: str, jobs: int, stats=(),
               input_rows: int = 0) -> TraceRun:
    """The run traced under ``trace_dir`` (the newest ``.xplane.pb``)."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, spans = load_profile(files[-1])
    return TraceRun(ops, spans, jobs, stats, input_rows)

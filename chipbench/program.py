"""What the engine says about its own traced jobs: its host spans, and the
dataframe operator behind each device op.

While a ``jax.profiler`` trace records, a ``collect`` traces itself
(``repro.obs``): every span is also a ``repro.<name>`` annotation on the
profile's host plane, on the clock of the device ops, and each job's
finished ``QueryTrace`` comes back on its ``ExecStats`` (``stats.trace``).
The trace knows the programs the job dispatched, and each program's HLO
says which operator scope (``join``, ``shuffle``, ...) every device op
belongs to.  A program without these (an older engine) reads None here,
so the readers built on this module report nothing rather than fail.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import PHASES, TraceRun, clip_ops

#: the prefix of the engine's annotations on the profile's host plane
ENGINE_PREFIX = "repro."


# -- host spans --------------------------------------------------------- #
def engine_spans(run: TraceRun) -> Optional[List[Tuple[str, float]]]:
    """``(span name, seconds)`` of every span the traced jobs recorded
    (the engine's own clock), or None when no job carries a trace."""
    traces = [t for t in (getattr(s, "trace", None) for s in run.stats)
              if t is not None]
    if not traces:
        return None
    return [(s.name, s.duration_s) for t in traces for s in t.spans
            if not s.instant]


def host_ms_per_job(run: TraceRun, names: Sequence[str]) -> Optional[float]:
    """Milliseconds per job in the engine's spans called one of ``names``;
    a name ending in ``:`` stands for every span it begins
    (``place:`` for ``place:left``, ``place:right``)."""
    spans = engine_spans(run)
    if spans is None:
        return None
    exact = tuple(n for n in names if not n.endswith(":"))
    prefixes = tuple(n for n in names if n.endswith(":"))
    total = sum(d for n, d in spans
                if n in exact or (prefixes and n.startswith(prefixes)))
    return 1e3 * total / run.jobs


# -- operator scopes of device ops -------------------------------------- #
def op_scopes(run: TraceRun) -> Optional[Dict[str, str]]:
    """HLO op name -> dataframe scope ("" for none) of the programs the
    traced jobs ran, or None when no job carries a trace.  A run may carry
    the map already as ``run.op_scopes`` (a recorded trace's, read from
    its file); otherwise it is built once, which lowers and compiles each
    program again (the compile caches make that cheap)."""
    if "op_scopes" not in vars(run):
        traces = [t for t in (getattr(s, "trace", None) for s in run.stats)
                  if t is not None and hasattr(t, "op_scopes")]
        run.op_scopes = None
        if traces:
            from repro.obs.hlo import merge_scopes
            run.op_scopes = merge_scopes(t.op_scopes() for t in traces)
    return run.op_scopes


def scope_seconds(run: TraceRun) -> Optional[Dict[str, float]]:
    """Device seconds of top-level ops (a while loop with its body, never
    the body twice) in the window, per dataframe scope ("" for an op no
    operator scopes), summed per chip and averaged over the chips."""
    if not run.ops:
        return None
    scopes = op_scopes(run)
    if scopes is None:
        return None
    lo, hi = run.window
    out: Dict[str, float] = {}
    for ops in run.ops.values():
        for o in clip_ops(ops, lo, hi):
            if not o.nested:
                k = scopes.get(o.name, "")
                out[k] = out.get(k, 0.0) + (o.end - o.start) * 1e-9
    return {k: v / len(run.ops) for k, v in out.items()}


def scope_ms_per_job(run: TraceRun, scope: str) -> Optional[float]:
    """Device milliseconds per job of the top-level ops under ``scope``."""
    per = scope_seconds(run)
    if not per or scope not in per:
        return None
    return 1e3 * per[scope] / run.jobs


# -- the engine's spans on the device clock ----------------------------- #
Span = Tuple[str, int, int]          # (name, start, end) in nanoseconds


def scopes_path(trace_path: str) -> str:
    """Where the op -> scope map of a recorded trace is kept: beside it,
    its name up to ``.xplane.pb`` followed by ``.scopes.json``."""
    return trace_path.split(".xplane.pb")[0] + ".scopes.json"


def load_engine_spans(path: str) -> List[Span]:
    """The engine's spans in one ``.xplane.pb`` (or ``.xplane.pb.gz``),
    their names without the ``repro.`` prefix."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            prof = ProfileData.from_serialized_xspace(f.read())
    else:
        prof = ProfileData.from_file(path)
    out: List[Span] = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ENGINE_PREFIX):
                        s = int(ev.start_ns)
                        out.append((ev.name[len(ENGINE_PREFIX):], s,
                                    s + int(ev.duration_ns)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


@dataclasses.dataclass
class SpanRun(TraceRun):
    """A traced run that also holds the engine's spans on the device
    clock, so that idle time is named by what the engine was doing."""

    engine: List[Span] = dataclasses.field(default_factory=list)

    def phase_at(self, t: int) -> str:
        """The innermost span, the engine's or the harness's, that holds
        time ``t``; on a tie the engine's."""
        best, width = "none", None
        for n, s, e in list(self.spans) + list(self.engine):
            if s <= t < e and (width is None or e - s <= width):
                best, width = n, e - s
        return best

    def idle_by_phase(self) -> Dict[str, float]:
        """Idle seconds of the window per innermost span, each gap split
        at the spans' edges, averaged over the chips."""
        lo, hi = self.window
        edges = sorted({t for _, s, e in list(self.spans) + self.engine
                        for t in (s, e) if lo < t < hi})
        out: Dict[str, float] = {}
        for chip in self.ops:
            t = lo
            for s, e in self.busy(chip) + [(hi, hi)]:
                if s > t:
                    cuts = edges[bisect.bisect_right(edges, t):
                                 bisect.bisect_left(edges, s)]
                    for a, b in zip([t] + cuts, cuts + [s]):
                        name = self.phase_at((a + b) // 2)
                        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
                t = max(t, e)
        n = max(len(self.ops), 1)
        return {k: v / n for k, v in out.items()}

    def engine_idle_share(self) -> Optional[float]:
        """Share of the window's idle time inside an engine span rather
        than in a bare harness phase; None when nothing was idle."""
        idle = self.idle_by_phase()
        total = sum(idle.values())
        if not total:
            return None
        bare = set(PHASES) | {"none"}
        return sum(v for k, v in idle.items() if k not in bare) / total

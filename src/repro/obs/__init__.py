"""``repro.obs`` — tracing + metrics: make every execution self-describing.

Three layers (see ``docs/observability.md``):

* ``trace``   — ``Tracer`` / ``Span`` / ``QueryTrace``: driver-side
                hierarchical spans (query -> stage -> shuffle -> chunk)
                with Chrome/Perfetto ``trace_event`` export,
* ``metrics`` — process-global ``MetricsRegistry`` (labeled counters /
                gauges / histograms + per-query records), the feed for a
                future multi-query admission controller,
* ``analyze`` — EXPLAIN ANALYZE (``QueryReport``): the EXPLAIN tree
                re-rendered with *measured* per-node rows / bytes / times
                plus a per-stage roofline table (``launch.roofline``),
* ``hlo``     — the dataframe-operator scope of each HLO op of a compiled
                program (``QueryTrace.op_scopes``), which names a profile's
                device ops by operator.

Tracing is opt-in (``trace=`` argument, ``REPRO_TRACE=1``, or a recording
``jax.profiler`` trace, whose host plane then carries every span as a
``repro.<name>`` annotation) and purely driver-side: compiled programs are
bit-identical with tracing on or off.

``analyze`` is imported lazily: it depends on ``repro.planner``, which
itself imports this package's trace layer — eager import would cycle.
"""

from .trace import (NULL_TRACER, QueryTrace, Span, Tracer, last_trace,
                    resolve_tracer)
from .metrics import (METRICS, MetricsRegistry, record_exec,
                      record_serve_query)

_ANALYZE_NAMES = ("QueryReport", "run_analyzed", "render_analyze",
                  "stage_table")

__all__ = [
    "METRICS", "MetricsRegistry", "NULL_TRACER", "QueryReport", "QueryTrace",
    "Span", "Tracer", "last_trace", "record_exec", "record_serve_query",
    "render_analyze", "resolve_tracer", "run_analyzed", "stage_table",
]


def __getattr__(name: str):
    if name in _ANALYZE_NAMES:
        from . import analyze
        return getattr(analyze, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

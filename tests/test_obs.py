"""Observability tests (``repro.obs``): span/trace mechanics, Chrome-trace
export, the metrics registry, counter accuracy against independently
computed values across all execution modes, and THE acceptance invariant —
tracing on/off yields bit-identical results and compiles nothing new.

Unit scope (1 CPU device); the 8-device EXPLAIN ANALYZE golden scenario
lives in ``tests/md_scripts/explain_analyze_fig9.py``.
"""

import glob
import json
import os
import re

import jax
import numpy as np
import pytest

from repro.core import CylonEnv, DistTable, Plan, execute
from repro.obs import (METRICS, NULL_TRACER, MetricsRegistry, Tracer,
                       last_trace, record_exec, resolve_tracer, run_analyzed)
from repro.obs.hlo import merge_scopes, op_scopes, scope_of
from repro.obs.trace import PROFILE_PREFIX
from repro.planner import compile_plan

#: row width of the (int32 k, float32 v0) test tables — the independent
#: bytes-per-row figure the counter-accuracy tests check against
ROW_BYTES = 8


def _data(rng, n=96, keys=12):
    """Integer-valued float32 payloads: aggregation is exact, so traced and
    untraced runs must agree to the bit."""
    return {"k": rng.integers(0, keys, n).astype(np.int32),
            "v0": rng.integers(0, 64, n).astype(np.float32)}


# ---------------------------------------------------------------------- #
# Tracer / Span mechanics
# ---------------------------------------------------------------------- #
def test_span_nesting_attrs_and_durations():
    tr = Tracer("t")
    with tr.span("query", "query") as q:
        with tr.span("stage:0", "stage", dispatch=0) as s:
            s.set(rows=10)
        tr.instant("chunk[0]", "chunk", bytes=64)
    assert q.span.end_s is not None
    trace = tr.finish()
    root = trace.root()
    assert root.name == "query" and root.parent_id is None
    assert [c.name for c in trace.children(root)] == ["stage:0", "chunk[0]"]
    stage = trace.find("stage")[0]
    assert stage.attrs == {"dispatch": 0, "rows": 10}
    assert root.duration_s >= stage.duration_s >= 0.0
    inst = trace.find("chunk")[0]
    assert inst.instant and inst.duration_s == 0.0
    assert trace.duration_s == root.duration_s


def test_finish_closes_open_spans_and_is_idempotent():
    tr = Tracer()
    tr.span("query", "query")               # never exited
    t1 = tr.finish()
    assert t1.root().end_s is not None
    assert tr.finish() is t1                # frozen, not rebuilt
    assert last_trace() is t1


def test_fence_returns_value():
    tr = Tracer()
    with tr.span("s") as h:
        assert h.fence(41) == 41            # block_until_ready passthrough


def test_chrome_trace_export(tmp_path):
    tr = Tracer("q")
    with tr.span("query", "query"):
        with tr.span("stage:0", "stage"):
            tr.instant("shuffle(k)", "shuffle", rows=4, bytes=32)
    path = tmp_path / "trace.json"
    payload = tr.finish().to_chrome_trace(str(path))
    assert json.loads(path.read_text()) == payload
    assert payload["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in payload["traceEvents"]}
    assert evs["query"]["ph"] == "X" and evs["shuffle(k)"]["ph"] == "i"
    assert evs["shuffle(k)"]["args"] == {"rows": 4, "bytes": 32}
    # timestamps are relative microseconds; children nest in the parent
    q, s = evs["query"], evs["stage:0"]
    assert q["ts"] == 0.0
    assert s["ts"] >= q["ts"]
    assert s["ts"] + s["dur"] <= q["ts"] + q["dur"] + 1e-3
    assert all(e["pid"] == 0 and e["tid"] == 0
               for e in payload["traceEvents"])


def test_null_tracer_is_falsy_noop():
    assert not NULL_TRACER and NULL_TRACER.enabled is False
    with NULL_TRACER.span("x", "stage", rows=1) as h:
        assert h.set(more=2) is h
        assert h.fence(42) == 42
    assert NULL_TRACER.instant("y") is None
    assert NULL_TRACER.finish() is None


def test_resolve_tracer_env_and_args(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert resolve_tracer(None) is NULL_TRACER
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert isinstance(resolve_tracer(None), Tracer)
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert resolve_tracer(None) is NULL_TRACER
    assert resolve_tracer(False) is NULL_TRACER
    assert isinstance(resolve_tracer(True), Tracer)
    t = Tracer("mine")
    assert resolve_tracer(t) is t           # passthrough, not re-wrapped
    assert resolve_tracer(NULL_TRACER) is NULL_TRACER


# ---------------------------------------------------------------------- #
# Metrics registry
# ---------------------------------------------------------------------- #
def test_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("queries_total")
    c.inc(mode="bsp")
    c.inc(2, mode="bsp")
    c.inc(mode="amt")
    assert c.value(mode="bsp") == 3 and c.value(mode="amt") == 1
    assert c.value(mode="nope") == 0
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("queries_total") is c   # create-on-first-use
    g = reg.gauge("queue_depth")
    g.set(5)
    g.set(2)
    assert g.value() == 2
    h = reg.histogram("wall", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 10.0):
        h.observe(v)
    s = h.series()
    assert s["count"] == 3 and s["bucket_counts"] == [1, 1, 1]
    assert s["min"] == 0.05 and s["max"] == 10.0 and s["sum"] == 10.55
    snap = json.loads(reg.to_json())
    assert snap["counters"]["queries_total"][0]["labels"] == {"mode": "amt"}
    assert snap["gauges"]["queue_depth"][0]["value"] == 2


def test_query_record_cap_and_reset():
    reg = MetricsRegistry(max_query_records=3)
    for i in range(5):
        reg.record_query({"i": i})
    assert [r["i"] for r in reg.query_records] == [2, 3, 4]  # drop-oldest
    assert all("recorded_at" in r for r in reg.query_records)
    reg.reset()
    assert reg.query_records == []
    assert reg.snapshot()["counters"] == {}


def test_record_exec_folds_stats_into_registry(rng):
    env = CylonEnv()
    t = DistTable.from_numpy(_data(rng), env.parallelism)
    plan = Plan.scan("l").shuffle(["k"])
    _, st = execute(plan, env, {"l": t}, optimize=False, collect_stats=True)
    reg = MetricsRegistry()
    rec = record_exec(st, "fp123", 0.5, query="q1", registry=reg)
    assert rec["fingerprint"] == "fp123" and rec["mode"] == "bsp"
    assert reg.counter("queries_total").value(mode="bsp") == 1
    assert (reg.counter("rows_shuffled_total").value(mode="bsp")
            == st.rows_shuffled)
    assert reg.histogram("query_wall_s").series(mode="bsp")["count"] == 1
    assert reg.query_records[-1]["rows_shuffled"] == st.rows_shuffled


# ---------------------------------------------------------------------- #
# Counter accuracy: stats vs independently computed volumes
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["bsp", "bsp_staged", "amt"])
def test_counter_accuracy_all_modes(rng, mode):
    n = 96
    env = CylonEnv()
    data = _data(rng, n)
    t = DistTable.from_numpy(data, env.parallelism)
    # unoptimized: the explicit shuffle AND the groupby's own shuffle each
    # move all n rows of (int32 k, float32 v0) = 8 bytes/row
    plan = Plan.scan("l").shuffle(["k"]).groupby(["k"], {"v0": ["sum"]})
    before = METRICS.counter("rows_shuffled_total").value(mode=mode)
    out, st = execute(plan, env, {"l": t}, mode=mode, optimize=False,
                      collect_stats=True)
    assert st.rows_shuffled == 2 * n
    assert st.bytes_shuffled == 2 * n * ROW_BYTES
    assert st.rows_dropped == 0
    recs = {r.label: r for r in st.shuffle_records}
    assert recs["shuffle(k)"].rows == n
    assert recs["groupby(k)"].rows == n
    assert recs["shuffle(k)"].bytes == n * ROW_BYTES
    # ... and the execution folded the same numbers into the global registry
    after = METRICS.counter("rows_shuffled_total").value(mode=mode)
    assert after - before == 2 * n
    assert len(out.to_numpy()["k"]) == len(np.unique(data["k"]))


def test_counter_accuracy_out_of_core(rng):
    n, m = 96, 16
    env = CylonEnv()
    data = _data(rng, n)
    plan = Plan.scan("l").shuffle(["k"])
    out, st = execute(plan, env, {"l": data}, optimize=False,
                      collect_stats=True, morsel_rows=m)
    # per-morsel shuffles must sum to exactly one pass over the data
    assert st.morsels == n // m
    assert st.rows_shuffled == n
    assert st.bytes_shuffled == n * ROW_BYTES
    assert {r.label: r.rows for r in st.shuffle_records} == {"shuffle(k)": n}
    assert out.total_rows() == n


def test_cache_hit_accuracy(rng):
    env = CylonEnv()
    t = DistTable.from_numpy(_data(rng), env.parallelism)
    plan = Plan.scan("l").shuffle(["k"]).groupby(["k"], {"v0": ["sum"]})
    _, s1 = execute(plan, env, {"l": t}, mode="bsp_staged", optimize=False,
                    collect_stats=True)
    assert s1.cache_hits + s1.cache_misses == s1.dispatches == 2
    _, s2 = execute(plan, env, {"l": t}, mode="bsp_staged", optimize=False,
                    collect_stats=True)
    assert s2.cache_misses == 0 and s2.cache_hits == s2.dispatches == 2


def test_exec_stats_timing_fields(rng):
    env = CylonEnv()
    t = DistTable.from_numpy(_data(rng), env.parallelism)
    plan = Plan.scan("l").shuffle(["k"]).groupby(["k"], {"v0": ["sum"]})
    _, st = execute(plan, env, {"l": t}, mode="bsp_staged", optimize=False,
                    collect_stats=True)
    assert st.wall_time_s > 0
    assert [nm for nm, _ in st.stage_times] == ["stage:0", "stage:1"]
    assert all(secs >= 0 for _, secs in st.stage_times)
    assert sum(secs for _, secs in st.stage_times) <= st.wall_time_s + 1e-6


# ---------------------------------------------------------------------- #
# THE invariant: tracing is invisible to results and to the compile cache
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["bsp", "bsp_staged", "amt"])
def test_tracing_invisible_to_results_and_cache(rng, mode):
    env = CylonEnv()
    ld = _data(rng, 128)
    rd = {"k": rng.integers(0, 12, 64).astype(np.int32),
          "w": rng.integers(0, 64, 64).astype(np.float32)}
    lt = DistTable.from_numpy(ld, env.parallelism)
    rt = DistTable.from_numpy(rd, env.parallelism)
    tables = {"l": lt, "r": rt}
    plan = (Plan.scan("l").join(Plan.scan("r"), on="k", out_capacity=8192)
            .groupby(["k"], {"v0": ["sum"]}).sort(["k"]))
    ref, s0 = execute(plan, env, tables, mode=mode, collect_stats=True)
    keys0 = set(env._cache)
    tr = Tracer("rerun")
    out, s1 = execute(plan, env, tables, mode=mode, collect_stats=True,
                      trace=tr)
    assert set(env._cache) == keys0          # tracing compiled NOTHING new
    assert s1.cache_misses == 0 and s1.cache_hits == s1.dispatches
    ref_np, out_np = ref.to_numpy(), out.to_numpy()
    for c in ref_np:
        np.testing.assert_array_equal(ref_np[c], out_np[c])
    trace = tr.finish()
    root = trace.root()
    assert root.category == "query"
    # the traced fingerprint is the plan's structural fingerprint
    assert root.attrs["fingerprint"] == compile_plan(plan,
                                                     tables).fingerprint
    assert trace.find("stage") and trace.find("shuffle")
    assert last_trace() is trace


def test_tracing_invisible_out_of_core(rng):
    env = CylonEnv()
    data = _data(rng, 128)
    plan = Plan.scan("l").shuffle(["k"]).groupby(["k"], {"v0": ["sum"]})
    kw = dict(optimize=False, collect_stats=True, morsel_rows=32)
    ref, s0 = execute(plan, env, {"l": data}, **kw)
    keys0 = set(env._cache)
    tr = Tracer("ooc")
    out, s1 = execute(plan, env, {"l": data}, trace=tr, **kw)
    assert set(env._cache) == keys0
    assert s1.cache_misses == 0
    ref_np, out_np = ref.to_numpy(), out.to_numpy()
    for c in ref_np:
        np.testing.assert_array_equal(ref_np[c], out_np[c])
    trace = tr.finish()
    assert trace.find("morsel")              # per-morsel spans
    assert trace.find("transfer", "h2d")     # MorselSource H2D volumes


# ---------------------------------------------------------------------- #
# Drop diagnostics name the op label and rank (never silent, never vague)
# ---------------------------------------------------------------------- #
def test_shuffle_drop_warning_names_label_and_rank(rng):
    env = CylonEnv()
    t = DistTable.from_numpy(_data(rng, 128), 1)
    plan = Plan.scan("l").shuffle(["k"], out_capacity=32,
                                  debug_overflow=True)
    with pytest.warns(RuntimeWarning, match=r"shuffle\(k\) @ rank 0"):
        out = execute(plan, env, {"l": t}, optimize=False)
        np.asarray(out.row_counts)           # force execution + callback


def test_morsel_drop_warning_attributes_loss(rng):
    env = CylonEnv()
    ld = {"k": np.zeros(64, np.int32), "v0": np.ones(64, np.float32)}
    rd = {"k": np.zeros(64, np.int32), "w": np.ones(64, np.float32)}
    plan = Plan.scan("l").join(Plan.scan("r"), on="k")
    with pytest.warns(RuntimeWarning,
                      match=r"capacity pressure \(join\(k\).*@ rank 0"):
        execute(plan, env, {"l": ld, "r": rd}, optimize=False,
                morsel_rows=16, overflow="warn")


# ---------------------------------------------------------------------- #
# EXPLAIN ANALYZE (plan-level; the df frontend wraps run_analyzed)
# ---------------------------------------------------------------------- #
def test_run_analyzed_report(rng, tmp_path):
    env = CylonEnv()
    ld = _data(rng, 128)
    rd = {"k": rng.integers(0, 12, 64).astype(np.int32),
          "w": rng.integers(0, 64, 64).astype(np.float32)}
    tables = {"l": DistTable.from_numpy(ld, env.parallelism),
              "r": DistTable.from_numpy(rd, env.parallelism)}
    plan = (Plan.scan("l").join(Plan.scan("r"), on="k", out_capacity=8192)
            .groupby(["k"], {"v0": ["sum"]}).sort(["k"]))
    result, report = run_analyzed(plan, env, tables)
    text = report.explain_analyze()
    assert "== EXPLAIN ANALYZE: mode=bsp_staged" in text
    assert "act: moved" in text              # measured per-node volumes
    assert "rows=128" in text                # scan actuals
    assert f"out_rows={result.total_rows()}" in text
    assert report.wall_time_s > 0
    stages = report.stage_table()
    assert [r["stage"] for r in stages] == sorted(r["stage"] for r in stages)
    # per-row width varies per stage (the right join side is projected to
    # just k = 4 bytes/row), but stays within the schema's bounds
    assert all(4 * r["rows_shuffled"] <= r["wire_bytes"]
               <= ROW_BYTES * r["rows_shuffled"]
               for r in stages if r["rows_shuffled"])
    md = report.roofline_table()
    assert md.splitlines()[0].startswith("| stage |")
    # a CPU run has no chip to hold a roofline against
    assert all(r["bound_s"] is None and r["roofline_fraction"] is None
               for r in stages)
    assert "not measured" in md
    d = json.loads(report.to_json())
    assert d["mode"] == "bsp_staged" and d["rows_dropped"] == 0
    assert d["fingerprint"] == report.pplan.fingerprint
    assert {r["label"] for r in d["shuffle_records"]} \
        == {r.label for r in report.stats.shuffle_records}
    payload = report.to_chrome_trace(str(tmp_path / "t.json"))
    cats = {e["cat"] for e in payload["traceEvents"]}
    assert {"query", "stage", "shuffle"} <= cats
    assert str(report).startswith("== EXPLAIN ANALYZE")


def test_run_analyzed_trace_off_keeps_tables(rng):
    env = CylonEnv()
    t = DistTable.from_numpy(_data(rng), env.parallelism)
    plan = Plan.scan("l").groupby(["k"], {"v0": ["sum"]})
    _, report = run_analyzed(plan, env, {"l": t}, trace=False)
    assert report.trace is None
    with pytest.raises(ValueError, match="no trace attached"):
        report.to_chrome_trace()
    assert "EXPLAIN ANALYZE" in report.explain_analyze()
    assert report.stage_table()              # tables survive without a trace


def test_df_collect_analyze(rng):
    rdf = pytest.importorskip("repro.df")
    env = CylonEnv()
    rdf.set_default_env(env)
    try:
        df = rdf.read_numpy(_data(rng))
        out, report = df.groupby("k").agg(v0="sum").collect(analyze=True)
        assert "act:" in report.explain_analyze()
        assert report.result_rows == out.total_rows()
        with pytest.raises(TypeError, match="already collects stats"):
            df.collect(analyze=True, collect_stats=True)
        text = df.groupby("k").agg(v0="sum").explain_analyze()
        assert "EXPLAIN ANALYZE" in text and "| stage |" in text
    finally:
        rdf.reset_default_env()


# ---------------------------------------------------------------------- #
# One clock: the engine's spans on the profiler's host plane
# ---------------------------------------------------------------------- #
def _fig9(rng, holder):
    """The Fig-9 join -> groupby -> sort plan over two tables built by
    ``holder(data, parallelism)``."""
    env = CylonEnv()
    rd = {"k": rng.integers(0, 12, 64).astype(np.int32),
          "w": rng.integers(0, 64, 64).astype(np.float32)}
    tables = {"l": holder(_data(rng, 128), env.parallelism),
              "r": holder(rd, env.parallelism)}
    plan = (Plan.scan("l").join(Plan.scan("r"), on="k", out_capacity=8192)
            .groupby(["k"], {"v0": ["sum"]}).sort(["k"]))
    return env, tables, plan


def _engine_events(trace_dir):
    """``(name, start_ns, end_ns)`` of the ``repro.`` events on the host
    planes of the one profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROFILE_PREFIX):
                        s = int(ev.start_ns)
                        out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


@pytest.mark.parametrize("trace", [True, False], ids=["on", "off"])
def test_spans_reach_the_profilers_host_plane_nested(rng, tmp_path, trace):
    """Under ``jax.profiler``, a traced collect puts every span of its
    tree on the host plane as ``repro.<name>``, each inside its parent's;
    with tracing off it puts none there."""
    from repro.core import SpillTable
    env, tables, plan = _fig9(rng, SpillTable.from_numpy)
    execute(plan, env, tables, collect_stats=True, trace=False)  # compiles
    with jax.profiler.trace(str(tmp_path)):
        _, st = execute(plan, env, tables, collect_stats=True, trace=trace)
    events = _engine_events(tmp_path)
    if not trace:
        assert events == [] and st.trace is None
        return
    names = {n for n, _, _ in events}
    assert {PROFILE_PREFIX + n for n in (
        "query", "plan", "place:l", "place:r", "adapt:sample",
        "stage:program", "dispatch", "wait", "readback")} <= names
    assert PROFILE_PREFIX + "rescatter" not in names
    at = {n: (s, e) for n, s, e in events}
    spans = {s.span_id: s for s in st.trace.spans}
    checked = 0
    for s in st.trace.spans:
        if s.parent_id is None or s.instant:
            continue
        (cs, ce), (ps, pe) = (at[PROFILE_PREFIX + s.name],
                              at[PROFILE_PREFIX + spans[s.parent_id].name])
        assert ps <= cs and ce <= pe, (s.name, spans[s.parent_id].name)
        checked += 1
    assert checked >= 8
    parent = {s.name: spans[s.parent_id].name for s in st.trace.spans
              if s.parent_id is not None}
    assert parent["plan"] == parent["place:l"] == "query"
    assert parent["dispatch"] == parent["wait"] == "stage:program"
    assert st.trace.root().name == "query"
    assert st.trace.find(name_prefix="dispatch")[0].attrs["cache_hit"]


def test_resolve_tracer_follows_the_profiler(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    with jax.profiler.trace(str(tmp_path)):
        assert isinstance(resolve_tracer(None), Tracer)
        assert resolve_tracer(False) is NULL_TRACER
        monkeypatch.setenv("REPRO_TRACE", "0")
        assert resolve_tracer(None) is NULL_TRACER
    monkeypatch.delenv("REPRO_TRACE")
    assert resolve_tracer(None) is NULL_TRACER


# ---------------------------------------------------------------------- #
# Operator scopes: named device ops, invisible to results and the cache
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("op_name, scope", [
    ("jit(df_program)/join/jit(searchsorted)/while", "join"),
    ("jit(df_program)/groupby/shuffle/all_to_all", "shuffle"),
    ("jit(df_program)/sort/jit(sort)/sort", "sort"),
    ("jit(df_program)/sort", ""),       # the primitive, not a scope
    ("jit(df_program)/stack", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scope_of(op_name) == scope


def test_op_scopes_reads_fusions_through_their_computations():
    text = "\n".join([
        "HloModule m, entry_computation_layout={(f32[8]{0})->f32[8]{0}}",
        "",
        "%fused_computation (p.1: f32[8]) -> f32[8] {",
        "  %p.1 = f32[8]{0} parameter(0)",
        '  ROOT %add.2 = f32[8]{0} add(%p.1, %p.1), metadata={op_name='
        '"jit(f)/groupby/add"}',
        "}",
        "",
        "ENTRY %main.3 (x.1: f32[8]) -> f32[8] {",
        "  %x.1 = f32[8]{0} parameter(0)",
        '  %sort.4 = f32[8]{0} sort(%x.1), dimensions={0}, metadata={'
        'op_name="jit(f)/join/sort" source_file="a.py" source_line=3}',
        "  ROOT %fusion.5 = f32[8]{0} fusion(%sort.4), kind=kLoop, "
        "calls=%fused_computation",
        "}"])
    scopes = op_scopes(text)
    assert scopes["sort.4"] == "join"
    assert scopes["fusion.5"] == "groupby"       # from its computation
    assert scopes["x.1"] == ""
    assert merge_scopes([{"a": "join"}, {"a": "sort", "b": ""}]) == \
        {"a": "", "b": ""}


def test_op_scopes_of_the_fig9_program(rng):
    env, tables, plan = _fig9(rng, DistTable.from_numpy)
    _, st = execute(plan, env, tables, collect_stats=True, trace=True)
    (program,) = {s.attrs["program"] for s in st.trace.find("dispatch")}
    assert set(st.trace.programs) == {program}
    scopes = st.trace.op_scopes()
    assert {"join", "groupby", "sort", "shuffle"} <= set(scopes.values())
    assert env.program_text(next(iter(env._cache))).startswith("HloModule")


def _instructions(hlo_text):
    """The instructions of an HLO text with their metadata left out."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo_text.splitlines()
            if re.match(r"\s*(ROOT\s+)?%|(ENTRY\s+)?%", line)]


@pytest.mark.parametrize("mode", ["bsp", "bsp_staged", "amt"])
def test_operator_scopes_invisible_to_results_and_cache(rng, monkeypatch,
                                                        mode):
    """The programs built with every operator and shuffle scope taken
    away have the same cache keys, results and instructions: the scopes
    are metadata and nothing else."""
    import contextlib
    import importlib
    physical, groupby_mod, sort_mod = (importlib.import_module(m) for m in (
        "repro.planner.physical", "repro.dataframe.groupby",
        "repro.dataframe.sort"))
    env, tables, plan = _fig9(rng, DistTable.from_numpy)
    ref = execute(plan, env, tables, mode=mode).to_numpy()
    texts = {k: env.program_text(k) for k in env._cache}

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    for mod, attr in [(physical, "df_shuffle"), (groupby_mod, "shuffle"),
                      (sort_mod, "shuffle"), (physical, "shuffle_allgather")]:
        monkeypatch.setattr(mod, attr, getattr(mod, attr).__wrapped__)
    bare = CylonEnv()
    out = execute(plan, bare, tables, mode=mode).to_numpy()
    for c in ref:
        np.testing.assert_array_equal(ref[c], out[c])
    assert set(bare._cache) == set(texts)
    for k, text in texts.items():
        plain = bare.program_text(k)
        assert _instructions(plain) == _instructions(text)
        assert not set(op_scopes(plain).values()) - {""}
    assert any(set(op_scopes(t).values()) - {""} for t in texts.values())

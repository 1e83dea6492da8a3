"""The engine's own spans and operator scopes as the per-layer readers read
them: host milliseconds per span, device time per operator scope, and
idle time named by the engine's spans, on synthetic runs, on four virtual
devices, and on small traces recorded on one and on four v5e chips with
the engine's spans on (``data/*_spans.*``, made by
``record_program_trace.py``)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench.program import (SpanRun, load_engine_spans, scope_seconds,
                               scopes_path)
from chipbench.spec import HERE, ROOT, load_reader
from chipbench.trace import PHASES, Op, TraceRun, load_profile, mark_nested
from repro.obs import QueryTrace, Span

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = [os.path.join(DATA, "fig9_4096_spans.xplane.pb"),
            os.path.join(DATA, "fig9-4chip_4096_spans.xplane.pb.gz")]
HOST = ("host_plan_ms_per_job", "host_place_ms_per_job",
        "host_executor_ms_per_job")
OPS = ("op_join_ms_per_job", "op_groupby_ms_per_job", "op_sort_ms_per_job",
       "op_shuffle_ms_per_job")
HARNESS = set(PHASES) | {"none"}


def _query_trace(engine):
    """A ``QueryTrace`` holding ``engine`` spans (ns) on a seconds clock."""
    return QueryTrace("recorded", 0, [Span(n, "span", s * 1e-9, e * 1e-9)
                                      for n, s, e in engine])


def _synthetic():
    ops = [Op("while.1", "while", 100, 400),
           Op("fusion.2", "fusion", 110, 200, "kCustom"),   # in the loop
           Op("sort.3", "sort", 500, 600),
           Op("fusion.5", "fusion", 700, 760, "kCustom"),
           Op("all-to-all.6", "all-to-all", 900, 950),
           Op("copy.7", "copy", 960, 970)]
    spans = [("job", 50, 1000), ("collect", 60, 640), ("fence", 640, 1000)]
    engine = [("query", 60, 640), ("plan", 60, 80), ("place:left", 80, 90),
              ("place:right", 90, 100), ("adapt:sample", 100, 101),
              ("stage:program", 101, 640), ("dispatch", 101, 110),
              ("wait", 110, 630), ("readback", 630, 640)]
    run = SpanRun({"/device:TPU:0": mark_nested(ops)}, spans, jobs=2,
                  stats=[SimpleNamespace(trace=_query_trace(engine)),
                         SimpleNamespace(trace=None)],
                  engine=engine)
    run.op_scopes = {"while.1": "join", "fusion.2": "join", "sort.3": "sort",
                     "fusion.5": "groupby", "all-to-all.6": "shuffle",
                     "copy.7": ""}
    return run


@pytest.mark.parametrize("name, ns", [
    ("host_plan_ms_per_job", 20), ("host_place_ms_per_job", 20),
    ("host_executor_ms_per_job", 1 + 9 + 10),
    ("op_join_ms_per_job", 300),        # the loop, its body not twice
    ("op_groupby_ms_per_job", 60), ("op_sort_ms_per_job", 100),
    ("op_shuffle_ms_per_job", 50)])
def test_readers_on_a_synthetic_run(name, ns):
    assert load_reader(name)(_synthetic()) == pytest.approx(ns * 1e-6 / 2)


def test_readers_read_nothing_from_an_engine_without_traces():
    """The parent engine's stats carry no ``trace``: every reader is
    None, on a run with device ops too."""
    run = _synthetic()
    run = TraceRun(run.ops, run.spans, 2, [SimpleNamespace(rows_dropped=0)])
    for name in HOST + OPS:
        assert load_reader(name)(run) is None


def test_phase_at_prefers_an_engine_span_over_the_harness():
    run = _synthetic()
    plain = TraceRun(run.ops, run.spans, run.jobs)
    assert plain.phase_at(85) == "collect"
    assert run.phase_at(85) == "place:left"
    assert run.phase_at(620) == "wait" and run.phase_at(650) == "fence"
    assert run.phase_at(55) == "job" and run.phase_at(2000) == "none"
    assert run.breakdown()["idle_gaps"][0] == ["fence", pytest.approx(140e-9)]
    assert ["wait", pytest.approx(100e-9)] in run.breakdown()["idle_gaps"]


def test_idle_time_is_split_at_the_spans_edges():
    idle = _synthetic().idle_by_phase()
    assert idle == pytest.approx({
        "job": 10e-9, "plan": 20e-9, "place:left": 10e-9,
        "place:right": 10e-9, "wait": 130e-9, "readback": 10e-9,
        "fence": 240e-9})
    run = _synthetic()
    assert sum(idle.values()) == pytest.approx(run.window_s - run.busy_s)
    # job and fence, the harness's, hold 10 + 240 of the 430 ns idle
    assert run.engine_idle_share() == pytest.approx(1 - 250 / 430)


def test_four_virtual_devices():
    """fig9-4chip.incore on four CPU devices, the engine tracing itself:
    each of the seven readers returns a value, the host spans' from the
    jobs' own traces, the scopes' from the program that ran."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(
        HERE, "tests", "four_devices_program.py")], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["programs"] == [1, 1]
    for name in HOST:
        assert res["readings"][name] > 0, name
    for name in OPS:
        assert res["readings"][name] == pytest.approx(res["op_ns"] * 1e-6 / 2)


def _recorded(path):
    ops, spans = load_profile(path)
    engine = load_engine_spans(path)
    run = SpanRun(ops, spans, 2, [SimpleNamespace(trace=_query_trace(engine))],
                  engine=engine)
    with open(scopes_path(path)) as f:
        run.op_scopes = json.load(f)
    return run


@pytest.mark.parametrize("path", RECORDED, ids=["1chip", "4chip"])
def test_recorded_trace_readers(path):
    run = _recorded(path)
    for name in HOST + OPS:
        assert load_reader(name)(run) > 0, name
    per = scope_seconds(run)
    assert sum(per.values()) == pytest.approx(sum(
        sum(o.end - o.start for o in ops if not o.nested)
        for ops in run.ops.values()) * 1e-9 / len(run.ops), rel=1e-3)


@pytest.mark.parametrize("path", RECORDED, ids=["1chip", "4chip"])
def test_recorded_trace_clocks_agree(path):
    """On every chip, one shift of its device ops for the whole trace puts
    each job's ops between the start of its ``dispatch`` span and the end
    of its ``wait`` span: the engine's spans and the device ops share one
    clock up to that chip's offset, which is under 2 ms on a v5e."""
    run = _recorded(path)
    jobs = [(s, e) for n, s, e in run.spans if n == "job"]
    assert len(jobs) == 2
    for chip, ops in run.ops.items():
        need, room = [], []    # least and most shift, per job
        for lo, hi in jobs:
            inside = {n: (s, e) for n, s, e in run.engine
                      if lo <= s and e <= hi}
            (d0, _), (_, w1) = inside["dispatch"], inside["wait"]
            mine = [o for o in ops if lo <= o.start < hi]
            assert mine, chip
            need.append(d0 - min(o.start for o in mine))
            room.append(w1 - max(o.end for o in mine))
        assert max(need) <= min(room), (chip, need, room)
        assert -2_000_000 < max(need) < 2_000_000, (chip, need)


@pytest.mark.parametrize("path", RECORDED, ids=["1chip", "4chip"])
def test_recorded_trace_idle_is_named_by_the_engine(path):
    run = _recorded(path)
    idle = run.idle_by_phase()
    assert sum(idle.values()) == pytest.approx(run.window_s - run.busy_s,
                                               rel=1e-6)
    assert run.engine_idle_share() > 0.9
    assert {n for n, _ in run.breakdown()["idle_gaps"]} - HARNESS

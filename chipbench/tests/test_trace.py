"""The reduction from a profiler trace to the per-layer metrics: op
classification, the union of busy intervals, the idle share and the
breakdown, on synthetic ops and on small traces recorded on one and on
four v5e chips (``data/``, made by ``record_trace.py``)."""

import os

import pytest

from chipbench.spec import load_benchmark, load_reader
from chipbench.trace import (COLLECTIVES, Op, TraceRun, load_profile,
                             mark_nested, parse_op, self_times, union)

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "fig9_4096.xplane.pb")
#: the same on four v5e chips, compressed with gzip
RECORDED_4 = os.path.join(os.path.dirname(__file__), "data",
                          "fig9-4chip_4096.xplane.pb.gz")


@pytest.mark.parametrize("text, want", [
    ("%sort.5 = (s32[8]{0}, f32[8]{0}) sort(s32[8]{0} %a, f32[8]{0} %b), "
     "dimensions={0}", ("sort.5", "sort", "")),
    ("%reduce-window.4 = s32[2,64,128]{2,1,0:T(8,128)S(1)} reduce-window("
     "s32[2,64,128]{2,1,0} %fusion.119, s32[]{:T(128)} %constant.242), "
     "window={size=1x1x128 pad=0_0x0_0x127_0}",
     ("reduce-window.4", "reduce-window", "")),
    ("%fusion.14 = f32[1048576]{0:T(1024)S(1)} fusion(f32[524288]{0} %g, "
     "s32[1048576]{0} %f), kind=kCustom, calls=%fused_computation.14",
     ("fusion.14", "fusion", "kCustom")),
    ("%all-to-all.2 = (s32[4,8]{1,0}) all-to-all(s32[4,8]{1,0} %x), "
     "replica_groups={{0,1,2,3}}", ("all-to-all.2", "all-to-all", "")),
    ("%while.22 = (s32[]{:T(128)}, /*index=1*/s32[8]{0}) while((s32[], "
     "s32[8]) %tuple.75), condition=%c, body=%b", ("while.22", "while", "")),
    ("collective-permute.7", ("collective-permute.7", "collective-permute",
                              "")),
])
def test_parse_op_names_the_opcode(text, want):
    assert parse_op(text) == want


def test_union_merges_overlaps_and_nesting():
    assert union([(5, 9), (0, 2), (1, 3), (6, 7), (9, 10), (12, 13)]) == \
        [(0, 3), (5, 10), (12, 13)]


def _synthetic():
    ops = [Op("while.1", "while", 100, 400),
           Op("fusion.2", "fusion", 110, 200, "kCustom"),     # in the loop
           Op("fusion.2", "fusion", 210, 300, "kCustom"),     # in the loop
           Op("sort.3", "sort", 500, 600),
           Op("reduce-window.4", "reduce-window", 550, 580),  # overlaps
           Op("fusion.5", "fusion", 700, 760, "kCustom"),
           Op("all-to-all.6", "all-to-all", 900, 950)]
    spans = [("job", 50, 1000), ("collect", 60, 640), ("fence", 640, 1000)]
    return TraceRun({"/device:TPU:0": mark_nested(ops)}, spans, jobs=2)


def test_busy_idle_and_op_time_on_synthetic_ops():
    run = _synthetic()
    # busy: [100,400) + [500,600) + [700,760) + [900,950) = 510 of 950 ns
    assert run.window_s == pytest.approx(950e-9)
    assert run.busy_s == pytest.approx(510e-9)
    idle = load_reader("device_idle_pct")(run)
    assert idle == pytest.approx(100 * (1 - 510 / 950))
    assert load_reader("sort_ms_per_job")(run) == pytest.approx(100e-6 / 2)
    assert load_reader("reduce_window_ms_per_job")(run) == \
        pytest.approx(30e-6 / 2)
    assert load_reader("while_ms_per_job")(run) == pytest.approx(300e-6 / 2)
    # only the kCustom fusion outside the loop counts
    assert load_reader("custom_fusion_ms_per_job")(run) == \
        pytest.approx(60e-6 / 2)
    assert load_reader("collective_ms_per_job")(run) == \
        pytest.approx(50e-6 / 2)


def test_self_time_and_breakdown_on_synthetic_ops():
    run = _synthetic()
    times = {o.name: 0 for o, _ in self_times(run.ops["/device:TPU:0"])}
    for o, d in self_times(run.ops["/device:TPU:0"]):
        times[o.name] += d
    assert times["while.1"] == 300 - 180 and times["fusion.2"] == 180
    assert times["sort.3"] == 70 and times["reduce-window.4"] == 30
    b = run.breakdown()
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(180e-9)]
    # gaps: [50,100) collect, [400,500) collect, [600,700) collect...
    # [760,900) fence, [950,1000) fence
    assert b["idle_gaps"][0] == ["fence", pytest.approx(140e-9)]
    assert sum(g for _, g in b["idle_gaps"]) == pytest.approx(440e-9)


def test_readers_find_nothing_to_read_without_device_ops():
    run = TraceRun({}, [("job", 0, 10)], jobs=1)
    for m in load_benchmark()["per_layer"]:
        assert load_reader(m["name"])(run) is None


def _sweep_union(intervals):
    """Busy time by counting open intervals at each boundary."""
    edges = sorted([(s, 1) for s, _ in intervals] +
                   [(e, -1) for _, e in intervals])
    busy, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_v5e_trace():
    ops, spans = load_profile(RECORDED)
    assert list(ops) == ["/device:TPU:0"]
    kinds = {o.kind for o in ops["/device:TPU:0"]}
    assert {"sort", "reduce-window", "while", "fusion"} <= kinds
    assert not kinds & {"all-to-all", "all-gather", "collective-permute"}
    assert any(o.fusion == "kCustom" for o in ops["/device:TPU:0"])
    assert [n for n, _, _ in spans].count("job") == 2
    run = TraceRun(ops, spans, jobs=2)
    lo, hi = run.window
    inside = [(max(o.start, lo), min(o.end, hi)) for o in ops["/device:TPU:0"]
              if min(o.end, hi) > max(o.start, lo)]
    assert run.busy_s == pytest.approx(_sweep_union(inside) * 1e-9)
    assert 0 < run.busy_s < run.window_s
    idle = load_reader("device_idle_pct")(run)
    assert idle == pytest.approx(100 * (1 - run.busy_s / run.window_s))
    assert 0 < idle < 100
    sort_ns = sum(e - s for s, e in
                  ((o.start, o.end) for o in ops["/device:TPU:0"]
                   if o.kind == "sort"))
    assert load_reader("sort_ms_per_job")(run) == pytest.approx(
        sort_ns * 1e-6 / 2)
    assert load_reader("collective_ms_per_job")(run) is None
    # self times partition the busy time: no op counted twice
    total = sum(d for _, d in self_times(
        [o for o in ops["/device:TPU:0"] if lo <= o.start and o.end <= hi]))
    assert total * 1e-9 == pytest.approx(run.busy_s, rel=1e-6)
    b = run.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert {p for p, _ in b["idle_gaps"]} <= {"job", "collect", "fence",
                                              "none"}


def test_recorded_four_chip_trace():
    """Each chip's plane is read, the collectives are classed as such, and
    per-job op time is the mean over the chips."""
    ops, spans = load_profile(RECORDED_4)
    assert len(ops) == 4 and all(p.startswith("/device:TPU:") for p in ops)
    run = TraceRun(ops, spans, jobs=2)
    lo, hi = run.window
    per_chip = []
    for plane, lst in ops.items():
        kinds = {o.kind for o in lst}
        assert "all-to-all" in kinds and "sort" in kinds
        per_chip.append(sum(min(o.end, hi) - max(o.start, lo) for o in lst
                            if o.kind.startswith(COLLECTIVES)
                            and min(o.end, hi) > max(o.start, lo)))
    assert load_reader("collective_ms_per_job")(run) == pytest.approx(
        sum(per_chip) / 4 * 1e-6 / 2)
    busy = [_sweep_union([(max(o.start, lo), min(o.end, hi)) for o in lst
                          if min(o.end, hi) > max(o.start, lo)])
            for lst in ops.values()]
    assert run.busy_s == pytest.approx(sum(busy) / 4 * 1e-9)
    idle = load_reader("device_idle_pct")(run)
    assert 0 < idle < 100
    assert idle == pytest.approx(100 * (1 - run.busy_s / run.window_s))

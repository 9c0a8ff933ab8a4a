"""The reduction from a profiler trace to per-layer numbers."""
import json
from pathlib import Path

import pytest

from bench import xtrace
from bench.cell import BENCH, load_reader
from bench.xtrace import MODULES_LINE, OPS_LINE

DATA = Path(__file__).resolve().parent / "data"

# two chips, ns; the window is the host's bench.call spans: [100, 1100)
EVENTS = {
    "host": [["bench.call", 100, 500], ["bench.call", 600, 500],
             ["bench.pull", 550, 80], ["PjitFunction(step)", 980, 50],
             ["bench.generate", 0, 90]],
    "devices": {
        "0": {OPS_LINE: [["fusion.1", 50, 150],       # clipped to [100,200)
                         ["fusion.1", 180, 120],      # overlaps: union
                         ["%ranking_victim_order.1 = (f32[40,128]) custom-call(...)", 400, 100],
                         ["copy.2", 700, 250],
                         ["copy.2", 1200, 100]],      # after the window
              MODULES_LINE: [["jit__chunk_step_jit", 100, 400],
                             ["jit__chunk_step_jit", 700, 250]]},
        "1": {OPS_LINE: [["fusion.1", 100, 1000]]},
    },
}


def test_window_busy_and_idle():
    v = xtrace.View(EVENTS)
    assert v.window_s == pytest.approx(1000e-9)
    # chip 0: [100,300) + [400,500) + [700,950) = 550 ns of 1000
    assert v.busy_s("0") == pytest.approx(550e-9)
    assert v.busy_s("1") == pytest.approx(1000e-9)
    assert v.busy_s() == pytest.approx(775e-9)
    assert v.idle_share("0") == pytest.approx(0.45)
    assert v.idle_share() == pytest.approx(0.225)


def test_op_ranking_and_time():
    v = xtrace.View(EVENTS)
    ranked = v.ranked_ops()
    # an op is named by its HLO instruction, without its shapes
    assert [n for n, _ in ranked] == ["fusion.1", "copy.2",
                                      "%ranking_victim_order.1"]
    assert ranked[0][1] == pytest.approx(1220e-9)      # clipped to the window
    assert v.op_time("ranking_victim_order") == pytest.approx((100e-9, 1))
    assert v.op_time("chunk_step", MODULES_LINE) == pytest.approx(
        (650e-9, 2))


def test_idle_gaps_named_by_innermost_host_span():
    v = xtrace.View(EVENTS)
    gaps = v.idle_gaps()
    # chip 0's gaps: [500,700) 200 ns, [950,1100) 150 ns, [300,400) 100 ns
    assert [round(s * 1e9) for _, s in gaps] == [200, 150, 100]
    assert gaps[0][0] == "bench.pull"               # mid 600 in [550,630)
    assert gaps[1][0] == "PjitFunction(step)"       # mid 1025 in [980,1030)
    assert gaps[2][0] == "bench.call"


def test_readers_on_the_reduced_view():
    v = xtrace.View(EVENTS)
    ctx = xtrace.Context(view=v, work=10,
                         timers={"generate": 1.5, "ingest": 0.5,
                                 "warmup": 3.0},
                         device_kind="TPU v5 lite", n_objects=4608)
    read = lambda m: load_reader(m)(ctx)
    assert read("ingest_s") == 2.0 and read("warmup_s") == 3.0
    assert read("device_idle.sweep") == pytest.approx(22.5)
    assert read("fabric_imbalance.sweep") == pytest.approx(1000 / 775)
    assert read("lane_us_per_lane_req.sweep") == pytest.approx(
        1e6 * 1550e-9 / 10)
    assert read("step_us_per_req.replay") == pytest.approx(1e6 * 650e-9 / 10)
    assert read("victim_kernel_us_per_req.replay") == pytest.approx(
        1e6 * 100e-9 / 10)
    share = read("victim_kernel_roofline.replay")
    assert share == pytest.approx(100 * (164352 / 819e9) / 100e-9)


def test_unknown_chip_has_no_peaks():
    v = xtrace.View(EVENTS)
    ctx = xtrace.Context(view=v, work=1, timers={},
                         device_kind="TPU v9", n_objects=4608)
    with pytest.raises(KeyError):
        ctx.peak()


def test_readers_find_nothing_without_chip_events():
    events = {"host": EVENTS["host"], "devices": {}}
    v = xtrace.View(events)
    ctx = xtrace.Context(view=v, work=10, timers={},
                         device_kind="TPU v5 lite", n_objects=4608)
    for m in ("device_idle.replay", "step_us_per_req.replay",
              "victim_kernel_roofline.replay",
              "victim_kernel_us_per_req.replay", "fabric_imbalance.sweep",
              "lane_us_per_lane_req.sweep"):
        assert load_reader(m)(ctx) is None, m


def _brute(events, window):
    """A plain reduction of the same events: busy nanoseconds of chip 0 as
    the count of covered nanosecond ticks, op totals clipped by hand."""
    lo, hi = window
    covered = set()
    totals = {}
    for name, s, d in events["devices"]["0"][OPS_LINE]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            covered.update(range(a, b))
            totals[name] = totals.get(name, 0) + (b - a)
    return len(covered), totals


def test_recorded_trace_slice():
    """2.5 ms of a trace recorded on a v5e: the reduction agrees with a
    tick-by-tick count over the same events."""
    events = xtrace.load_events(DATA / "v5e_sweep_slice.json")
    v = xtrace.View(events)
    call = next(h for h in events["host"] if h[0] == "bench.call")
    busy_ns, totals = _brute(events, (call[1], call[1] + call[2]))
    assert v.window_s == pytest.approx(call[2] * 1e-9)
    assert v.busy_s("0") == pytest.approx(busy_ns * 1e-9)
    assert 0.0 < v.idle_share() < 1.0
    ranked = v.ranked_ops()
    want = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    assert [n for n, _ in ranked] == [n for n, _ in want]
    assert ranked[0][1] == pytest.approx(want[0][1] * 1e-9)
    seconds, n = v.op_time("sweep_multi", MODULES_LINE)
    assert n == 1 and 0 < seconds <= v.window_s
    host = {h[0] for h in events["host"]}
    assert all(name in host for name, _ in v.idle_gaps())


def test_events_survive_a_round_trip(tmp_path):
    events = xtrace.load_events(DATA / "v5e_sweep_slice.json")
    v = xtrace.View(events)
    extracted = {"host": events["host"], "devices": {
        d: {ln: xtrace._line(evs) for ln, evs in lines.items()}
        for d, lines in events["devices"].items()}}
    xtrace.save_events(extracted, tmp_path / "e.json")
    w = xtrace.View(xtrace.load_events(tmp_path / "e.json"))
    assert (w.busy_s(), w.ranked_ops(), w.idle_gaps()) == (
        v.busy_s(), v.ranked_ops(), v.idle_gaps())


MS = 1_000_000


def test_a_cut_trace_ends_the_window():
    """Ops the device dropped (its trace buffer filled) are not idle time:
    the window ends where the recorded ops end, and readers of whole calls
    read nothing."""
    events = {"host": [["bench.call", 100 * MS, 1000 * MS]],
              "devices": {"0": {OPS_LINE: [["op", 100 * MS, 200 * MS],
                                           ["op", 400 * MS, 200 * MS]],
                                MODULES_LINE: [["jit__chunk_step_jit",
                                                100 * MS, 500 * MS]]}}}
    v = xtrace.View(events)
    assert not v.complete
    assert v.window_s == pytest.approx(0.5)
    assert v.busy_s() == pytest.approx(0.4)
    assert v.idle_share() == pytest.approx(0.2)
    ctx = xtrace.Context(view=v, work=10, timers={},
                         device_kind="TPU v5 lite", n_objects=100)
    for m in ("device_idle.replay", "device_idle.sweep",
              "step_us_per_req.replay", "lane_us_per_lane_req.sweep"):
        assert load_reader(m)(ctx) is None, m


def test_a_complete_trace_keeps_the_pull():
    """A trace whose ops reach the end of the call keeps the whole call as
    its window: the host's pull after the last op is idle time."""
    events = {"host": [["bench.call", 100 * MS, 1000 * MS],
                       ["bench.pull", 1090 * MS, 10 * MS]],
              "devices": {"0": {OPS_LINE: [["op", 110 * MS, 500 * MS],
                                           ["op", 700 * MS, 385 * MS]]}}}
    v = xtrace.View(events)
    assert v.complete
    assert v.window_s == pytest.approx(1.0)
    assert v.idle_share() == pytest.approx(1 - 0.885)
    ctx = xtrace.Context(view=v, work=10, timers={},
                         device_kind="TPU v5 lite", n_objects=100)
    assert load_reader("device_idle.replay")(ctx) == pytest.approx(11.5)

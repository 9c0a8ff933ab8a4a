"""The readers of the program's own host spans (``bench/program_spans.py``)
on hand-made events."""
import pytest
from test_bench_trace import DATA, EVENTS, MS

from bench import xtrace
from bench.cell import load_reader
from bench.program_spans import EXECUTE
from bench.xtrace import OPS_LINE

REPLAY = ("stream_init_idle.replay", "chunk_feed_idle.replay")
SWEEP = ("prologue_idle.sweep", "dispatch_idle.sweep")
READERS = REPLAY + SWEEP + ("prologue_launches.sweep",)

# one chip, ns; the window is [100, 1100) and the chip is busy in
# [150,180) + [280,600) + [700,1050): 700 ns, so idle 30% of the window
OPS = [["op", 150, 30], ["op", 280, 200], ["op", 400, 200],
       ["op", 700, 350]]


def _ctx(host, ops=OPS, lo=100, hi=1100):
    events = {"host": [["bench.call", lo, hi - lo]] + host,
              "devices": {"0": {OPS_LINE: ops}}}
    return xtrace.Context(view=xtrace.View(events), work=10, timers={},
                          device_kind="TPU v5 lite", n_objects=100)


def _replay_host(init="repro.stream.init", prep="repro.stream.prep",
                 dispatch="repro.stream.dispatch"):
    return [[init, 100, 100],                               # idle 70
            [prep, 200, 50], [dispatch, 250, 50],           # idle 50 + 30
            [prep, 400, 50], [dispatch, 450, 50]]           # busy


def _read(name, ctx):
    return load_reader(name)(ctx)


def test_replay_idle_split():
    ctx = _ctx(_replay_host())
    assert _read("device_idle.replay", ctx) == pytest.approx(30.0)
    assert _read("stream_init_idle.replay", ctx) == pytest.approx(7.0)
    assert _read("chunk_feed_idle.replay", ctx) == pytest.approx(8.0)


def test_sweep_idle_split_and_launches():
    host = [["repro.sweep.prologue", 100, 200],             # idle 150
            [EXECUTE, 120, 5], [EXECUTE, 290, 5],
            ["repro.sweep.dispatch", 600, 150],             # idle 100
            [EXECUTE, 650, 5]]
    ctx = _ctx(host)
    assert _read("prologue_idle.sweep", ctx) == pytest.approx(15.0)
    assert _read("dispatch_idle.sweep", ctx) == pytest.approx(10.0)
    assert _read("prologue_launches.sweep", ctx) == 2.0


def test_launches_are_per_traced_call():
    host = [["bench.call", 1100, 1000], ["repro.sweep.prologue", 100, 50],
            ["repro.sweep.prologue", 1100, 50]] + [
        [EXECUTE, t, 1] for t in (110, 120, 130, 1110, 1160)]
    ops = OPS + [["op", 1200, 890]]
    ctx = _ctx(host, ops=ops)
    assert _read("prologue_launches.sweep", ctx) == 2.0


def test_spans_clip_to_the_window_and_overlaps_count_once():
    # a span reaching before the window counts from its start; a nested
    # duplicate of the same span adds nothing
    host = [["repro.sweep.prologue", 0, 200],
            ["repro.sweep.prologue", 110, 20]]
    ctx = _ctx(host)
    assert _read("prologue_idle.sweep", ctx) == pytest.approx(7.0)


@pytest.mark.parametrize("cell, names", [("replay", REPLAY),
                                         ("sweep", SWEEP)])
def test_idle_shares_sum_to_no_more_than_device_idle(cell, names):
    host = _replay_host() if cell == "replay" else _replay_host(
        "repro.sweep.prologue", "repro.sweep.dispatch",
        "repro.sweep.dispatch")
    ops = [["op", s, 10] for s in range(100, 1100, 37)]
    ctx = _ctx(host, ops=ops)
    parts = [_read(m, ctx) for m in names]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= _read(f"device_idle.{cell}", ctx) + 1e-9


@pytest.mark.parametrize("metric", READERS)
def test_nothing_without_program_spans(metric):
    """A trace of a program without ``repro.*`` spans reads nothing."""
    ctx = xtrace.Context(view=xtrace.View(EVENTS), work=10, timers={},
                         device_kind="TPU v5 lite", n_objects=100)
    assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric", READERS)
def test_nothing_from_a_cut_trace(metric):
    host = _replay_host() + [["repro.sweep.prologue", 100 * MS, 10 * MS],
                             ["repro.sweep.dispatch", 120 * MS, 10 * MS],
                             [EXECUTE, 101 * MS, 1]]
    ctx = _ctx(host, ops=[["op", 100 * MS, 200 * MS]], lo=100 * MS,
               hi=1100 * MS)
    assert not ctx.view.complete
    assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric", REPLAY + SWEEP)
def test_idle_readers_need_a_chip(metric):
    events = {"host": [["bench.call", 100, 1000]] + _replay_host() + [
        ["repro.sweep.prologue", 100, 200], ["repro.sweep.dispatch", 600,
                                             150]], "devices": {}}
    ctx = xtrace.Context(view=xtrace.View(events), work=10, timers={},
                         device_kind="TPU v5 lite", n_objects=100)
    assert _read(metric, ctx) is None


def test_recorded_trace_slice_with_a_program_span():
    """On 2.5 ms of a trace recorded on a v5e, with a prologue span laid
    over the first half of its call: the idle share and the launches agree
    with a tick-by-tick count of the same events."""
    events = xtrace.load_events(DATA / "v5e_sweep_slice.json")
    call = next(h for h in events["host"] if h[0] == "bench.call")
    lo, mid = call[1], call[1] + call[2] // 2
    events["host"].append(["repro.sweep.prologue", lo, mid - lo])
    ctx = xtrace.Context(view=xtrace.View(events), work=1, timers={},
                         device_kind="TPU v5 lite", n_objects=100)
    busy = set()
    for _, s, d in events["devices"]["0"][OPS_LINE]:
        busy.update(range(max(s, lo), min(s + d, mid)))
    want = 100.0 * ((mid - lo) - len(busy)) / call[2]
    assert _read("prologue_idle.sweep", ctx) == pytest.approx(want)
    assert _read("prologue_launches.sweep", ctx) == sum(
        h[0] == EXECUTE and lo <= h[1] < mid for h in events["host"])

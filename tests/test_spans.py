"""The program's own names in a profiler trace (``repro.core.spans``).

Host spans land on the profiler's host plane once per call or chunk, with
no name outside ``SPANS``; device scopes reach the compiled program's
``op_name`` metadata.  Neither changes a result (the parity suites pin
that).
"""
import collections
import glob
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import PolicyParams, simulate_stream, sweep_grid
from repro.core.simulator import _chunk_step_jit, _slot_chunk_step_jit
from repro.core.spans import PREFIX, SCOPES, SPANS, scope, span
from repro.core.state import init_slot_state, init_state
from repro.core.trace import stream_of_trace
from repro.data.traces import SyntheticSpec, synthetic_trace


def _trace(n_requests=300, n_objects=20):
    spec = SyntheticSpec(n_objects=n_objects, n_requests=n_requests,
                         rate=300.0, size_min=1.0, size_max=20.0,
                         latency_base=0.01, latency_per_mb=1e-3)
    return synthetic_trace(jax.random.key(0), spec)


def _host_events(trace_dir):
    """``(name, start_ns, end_ns)`` of every event on the host planes of
    the newest trace under ``trace_dir``."""
    path = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host")
            for line in plane.lines for e in line.events]


def _host_spans(trace_dir) -> collections.Counter:
    """Count of every ``repro.*`` event on the host planes of the newest
    trace under ``trace_dir``."""
    return collections.Counter(n for n, _, _ in _host_events(trace_dir)
                               if n.startswith(PREFIX))


@pytest.mark.parametrize("prefetch", [True, False])
def test_stream_spans_once_per_call_and_chunk(tmp_path, prefetch):
    stream = stream_of_trace(_trace())
    run = lambda: simulate_stream(stream, 60.0, "lru", chunk_size=100,
                                  prefetch=prefetch)
    jax.block_until_ready(run())          # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run())
    assert _host_spans(tmp_path) == {"repro.stream.init": 1,
                                     "repro.stream.prep": 3,
                                     "repro.stream.dispatch": 3}


def test_sweep_spans_once_per_call(tmp_path):
    trace = _trace()
    run = lambda: sweep_grid(trace, 60.0, ["lru", "stoch_vacdh"],
                             [PolicyParams()])
    jax.block_until_ready(run().result)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run().result)
    assert _host_spans(tmp_path) == {"repro.sweep.prologue": 1,
                                     "repro.sweep.dispatch": 1}


# the CPU runtime's host event for one launch of a compiled program
CPU_EXECUTE = "PjRtCpuExecutable::Execute"


def test_sweep_prologue_launches_one_lane_setup(tmp_path):
    """A roster-shaped grid (11 policies x 1 x 1 x 1) launches at most 3
    compiled programs while the host is in ``repro.sweep.prologue``: the
    lanes are built by one program, not per leaf."""
    trace = _trace()
    names = ["lru", "lfu", "lhd", "adaptsize", "lru_mad", "lhd_mad", "lac",
             "cala", "vacdh", "lrb_lite", "stoch_vacdh"]
    run = lambda: sweep_grid(trace, 60.0, names, [PolicyParams(omega=1.0)],
                             estimate_z=True)
    jax.block_until_ready(run().result)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run().result)
    events = _host_events(tmp_path)
    (lo, hi), = [(s, e) for n, s, e in events
                 if n == PREFIX + "sweep.prologue"]
    launches = [s for n, s, _ in events if n == CPU_EXECUTE and lo <= s < hi]
    assert 1 <= len(launches) <= 3


def test_names_come_from_the_tuples():
    assert set(SPANS).isdisjoint(SCOPES)
    with pytest.raises(KeyError):
        span("stream.unknown")
    with pytest.raises(KeyError):
        scope("unknown")


def test_scan_body_scopes_reach_the_compiled_program():
    """Every device scope names ops of the chunk-step program the replay
    runs (the rank-select branch and both evict loops included): the dense
    program carries every scope but the slot table's lookup, the slot
    program every scope."""
    trace = _trace(n_requests=64)
    state = init_state(trace.n_objects, jax.numpy.float32(60.0),
                       jax.random.key(0), trace.z_mean)
    compiled = _chunk_step_jit.lower(
        state, trace.times, trace.objs.astype("int32"), trace.z_draw, None,
        jax.numpy.float32(0.0), trace.sizes, PolicyParams(),
        policy_name="stoch_vacdh", estimate_z=True, score_mode="rank",
        evict_top=4).compile()
    slots = init_slot_state(64, jax.numpy.float32(60.0), jax.random.key(0))
    compiled_slots = _slot_chunk_step_jit.lower(
        slots, trace.times, trace.objs.astype("int32"), trace.z_draw, None,
        jax.numpy.float32(0.0), trace.sizes, trace.z_mean, PolicyParams(),
        policy_name="stoch_vacdh", estimate_z=True,
        score_mode="rank").compile()
    for program, scopes in ((compiled, set(SCOPES) - {"slot_lookup"}),
                            (compiled_slots, SCOPES)):
        names = set(re.findall(r'op_name="([^"]*)"', program.as_text()))
        for s in scopes:
            assert any(f"/{PREFIX}{s}/" in n for n in names), s

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
from repro.core.simulator import _chunk_step_jit
from repro.core.spans import PREFIX, SCOPES, SPANS, scope, span
from repro.core.state import init_state
from repro.core.trace import stream_of_trace
from repro.data.traces import SyntheticSpec, synthetic_trace


def _trace(n_requests=300, n_objects=20):
    spec = SyntheticSpec(n_objects=n_objects, n_requests=n_requests,
                         rate=300.0, size_min=1.0, size_max=20.0,
                         latency_base=0.01, latency_per_mb=1e-3)
    return synthetic_trace(jax.random.key(0), spec)


def _host_spans(trace_dir) -> collections.Counter:
    """Count of every ``repro.*`` event on the host planes of the newest
    trace under ``trace_dir``."""
    path = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    return collections.Counter(
        e.name for plane in data.planes if plane.name.startswith("/host")
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX))


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


def test_names_come_from_the_tuples():
    assert set(SPANS).isdisjoint(SCOPES)
    with pytest.raises(KeyError):
        span("stream.unknown")
    with pytest.raises(KeyError):
        scope("unknown")


def test_scan_body_scopes_reach_the_compiled_program():
    """Every device scope names ops of the chunk-step program the replay
    runs (the rank-select branch and both evict loops included)."""
    trace = _trace(n_requests=64)
    state = init_state(trace.n_objects, jax.numpy.float32(60.0),
                       jax.random.key(0), trace.z_mean)
    compiled = _chunk_step_jit.lower(
        state, trace.times, trace.objs.astype("int32"), trace.z_draw, None,
        jax.numpy.float32(0.0), trace.sizes, PolicyParams(),
        policy_name="stoch_vacdh", estimate_z=True, score_mode="rank",
        evict_top=4).compile()
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for s in SCOPES:
        assert any(f"/{PREFIX}{s}/" in n for n in names), s

"""The slot table's own counters and the slot path's shared host loop.

``SlotView`` counts the keys it gives a slot (``n_inserts``) and the
inserts that found the table full (``n_reclaims``); a slot replay's result
carries both after the dense fields.  Over a large key universe of which a
replay touches a part, the slot replay equals the dense replay of the same
requests relabelled to dense ids in ascending raw-id order (the order
every tie breaks in), and inserts each distinct key once.  The slot path
runs ``simulate_stream``'s one host loop, under the same ``repro.stream.*``
spans as the dense path, bitwise equal to the loop it had of its own.
"""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import SlotResult, simulate_stream
from repro.core.simulator import (_slot_chunk_step_jit, _stream_chunks,
                                  resolve_chunk_size)
from repro.core.spans import PREFIX
from repro.core.state import init_slot_state, slot_table_size
from repro.core.trace import RequestStream

N_KEYS = 20_000
CAPACITY = 400.0


def _stream(n_requests=4096, seed=0, n_keys=N_KEYS):
    """Zipf(0.9) requests over ``n_keys`` raw ids, lognormal sizes (MB),
    float64 arrival times from an epoch-scale origin."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -0.9
    ids = rng.permutation(n_keys).astype(np.int32)
    objs = ids[rng.choice(n_keys, n_requests, p=p / p.sum())]
    sizes = np.minimum(rng.lognormal(0.0, 1.2, n_keys), 512.0)
    sizes = sizes.astype(np.float32)
    z_mean = (0.005 + 2e-4 * sizes).astype(np.float32)
    times = 1.7e9 + np.cumsum(rng.exponential(1 / 2000.0, n_requests))
    unit = rng.exponential(1.0, n_requests).astype(np.float32)
    return RequestStream(times=times, objs=objs, sizes=sizes, z_mean=z_mean,
                         z_draw=(z_mean[objs] * unit).astype(np.float32))


def _relabelled(stream):
    keys, dense = np.unique(stream.objs, return_inverse=True)
    return stream._replace(objs=dense.astype(np.int32),
                           sizes=stream.sizes[keys],
                           z_mean=stream.z_mean[keys]), keys.size


def _assert_same(a, b, msg=""):
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                      err_msg=msg)


KW = dict(estimate_z=True, chunk_size=1024)


@pytest.mark.parametrize("policy", ["stoch_vacdh", "lru"])
def test_slot_replay_of_a_large_universe_equals_relabelled_dense(policy):
    stream = _stream()
    dense_stream, n_distinct = _relabelled(stream)
    dense = simulate_stream(dense_stream, CAPACITY, policy, evict_top=0,
                            **KW)
    slots = simulate_stream(stream, CAPACITY, policy, state_mode="slots",
                            n_slots=slot_table_size(n_distinct, load=0.75),
                            **KW)
    assert isinstance(slots, SlotResult) and len(slots) == len(dense) + 2
    _assert_same(dense, slots, policy)
    assert int(slots.n_evictions) > 0         # the phase-2 argmin ran
    assert int(slots.n_inserts) == n_distinct
    assert int(slots.n_reclaims) == 0
    assert int(slots.n_requests) == stream.n_requests


def test_a_table_below_the_key_count_reclaims():
    stream = _stream(n_requests=2048)
    n_distinct = np.unique(stream.objs).size
    r = simulate_stream(stream, CAPACITY, "stoch_vacdh", state_mode="slots",
                        n_slots=256, **KW)
    assert n_distinct > 256
    assert int(r.n_reclaims) > 0
    # every key beyond the table's slots took an occupied one
    assert int(r.n_inserts) >= n_distinct
    assert int(r.n_inserts) - int(r.n_reclaims) == 256


def _own_loop(stream, capacity, policy, params, key, estimate_z, chunk_size,
              rebase, n_slots, prefetch):
    """The slot path's own chunk and prefetch loop, as it was before
    ``simulate_stream`` served both modes with one loop."""
    times64 = np.asarray(stream.times, np.float64)
    objs = np.asarray(stream.objs, np.int32)
    z_draw = np.asarray(stream.z_draw, np.float32)
    sizes_full = jnp.asarray(stream.sizes, jnp.float32)
    z_prior_full = jnp.asarray(stream.z_mean, jnp.float32)
    state = init_slot_state(int(n_slots), jnp.float32(capacity),
                            jnp.asarray(key).copy(), 0)

    def dispatch(state, chunk):
        t, i, z, valid, delta = chunk
        return _slot_chunk_step_jit(state, t, i, z, valid, delta, sizes_full,
                                    z_prior_full, params, policy, estimate_z,
                                    "rank")

    chunks = _stream_chunks(times64, objs, z_draw, chunk_size, rebase)
    if prefetch:
        pending = next(chunks, None)
        while pending is not None:
            cur, pending = pending, next(chunks, None)
            state = dispatch(state, cur)
    else:
        for cur in chunks:
            state = dispatch(state, cur)
    return state


@pytest.mark.parametrize("prefetch", [True, False])
@pytest.mark.parametrize("chunk_size", [1000, 4096])
def test_one_loop_is_bitwise_the_slot_path_own(prefetch, chunk_size):
    """Chunk 1000 leaves a padded tail; 4096 is one whole chunk."""
    from repro.core import PolicyParams
    stream = _stream()
    key = jax.random.key(3)
    params = PolicyParams(omega=1.0)
    got = simulate_stream(stream, CAPACITY, "stoch_vacdh", params, key=key,
                          estimate_z=True, chunk_size=chunk_size,
                          prefetch=prefetch, state_mode="slots",
                          n_slots=8192)
    st = _own_loop(stream, CAPACITY, "stoch_vacdh", params, key, True,
                   resolve_chunk_size(chunk_size, stream.n_requests), True,
                   8192, prefetch)
    s = st.sim
    _assert_same(got, (s.lat_sum, s.n_hits, s.n_delayed, s.n_misses,
                       s.n_evictions, st.tab.n_inserts, st.tab.n_reclaims))


def _host_spans(trace_dir) -> collections.Counter:
    path = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    return collections.Counter(
        e.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host") for line in plane.lines
        for e in line.events if e.name.startswith(PREFIX))


@pytest.mark.parametrize("prefetch", [True, False])
def test_slot_path_spans_once_per_call_and_chunk(tmp_path, prefetch):
    stream = _stream(n_requests=300, n_keys=500)
    run = lambda: simulate_stream(stream, 60.0, "lru", chunk_size=100,
                                  prefetch=prefetch, state_mode="slots")
    jax.block_until_ready(run())          # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run())
    assert _host_spans(tmp_path) == {"repro.stream.init": 1,
                                     "repro.stream.prep": 3,
                                     "repro.stream.dispatch": 3}

"""The per-object state as one ``[F, N]`` block (DESIGN.md §2).

The compiled engines carry the 14 :class:`ObjStats` fields as the rows of
one float32 block, so that a request reads its object as one column and
writes it back as one column.  These tests pin the layout: packing and
unpacking are bitwise exact, the time rebase moves exactly the four time
rows, every update lowering replays a short trace to the counters and
latency sum the per-field layout gave, and the chunk programs keep their
writes to the state few.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PolicyParams, simulate, sweep_grid
from repro.core.simulator import _chunk_step_jit, _slot_chunk_step_jit
from repro.core.state import (N_FIELDS, ObjStats, get_col, init_slot_state,
                              init_state, obj_record, obj_rows, pack_record,
                              pack_rows, set_lane_backend, shift_times)
from repro.data.traces import SyntheticSpec, synthetic_trace

SPEC = SyntheticSpec(n_objects=24, n_requests=600, rate=300.0,
                     size_min=1.0, size_max=20.0,
                     latency_base=0.01, latency_per_mb=1e-3,
                     stochastic=True)
TIME_FIELDS = ("complete_t", "issue_t", "last_access", "first_access")


def _bits(x):
    x = np.asarray(x)
    return x if x.dtype == bool else x.view(np.uint32)


def _assert_obj_equal(a: ObjStats, b: ObjStats):
    for name, fa, fb in zip(ObjStats._fields, a, b):
        assert np.asarray(fa).dtype == np.asarray(fb).dtype, name
        np.testing.assert_array_equal(_bits(fa), _bits(fb), err_msg=name)


def _mid_replay():
    """The dense state after 300 requests of a replay with evictions."""
    trace = synthetic_trace(jax.random.key(11), SPEC)
    st = init_state(trace.n_objects, jnp.float32(60.0), jax.random.key(0),
                    trace.z_mean)
    return _chunk_step_jit(
        st, trace.times[:300], trace.objs[:300].astype(jnp.int32),
        trace.z_draw[:300], None, jnp.float32(0.0), trace.sizes,
        PolicyParams(omega=1.0), policy_name="lru_mad", estimate_z=True,
        score_mode="rank")


def _random_obj():
    rng = np.random.default_rng(7)
    n = 37
    f = lambda: rng.standard_normal(n).astype(np.float32)
    special = np.array([np.inf, -np.inf, -0.0, 0.0, 1e-38, 3.4e38],
                       np.float32)
    return ObjStats(*(
        jnp.asarray(rng.standard_normal(n) > 0) if k < 2
        else jnp.asarray(np.concatenate([special, f()[len(special):]]))
        for k in range(N_FIELDS)))


STATES = {
    "init_state": lambda: init_state(24, jnp.float32(60.0),
                                     jax.random.key(0),
                                     jnp.linspace(0.01, 0.3, 24)),
    "init_slot_state": lambda: init_slot_state(
        64, jnp.float32(60.0), jax.random.key(0)).sim,
    "init_slot_state_slabs": lambda: init_slot_state(
        2048, jnp.float32(60.0), jax.random.key(0)).sim,
    "mid_replay": _mid_replay,
}


@pytest.mark.parametrize("which", sorted(STATES))
def test_block_round_trips_bitwise(which):
    """Rows unpack and repack to the same bits, and each object's column
    is its record: the view the scoring code reads and the record the
    serve and commit steps compute on hold the stored bits exactly."""
    st = STATES[which]()
    o = st.obj
    n = o.count.shape[0]
    assert st.block.shape == ((N_FIELDS, n // 128, 128) if n % 1024 == 0
                              else (N_FIELDS, n))
    assert o.cached.dtype == jnp.bool_ and o.in_flight.dtype == jnp.bool_
    np.testing.assert_array_equal(_bits(pack_rows(o)), _bits(st.block))
    _assert_obj_equal(obj_rows(pack_rows(o)), o)
    for j in (0, 5, o.count.shape[0] - 1):
        col = get_col(st.block, jnp.int32(j))
        r = obj_record(col)
        _assert_obj_equal(r, ObjStats(*(f[j] for f in o)))
        np.testing.assert_array_equal(_bits(pack_record(r)), _bits(col))


def test_objstats_round_trips_bitwise():
    """An ObjStats with infinities, signed zeros, extremes and flags packs
    into the block and unpacks to itself."""
    o = _random_obj()
    _assert_obj_equal(obj_rows(pack_rows(o)), o)


def test_mid_replay_state_is_not_trivial():
    """The mid-replay case above covers the values a replay writes."""
    o = _mid_replay().obj
    assert bool(jnp.any(o.cached)) and bool(jnp.any(o.agg_cnt > 0))
    assert bool(jnp.any(jnp.isinf(o.complete_t)))
    assert bool(jnp.any(jnp.isfinite(o.complete_t)))


@pytest.mark.parametrize("delta", [0.0, 1.7e9])
@pytest.mark.parametrize("which", ["init_state", "mid_replay"])
def test_shift_times_moves_exactly_the_time_rows(which, delta):
    """The rebase subtracts ``delta`` from the four time-point rows and
    leaves every other row's bits alone; at 0.0 it is a bitwise no-op."""
    st = STATES[which]()
    d = jnp.float32(delta)
    got = jax.jit(shift_times)(st, d)
    o = st.obj
    want = o._replace(**{k: np.asarray(getattr(o, k)) - np.float32(delta)
                         for k in TIME_FIELDS})
    _assert_obj_equal(got.obj, ObjStats(*(jnp.asarray(f) for f in want)))
    np.testing.assert_array_equal(
        _bits(got.min_complete),
        _bits(np.asarray(st.min_complete) - np.float32(delta)))
    if delta == 0.0:
        np.testing.assert_array_equal(_bits(got.block), _bits(st.block))


# The per-field layout's answers on this trace (every field its own [N]
# array, each write its own scatter): hits, delayed hits, misses,
# evictions, then the Kahan latency sum's exact bits.
NAMES = ("lru", "stoch_vacdh", "lru_mad", "adaptsize")
PER_FIELD = {
    "lru": ((236, 85, 279, 273), "0x1.e281300000000p+2"),
    "stoch_vacdh": ((280, 70, 250, 193), "0x1.b5c6a40000000p+2"),
    "lru_mad": ((285, 73, 242, 197), "0x1.bb46ca0000000p+2"),
    "adaptsize": ((257, 83, 260, 177), "0x1.c3ac780000000p+2"),
}


def _check_lane(res, k, name):
    counts, lat = PER_FIELD[name]
    got = tuple(int(np.asarray(f).reshape(-1)[k]) for f in res[1:])
    assert got == counts, name
    assert float(np.asarray(res.total_latency).reshape(-1)[k]).hex() == lat, \
        name


@pytest.mark.parametrize("mode", ["scatter", "onehot", "lane",
                                  "lane_kernel_interpret"])
def test_update_mode_replays_to_the_per_field_answers(mode):
    """Each column-write lowering — the dynamic-update-slice, the one-hot
    select over the block, the lane scatter (jnp and the interpreted
    Pallas kernel) — replays four policies as lanes of one sweep to the
    per-field layout's counters and latency bits."""
    trace = synthetic_trace(jax.random.key(11), SPEC)
    update = mode.split("_")[0]
    if mode == "lane_kernel_interpret":
        set_lane_backend("kernel_interpret")
    jax.clear_caches()
    try:
        res = sweep_grid(trace, 60.0, list(NAMES), [PolicyParams(omega=1.0)],
                         seeds=(0,), update=update, estimate_z=True).result
    finally:
        set_lane_backend("scatter")
        jax.clear_caches()
    for k, name in enumerate(NAMES):
        _check_lane(res, k, name)
    if mode == "scatter":       # and the unbatched engine, policy by policy
        for name in NAMES:
            r = simulate(trace, 60.0, name, PolicyParams(omega=1.0),
                         key=jax.random.key(0), estimate_z=True)
            _check_lane(r, 0, name)


# Writes to the state in one lowered chunk program (StableHLO scatter and
# dynamic-update-slice ops).  The per-field layout lowered 18 scatters in
# the dense program and 33 in the slot program (36 and 66 matches of
# "stablehlo.scatter", which names each op twice).  The block lowers five
# column writes in the dense program — the serve, the commit's finalize,
# a victim in each evict loop, the admission — and in the slot program
# four (no precomputed victim order, so one evict loop) plus the insert's
# column and the table's key and size.  A ceiling far below the per-field
# count keeps a later change from unpacking the block unnoticed.
WRITE_CEILING = {"dense_lru": 5, "dense_stoch_vacdh": 5, "slots": 7}


def _writes(lowered) -> int:
    text = lowered.as_text()
    return (text.count('"stablehlo.scatter"(')
            + text.count("stablehlo.dynamic_update_slice "))


@pytest.mark.parametrize("program", sorted(WRITE_CEILING))
def test_chunk_programs_write_the_state_in_columns(program):
    n, chunk = 100, 64
    t = jnp.zeros(chunk, jnp.float32)
    i = jnp.zeros(chunk, jnp.int32)
    ones = jnp.ones(n, jnp.float32)
    if program == "slots":
        st = init_slot_state(64, jnp.float32(100.0), jax.random.key(0))
        lowered = _slot_chunk_step_jit.lower(
            st, t, i, t, None, jnp.float32(0.0), ones, ones, PolicyParams(),
            policy_name="stoch_vacdh", estimate_z=True, score_mode="ref")
    else:
        policy, mode = (("lru", "rank") if program == "dense_lru"
                        else ("stoch_vacdh", "ref"))
        st = init_state(n, jnp.float32(100.0), jax.random.key(0), ones)
        lowered = _chunk_step_jit.lower(
            st, t, i, t, None, jnp.float32(0.0), ones, PolicyParams(),
            policy_name=policy, estimate_z=True, score_mode=mode)
    assert _writes(lowered) <= WRITE_CEILING[program]

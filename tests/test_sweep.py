"""The batched sweep engine (repro.core.sweep) vs per-point simulate.

The contract is *bitwise* equality: batching must change dispatch structure
only, never per-lane arithmetic — for the single-policy vmap path, the
unified multi-policy graph (traced policy index + flag selects), lane
padding, and stacked-trace batching alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Erlang, PolicyParams, make_hier_trace, simulate,
                        simulate_hier, sweep_grid, sweep_hier_grid)
from repro.data.traces import SyntheticSpec, synthetic_trace

SPEC = SyntheticSpec(n_objects=40, n_requests=2500, rate=600.0,
                     size_min=1.0, size_max=20.0,
                     latency_base=0.01, latency_per_mb=1e-3)


def _trace(seed=0, **kw):
    import dataclasses
    spec = dataclasses.replace(SPEC, **kw) if kw else SPEC
    return synthetic_trace(jax.random.key(seed), spec)


def _assert_point_matches(grid, trace_list, names, params_list, caps, seeds,
                          estimate_z):
    for ti, tr in enumerate(trace_list):
        for li, pol in enumerate(names):
            for pi, p in enumerate(params_list):
                for ci, c in enumerate(caps):
                    for si, s in enumerate(seeds):
                        ref = simulate(tr, c, pol, p,
                                       key=jax.random.key(s),
                                       estimate_z=estimate_z)
                        got = grid.point(ti, li, pi, ci, si)
                        assert float(got.total_latency) == \
                            float(ref.total_latency), (pol, pi, ci, si)
                        for f in ("n_hits", "n_delayed", "n_misses",
                                  "n_evictions"):
                            assert int(getattr(got, f)) == \
                                int(getattr(ref, f)), (pol, f)


def test_single_policy_grid_bitwise_matches_simulate():
    trace = _trace()
    params = [PolicyParams(omega=o) for o in (0.0, 1.0, 2.0)]
    caps = [60.0, 150.0]
    g = sweep_grid(trace, caps, "stoch_vacdh", params, seeds=(0,),
                   estimate_z=True)
    assert g.result.total_latency.shape == (1, 1, 3, 2, 1)
    _assert_point_matches(g, [trace], ["stoch_vacdh"], params, caps, [0],
                          estimate_z=True)


def test_multi_policy_grid_bitwise_matches_simulate():
    """The unified graph (traced policy lane) must agree with each policy's
    statically specialized graph — including GreedyDual and AdaptSize."""
    trace = _trace()
    names = ["lru", "lfu", "lac", "vacdh", "stoch_vacdh", "lru_mad",
             "adaptsize"]
    params = [PolicyParams(omega=1.0)]
    g = sweep_grid(trace, 100.0, names, params, seeds=(0,))
    assert g.result.total_latency.shape == (1, len(names), 1, 1, 1)
    _assert_point_matches(g, [trace], names, params, [100.0], [0],
                          estimate_z=False)


def test_stacked_traces_and_seeds_bitwise_match():
    traces = [_trace(seed=s) for s in (0, 1, 2)]
    params = [PolicyParams(omega=1.0)]
    seeds = (0, 7)
    g = sweep_grid(traces, 80.0, "vacdh", params, seeds=seeds)
    assert g.result.total_latency.shape == (3, 1, 1, 1, 2)
    _assert_point_matches(g, traces, ["vacdh"], params, [80.0], list(seeds),
                          estimate_z=False)


def test_lane_padding_is_transparent():
    trace = _trace()
    params = [PolicyParams(omega=o) for o in (0.0, 2.0)]
    g_pad = sweep_grid(trace, 100.0, ["lru", "stoch_vacdh"], params,
                       lane_bucket=12)
    g_raw = sweep_grid(trace, 100.0, ["lru", "stoch_vacdh"], params)
    for a, b in zip(g_pad.result, g_raw.result):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resid_axis_sweeps_in_one_grid():
    """'rate' vs 'recency' is a traced leaf — one grid, two estimators."""
    trace = _trace()
    params = [PolicyParams(omega=1.0, resid=m) for m in ("rate", "recency")]
    g = sweep_grid(trace, 100.0, "stoch_vacdh", params)
    _assert_point_matches(g, [trace], ["stoch_vacdh"], params, [100.0], [0],
                          estimate_z=False)
    # the two estimators genuinely differ on this workload
    assert float(g.result.total_latency[0, 0, 0, 0, 0]) != \
        float(g.result.total_latency[0, 0, 1, 0, 0])


def test_distribution_parameter_axis():
    """An Erlang-k grid rides the params axis of one compiled graph."""
    trace = _trace()
    params = [PolicyParams(omega=1.0, dist=Erlang(k=k))
              for k in (1.0, 2.0, 8.0)]
    g = sweep_grid(trace, 100.0, "stoch_vacdh", params, estimate_z=True)
    _assert_point_matches(g, [trace], ["stoch_vacdh"], params, [100.0], [0],
                          estimate_z=True)


def test_mixed_param_structure_rejected():
    from repro.core import Hyperexponential
    trace = _trace()
    with pytest.raises(ValueError, match="static structure"):
        sweep_grid(trace, 100.0, "stoch_vacdh",
                   [PolicyParams(dist=Erlang(k=2.0)),
                    PolicyParams(dist=Hyperexponential())])


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown policies"):
        sweep_grid(_trace(), 100.0, ["lru", "belady"], [PolicyParams()])


def test_kernel_rejected_for_multi_policy():
    with pytest.raises(ValueError, match="single-policy"):
        sweep_grid(_trace(), 100.0, ["lru", "stoch_vacdh"], [PolicyParams()],
                   use_kernel="ref")


def _assert_hier_point_matches(g, ht, n_shards, names, params_list, c1s, c2s,
                               seeds, l2_policy="lru"):
    for li, pol in enumerate(names):
        for pi, p in enumerate(params_list):
            for i1, c1 in enumerate(c1s):
                for i2, c2 in enumerate(c2s):
                    for si, s in enumerate(seeds):
                        ref = simulate_hier(ht, n_shards, c1, c2, pol,
                                            l2_policy=l2_policy, params=p,
                                            key=jax.random.key(s))
                        got = g.point(0, li, pi, i1, i2, si)
                        for fg, fr in zip(got.per_shard, ref.per_shard):
                            np.testing.assert_array_equal(
                                np.asarray(fg), np.asarray(fr),
                                err_msg=f"{pol} per_shard")
                        for fg, fr in zip(got.l2, ref.l2):
                            assert float(fg) == float(fr), (pol, "l2")


def test_hier_single_policy_grid_bitwise_matches_simulate_hier():
    """Hierarchy sweep points == per-point simulate_hier, bitwise — the
    same contract as the single-tier engine (DESIGN.md §8)."""
    ht = make_hier_trace(_trace(), 3, hop_mean=0.004, route="random",
                         key=jax.random.key(5))
    params = [PolicyParams(omega=o) for o in (0.0, 1.0)]
    c1s, c2s = [20.0, 40.0], [0.0, 90.0]
    g = sweep_hier_grid(ht, 3, c1s, c2s, "stoch_vacdh", params)
    assert g.result.l2.total_latency.shape == (1, 1, 2, 2, 2, 1)
    assert g.result.per_shard.total_latency.shape == (1, 1, 2, 2, 2, 1, 3)
    _assert_hier_point_matches(g, ht, 3, ["stoch_vacdh"], params, c1s, c2s,
                               [0])


def test_hier_multi_policy_grid_bitwise_matches_simulate_hier():
    ht = make_hier_trace(_trace(), 2, hop_mean=0.002, route="hash")
    names = ["lru", "vacdh", "stoch_vacdh"]
    params = [PolicyParams(omega=1.0)]
    g = sweep_hier_grid(ht, 2, 30.0, 90.0, names, params, lane_bucket=4)
    assert g.result.l2.total_latency.shape == (1, 3, 1, 1, 1, 1)
    _assert_hier_point_matches(g, ht, 2, names, params, [30.0], [90.0], [0])


def test_hier_params_axis_with_params_sensitive_l2_stays_bitwise():
    """The L2 runs ONE params setting while the L1 params axis sweeps; with
    a params-sensitive L2 policy the decoupled l2_params default must keep
    every point bitwise equal to per-point simulate_hier."""
    ht = make_hier_trace(_trace(), 2, hop_mean=0.003, route="random",
                         key=jax.random.key(1))
    params = [PolicyParams(omega=o) for o in (0.0, 2.0)]
    g = sweep_hier_grid(ht, 2, 25.0, 70.0, "stoch_vacdh", params,
                        l2_policy="stoch_vacdh")
    _assert_hier_point_matches(g, ht, 2, ["stoch_vacdh"], params, [25.0],
                               [70.0], [0], l2_policy="stoch_vacdh")


def test_hier_aggregate_properties_reduce_shard_axis():
    ht = make_hier_trace(_trace(), 2, hop_mean=0.002)
    g = sweep_hier_grid(ht, 2, 30.0, [0.0, 90.0], "lru")
    assert g.result.total_latency.shape == (1, 1, 1, 1, 2, 1)
    assert np.all(np.asarray(g.result.n_requests) == SPEC.n_requests)


def test_kernel_scored_single_policy_sweep_matches():
    """The fused-kernel scoring path ('ref' backend on CPU) slots into the
    sweep engine and agrees with the jnp rank path."""
    trace = _trace()
    params = [PolicyParams(omega=o) for o in (0.0, 1.0)]
    g_k = sweep_grid(trace, 100.0, "stoch_vacdh", params, use_kernel="ref")
    g_r = sweep_grid(trace, 100.0, "stoch_vacdh", params)
    np.testing.assert_allclose(
        np.asarray(g_k.result.total_latency),
        np.asarray(g_r.result.total_latency), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(g_k.result.n_evictions),
                                  np.asarray(g_r.result.n_evictions))


# ---------------------------------------------------------------------------
# The lane set-up (sweep._flatten_lanes): one compiled program builds every
# grid input; each must equal the eager per-call construction it replaced.
# ---------------------------------------------------------------------------
ROSTER = ("lru", "lfu", "lhd", "adaptsize", "lru_mad", "lhd_mad", "lac",
          "cala", "vacdh", "lrb_lite", "stoch_vacdh")


def _eager_lanes(policy_names, params_list, cap_arrays, seeds, lane_bucket,
                 multiple=1):
    """The eager lane construction, kept as it was before the lanes were
    built in one program: a stack, meshgrid and gather per call."""
    stack = lambda ts: jax.tree.map(lambda *xs: jnp.stack(xs), *ts)
    bucket = lambda n, b: -(-n // b) * b if b else n
    dims = [len(policy_names), len(params_list),
            *[c.shape[0] for c in cap_arrays], len(seeds)]
    grids = jnp.meshgrid(*[jnp.arange(d) for d in dims], indexing="ij")
    lflat = grids[0].ravel()
    pflat = jax.tree.map(lambda x: x[grids[1].ravel()], stack(params_list))
    capflats = [c[g.ravel()] for c, g in zip(cap_arrays, grids[2:-1])]
    keys = jnp.stack([jax.random.key(s) for s in seeds])
    kflat = keys[grids[-1].ravel()]
    G = int(np.prod(dims))
    Gpad = bucket(bucket(G, lane_bucket), multiple)
    if Gpad > G:
        ext = lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (Gpad - G,) + x.shape[1:])])
        lflat, kflat = ext(lflat), ext(kflat)
        capflats = [ext(c) for c in capflats]
        pflat = jax.tree.map(ext, pflat)
    lane_policy = tuple(int(x) for x in np.asarray(lflat))
    return lflat, pflat, capflats, kflat, G, lane_policy


def _assert_same_arrays(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g.dtype, g.shape, g.weak_type) == \
            (w.dtype, w.shape, w.weak_type)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


LANE_SEEDS = (0, 2**31 - 1, 2**31 + 5, 2**32 + 3, -4)


def _lane_grid(kind, seed):
    """(traces, policies, params, capacity axes, seeds, bucket, multiple)
    of one grid shape the API accepts."""
    caps = lambda *c: jnp.asarray(np.asarray(c, np.float32))
    if kind == "roster":
        return ([_trace()], ROSTER, [PolicyParams(omega=1.0)], [caps(500.0)],
                [seed], None, 1)
    if kind == "omega_cap_seed":
        return ([_trace(), _trace(seed=1)], ("stoch_vacdh",),
                [PolicyParams(omega=o) for o in (0.0, 0.5, 2.0)],
                [caps(60.0, 150.0)], [seed, 7, 0], None, 1)
    if kind == "lane_bucket":
        return ([_trace()], ("lru", "stoch_vacdh"),
                [PolicyParams(omega=o, dist=Erlang(k=2.0)) for o in (0., 2.)],
                [caps(100.0)], [seed], 12, 1)
    if kind == "fabric4":
        return ([_trace()], ("lru", "lfu", "vacdh"), [PolicyParams()],
                [caps(40.0, 80.0)], [seed], None, 4)
    ht = make_hier_trace(_trace(), 2, hop_mean=0.002, route="hash")
    return ([ht], ("lru", "stoch_vacdh"), [PolicyParams(omega=1.0)],
            [caps(20.0, 40.0), caps(0.0, 90.0, 120.0)], [seed, 3], 8, 1)


@pytest.mark.parametrize("seed", LANE_SEEDS)
@pytest.mark.parametrize("kind", ["roster", "omega_cap_seed", "lane_bucket",
                                  "fabric4", "hier_two_caps"])
def test_lane_setup_matches_eager_construction(kind, seed):
    """Lanes, params, capacities, keys, the true lane count and the host
    lane->policy map equal the eager construction bit for bit, padding
    and seeds past 32 bits included."""
    from repro.core.sweep import _flatten_lanes
    traces, names, params, caps, seeds, bucket, multiple = \
        _lane_grid(kind, seed)
    tstack, lflat, pflat, capflats, kflat, G, lanes = _flatten_lanes(
        traces, names, params, caps, seeds, bucket, multiple)
    want = _eager_lanes(names, params, caps, seeds, bucket, multiple)
    _assert_same_arrays((lflat, pflat, capflats), want[:3])
    assert kflat.dtype == want[3].dtype
    _assert_same_arrays(jax.random.key_data(kflat),
                        jax.random.key_data(want[3]))
    assert G == want[4]
    assert tuple(lanes.tolist()) == want[5]
    _assert_same_arrays(tstack, jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *traces))


def test_lane_setup_compiles_once_per_grid_shape():
    """Another trace of the same shape reuses the compiled lane set-up;
    another grid shape compiles one more."""
    from repro.core.sweep import _lane_setup
    _lane_setup.clear_cache()
    names, params = ["lru", "stoch_vacdh"], [PolicyParams(omega=1.0)]
    sweep_grid(_trace(seed=0), 100.0, names, params)
    assert _lane_setup._cache_size() == 1
    sweep_grid(_trace(seed=1), 100.0, names, params)
    assert _lane_setup._cache_size() == 1
    sweep_grid(_trace(seed=1), [60.0, 100.0], names, params)
    assert _lane_setup._cache_size() == 2

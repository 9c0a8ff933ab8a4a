"""The benchmark's plain reference against the program's own oracle and
its compiled simulator, on small seeded traces (CPU)."""
import jax
import numpy as np
import pytest

from bench import reference as R
from repro.core import PolicyParams, simulate
from repro.core.refsim import simulate_ref
from repro.data.traces import SyntheticSpec, synthetic_trace

COUNTERS = ("n_hits", "n_delayed", "n_misses", "n_evictions")


def _trace(seed, stochastic=True):
    spec = SyntheticSpec(n_objects=40, n_requests=1500, rate=300.0,
                         size_min=1.0, size_max=20.0, latency_base=0.01,
                         latency_per_mb=1e-3, stochastic=stochastic)
    return synthetic_trace(jax.random.key(seed), spec)


def _ours(trace, cap, policy, omega=1.0, estimate_z=False, coin_seed=0):
    t = [np.asarray(x) for x in trace]
    return R.replay(t[0], t[1], t[4], t[2], t[3], cap, policy,
                    R.Params(omega=omega), estimate_z=estimate_z,
                    coin_seed=coin_seed)


@pytest.mark.parametrize("policy", [p for p in R.ROSTER if p != "adaptsize"])
@pytest.mark.parametrize("estimate_z", [False, True])
def test_reference_matches_refsim(policy, estimate_z):
    trace = _trace(11)
    got = _ours(trace, 100.0, policy, estimate_z=estimate_z)
    ref = simulate_ref(trace, 100.0, policy, PolicyParams(omega=1.0),
                       estimate_z=estimate_z)
    assert {k: got[k] for k in COUNTERS} == {k: ref[k] for k in COUNTERS}
    np.testing.assert_allclose(got["total_latency"], ref["total_latency"],
                               rtol=1e-12)


@pytest.mark.parametrize("policy", R.ROSTER)
@pytest.mark.parametrize("seed", [3, 7])
def test_reference_matches_simulator(policy, seed):
    """The program's oracle skips AdaptSize's admission coin; the compiled
    simulator on the CPU covers every roster policy, coin included."""
    trace = _trace(seed)
    got = _ours(trace, 60.0, policy, omega=0.5, coin_seed=seed)
    sim = simulate(trace, 60.0, policy, PolicyParams(omega=0.5),
                   key=jax.random.key(seed))
    assert {k: got[k] for k in COUNTERS} == {
        k: int(getattr(sim, k)) for k in COUNTERS}
    np.testing.assert_allclose(got["total_latency"],
                               float(sim.total_latency), rtol=2e-6)


def test_streamed_rebase_matches_program():
    """Chunked float64 epoch times, rebased per chunk, as simulate_stream
    states them (with the eq.-16 kernel through the Pallas interpreter)."""
    from repro.core import simulate_stream
    from repro.core.trace import stream_of_trace
    trace = _trace(5)
    s = stream_of_trace(trace)
    s = s._replace(times=s.times + 1.7e9)
    got = R.replay(s.times, s.objs, s.z_draw, s.sizes, s.z_mean, 60.0,
                   "stoch_vacdh", R.Params(omega=1.0), estimate_z=True,
                   chunk=512)
    sim = simulate_stream(s, 60.0, "stoch_vacdh", PolicyParams(omega=1.0),
                          estimate_z=True, use_kernel="interpret",
                          chunk_size=512)
    assert {k: got[k] for k in COUNTERS} == {
        k: int(getattr(sim, k)) for k in COUNTERS}
    np.testing.assert_allclose(got["total_latency"],
                               float(sim.total_latency), rtol=2e-6)


@pytest.mark.parametrize("seed", [0, 1, 12345, 2 ** 31 - 1])
def test_threefry_coins_match_jax(seed):
    key = jax.random.key(seed)
    want = []
    for _ in range(5):
        key, sub = jax.random.split(key)
        want.append(float(jax.random.uniform(sub)))
    coins = R.coin_stream(R.key_of_seed(seed))
    assert [float(next(coins)) for _ in range(5)] == want


def test_gaps():
    ref = dict(total_latency=10.0, n_hits=5, n_delayed=1, n_misses=4,
               n_evictions=2)
    assert R.gaps(ref, ref, 10) == (0.0, 0.0)
    got = dict(ref, n_hits=6, n_misses=3, total_latency=11.0)
    assert R.gaps(got, ref, 10) == (0.1, 0.1)
    assert R.gaps(dict(ref, total_latency=float("nan")), ref, 10)[1] \
        == float("inf")

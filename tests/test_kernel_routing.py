"""Which commits score through the ranking kernel, end to end.

``_kernelable`` (core/simulator.py) admits exactly one case to the kernel
family: ``stoch_vacdh`` under the Exponential law, whose Theorem-2 moments
the kernel hard-codes.  Every other policy, and ``stoch_vacdh`` under any
other law, must score through its own epilogue whatever ``use_kernel``
says, so ``use_kernel='ref'`` is bitwise invisible there — in the dense
engine and in the slot-table engine alike.

Where the kernel does run, its results must match the epilogue path: the
slot-table engine (the ``cdn_replay_exact`` path) scores through
``ranking_scores``, and the streamed dense engine through
``ranking_victim_order`` with the state carried across chunk boundaries.
"""
import jax
import numpy as np
import pytest

from repro.core import (Deterministic, Erlang, Hyperexponential,
                        PolicyParams, simulate, simulate_stream)
from repro.core.ranking import POLICIES
from repro.core.trace import stream_of_trace
from repro.data.traces import SyntheticSpec, synthetic_trace

OTHER_POLICIES = sorted(p for p in POLICIES if p != "stoch_vacdh")
OTHER_LAWS = {"deterministic": Deterministic(), "erlang2": Erlang(k=2.0),
              "erlang4": Erlang(k=4.0), "hyperexp": Hyperexponential()}

SPEC = SyntheticSpec(n_objects=24, n_requests=500, rate=300.0,
                     size_min=1.0, size_max=20.0,
                     latency_base=0.01, latency_per_mb=1e-3,
                     stochastic=True)


def _trace(seed=0):
    return synthetic_trace(jax.random.key(seed), SPEC)


def _assert_same(a, b, msg=""):
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                      err_msg=msg)


# ---------------------------------------------------------------------------
# the kernel stays out: use_kernel is bitwise invisible
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("state_mode", ["dense", "slots"])
@pytest.mark.parametrize("policy", OTHER_POLICIES)
def test_non_kernel_policy_ignores_use_kernel(policy, state_mode):
    trace = _trace()
    base = simulate(trace, 60.0, policy, estimate_z=True,
                    state_mode=state_mode)
    got = simulate(trace, 60.0, policy, estimate_z=True, use_kernel="ref",
                   state_mode=state_mode)
    _assert_same(base, got, f"{policy}/{state_mode}")
    assert int(base.n_evictions) > 0      # the scoring pass actually ran


@pytest.mark.parametrize("state_mode", ["dense", "slots"])
@pytest.mark.parametrize("law", sorted(OTHER_LAWS))
def test_non_exponential_law_keeps_kernel_out(law, state_mode):
    """The kernel's moments are Exponential-only: under any other law
    ``stoch_vacdh`` must score through its epilogue, bit for bit."""
    trace = _trace(seed=1)
    p = PolicyParams(omega=1.0, dist=OTHER_LAWS[law])
    base = simulate(trace, 60.0, "stoch_vacdh", p, estimate_z=True,
                    state_mode=state_mode)
    got = simulate(trace, 60.0, "stoch_vacdh", p, estimate_z=True,
                   use_kernel="ref", state_mode=state_mode)
    _assert_same(base, got, f"{law}/{state_mode}")
    assert int(base.n_evictions) > 0


# ---------------------------------------------------------------------------
# the kernel runs: streamed replays agree with the epilogue path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk_size", [7, 500])
@pytest.mark.parametrize("estimate_z", [False, True])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("state_mode", ["dense", "slots"])
def test_streamed_kernel_scoring_matches_rank_path(state_mode, backend,
                                                   estimate_z, chunk_size):
    """Counters equal and total latency within float32 rounding of the
    epilogue path (the kernel's arithmetic order differs from the jnp
    epilogue's), with the carry crossing chunk boundaries."""
    stream = stream_of_trace(_trace(seed=2))
    kw = dict(estimate_z=estimate_z, chunk_size=chunk_size,
              state_mode=state_mode)
    base = simulate_stream(stream, 60.0, "stoch_vacdh", **kw)
    got = simulate_stream(stream, 60.0, "stoch_vacdh", use_kernel=backend,
                          **kw)
    for field in ("n_hits", "n_delayed", "n_misses", "n_evictions"):
        assert int(getattr(got, field)) == int(getattr(base, field)), field
    np.testing.assert_allclose(float(got.total_latency),
                               float(base.total_latency), rtol=1e-6)
    assert int(base.n_evictions) > 0
    if state_mode == "slots":
        assert int(got.n_inserts) == int(base.n_inserts)
        assert int(got.n_reclaims) == int(base.n_reclaims)

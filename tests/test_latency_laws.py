"""Slot-table / dense parity under every miss-latency law.

``stoch_vacdh`` is the one policy that reads ``PolicyParams.dist``: its
eq.-16 score takes the aggregate-delay moments of the configured law.  The
slot-table engine must stay bitwise equal to the dense engine (on its
pinned ``evict_top=0`` path) under each law, with and without the
z-estimator and at both a variance-blind and a variance-heavy ``omega``.
"""
import jax
import numpy as np
import pytest

from repro.core import (Deterministic, Erlang, Hyperexponential,
                        PolicyParams, simulate)
from repro.data.traces import SyntheticSpec, synthetic_trace

LAWS = {"deterministic": Deterministic(), "erlang2": Erlang(k=2.0),
        "erlang4": Erlang(k=4.0), "hyperexp": Hyperexponential()}

SPEC = SyntheticSpec(n_objects=24, n_requests=500, rate=300.0,
                     size_min=1.0, size_max=20.0,
                     latency_base=0.01, latency_per_mb=1e-3,
                     stochastic=True)


def _assert_same(a, b, msg=""):
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                      err_msg=msg)


@pytest.mark.parametrize("omega", [0.0, 2.0])
@pytest.mark.parametrize("estimate_z", [False, True])
@pytest.mark.parametrize("law", sorted(LAWS))
def test_slot_mode_bitwise_matches_dense_per_law(law, estimate_z, omega):
    trace = synthetic_trace(jax.random.key(4), SPEC)
    p = PolicyParams(omega=omega, dist=LAWS[law])
    dense = simulate(trace, 60.0, "stoch_vacdh", p, estimate_z=estimate_z,
                     evict_top=0)
    slots = simulate(trace, 60.0, "stoch_vacdh", p, estimate_z=estimate_z,
                     state_mode="slots")
    _assert_same(dense, slots, f"{law}/z={estimate_z}/omega={omega}")
    assert int(dense.n_evictions) > 0

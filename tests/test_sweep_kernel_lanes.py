"""Sweep lanes through the kernel-routed scoring pass, per policy.

A single-policy ``sweep_grid`` accepts ``use_kernel``; its scoring backend
is routed per lane by the same eligibility rule as ``simulate``.  Each lane
of a kernel-routed grid must be bitwise equal to the per-point
``simulate(..., use_kernel='ref')`` call, for every policy the grid
accepts — the kernel-eligible ``stoch_vacdh`` and the policies that must
fall through to their own epilogue.
"""
import jax
import numpy as np
import pytest

from repro.core import PolicyParams, simulate, sweep_grid
from repro.core.ranking import POLICIES
from repro.data.traces import SyntheticSpec, synthetic_trace

ALL_POLICIES = sorted(POLICIES)
CAPS = (40.0, 60.0)
OMEGAS = (0.0, 1.0)

SPEC = SyntheticSpec(n_objects=24, n_requests=500, rate=300.0,
                     size_min=1.0, size_max=20.0,
                     latency_base=0.01, latency_per_mb=1e-3,
                     stochastic=True)


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_kernel_routed_lanes_match_simulate(policy):
    trace = synthetic_trace(jax.random.key(5), SPEC)
    params = [PolicyParams(omega=o) for o in OMEGAS]
    grid = sweep_grid(trace, CAPS, policy, params, estimate_z=True,
                      use_kernel="ref")
    for pi, p in enumerate(params):
        for ci, c in enumerate(CAPS):
            want = simulate(trace, c, policy, p, estimate_z=True,
                            use_kernel="ref")
            got = grid.point(0, 0, pi, ci, 0)
            for fg, fw in zip(got, want):
                np.testing.assert_array_equal(
                    np.asarray(fg), np.asarray(fw),
                    err_msg=f"{policy} omega={p.omega} cap={c}")
    assert int(np.asarray(grid.result.n_evictions).min()) > 0

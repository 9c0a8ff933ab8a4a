"""Cross-device bitwise-parity suite for the multi-device sweep fabric.

The fabric (repro.launch.fabric, DESIGN.md §13) shards the sweep engine's
flattened lane axis over a 1-D ``data`` mesh with ``shard_map``.  Its
contract is that device count and lane->device assignment are **bitwise
invisible** in results.  Two layers of enforcement here:

* **subprocess parity** — real multi-device meshes need
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
  initializes, so (like ``benchmarks/probe_memory.py``) the cross-count
  checks shell out to a child that forces 8 fake host devices and
  compares ``sweep_grid`` / ``sweep_hier_grid`` across
  ``devices ∈ {1, 2, 4, 8}``, non-divisible lane counts (dead-lane
  padding) and a shuffled lane->device assignment;
* **in-process parity** — a 1-device ``data`` mesh exercises the whole
  shard_map machinery (specs, key-data round-trip, gather layout) without
  forced devices, cheap enough for a hypothesis property over grid
  shapes.  ``hypothesis`` is optional (same stance as tests/test_scenarios
  .py): without it the property degrades to a direct parametrized sweep
  instead of skipping the module.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import PolicyParams, sweep_grid
from repro.data.traces import SyntheticSpec, synthetic_trace
from repro.launch.fabric import fabric_lane_multiple, resolve_fabric
from repro.launch.mesh import make_data_mesh

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dep: degrade to direct examples
    HAVE_HYPOTHESIS = False

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SPEC = SyntheticSpec(n_objects=16, n_requests=250, rate=600.0,
                     latency_base=0.01, latency_per_mb=1e-3)


def _trace(seed=0):
    return synthetic_trace(jax.random.key(seed), SPEC)


def _grids_equal(a, b):
    la, lb = jax.tree.leaves(a.result), jax.tree.leaves(b.result)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# --- subprocess cross-device parity ------------------------------------

_CHILD = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
import jax
import numpy as np
from repro.core import PolicyParams, sweep_grid, sweep_hier_grid, \
    make_hier_trace
from repro.data.traces import SyntheticSpec, synthetic_trace
from repro.launch.mesh import make_data_mesh

assert jax.device_count() == 8, jax.device_count()
spec = SyntheticSpec(n_objects=16, n_requests=250, rate=600.0,
                     latency_base=0.01, latency_per_mb=1e-3)
trace = synthetic_trace(jax.random.key(0), spec)
params = [PolicyParams(omega=o) for o in (0.0, 1.0, 2.0)]
caps = [30.0, 60.0]          # G = 6 lanes: non-divisible by 4 and 8

def eq(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a.result),
                               jax.tree.leaves(b.result)))

checks = {}
mode = sys.argv[2]
if mode == "single":
    base = sweep_grid(trace, caps, "stoch_vacdh", params, estimate_z=True)
    for d in (1, 2, 4, 8):
        g = sweep_grid(trace, caps, "stoch_vacdh", params, estimate_z=True,
                       devices=d)
        checks[f"d{d}"] = eq(g, base)
    # shuffled lane->device assignment: reversed 4-device mesh
    perm = make_data_mesh(devices=list(reversed(jax.devices()[:4])))
    checks["shuffled"] = eq(
        sweep_grid(trace, caps, "stoch_vacdh", params, estimate_z=True,
                   mesh=perm), base)
else:
    base = sweep_grid(trace, 40.0, ["lru", "lfu", "stoch_vacdh"],
                      [PolicyParams(omega=1.0)], seeds=(0, 1))
    for d in (2, 8):         # G = 6 lanes again (3 policies x 2 seeds)
        checks[f"multi_d{d}"] = eq(
            sweep_grid(trace, 40.0, ["lru", "lfu", "stoch_vacdh"],
                       [PolicyParams(omega=1.0)], seeds=(0, 1), devices=d),
            base)
    ht = make_hier_trace(trace, 2, hop_mean=0.002, route="hash")
    hb = sweep_hier_grid(ht, 2, [10.0, 20.0], 40.0, "stoch_vacdh",
                         params[:2])
    checks["hier_d4"] = eq(
        sweep_hier_grid(ht, 2, [10.0, 20.0], 40.0, "stoch_vacdh",
                        params[:2], devices=4), hb)
    hm = sweep_hier_grid(ht, 2, 15.0, 40.0, ["lru", "stoch_vacdh"],
                         params[:1])
    checks["hier_multi_d2"] = eq(
        sweep_hier_grid(ht, 2, 15.0, 40.0, ["lru", "stoch_vacdh"],
                        params[:1], devices=2), hm)
print("PARITY " + json.dumps(checks))
"""


def _run_child(mode):
    # fake host devices exist on the CPU platform only; the child inherits
    # the test run's platform (the CPU route: JAX_PLATFORMS=cpu)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, SRC, mode],
        capture_output=True, text=True, timeout=570, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("PARITY ")]
    assert line, proc.stdout
    return json.loads(line[-1][len("PARITY "):])


def test_subprocess_parity_across_device_counts():
    """sweep_grid bitwise-equal for devices in {1,2,4,8} on a 6-lane grid
    (pad-lane path for 4 and 8) and under a reversed device assignment."""
    checks = _run_child("single")
    assert checks == {k: True for k in checks} and set(checks) == \
        {"d1", "d2", "d4", "d8", "shuffled"}, checks


@pytest.mark.slow
def test_subprocess_parity_multi_policy_and_hier():
    """Unified multi-policy and both hierarchy dispatches stay bitwise
    device-count-invisible (run in CI's multi-device-smoke job)."""
    checks = _run_child("multi_hier")
    assert checks == {k: True for k in checks} and set(checks) == \
        {"multi_d2", "multi_d8", "hier_d4", "hier_multi_d2"}, checks


# --- in-process parity: 1-device mesh routes through shard_map ----------

@pytest.fixture(scope="module")
def trace():
    return _trace()


def _check_shape(trace, n_pol, n_par, n_caps, n_seeds):
    """Any grid shape: fabric dispatch (1-device mesh) == legacy dispatch.

    lane_bucket=8 pins every shape here to the same padded lane count, so
    the whole property reuses two compiled graphs (single + multi)."""
    names = ["lru", "lfu"][:n_pol]
    params = [PolicyParams(omega=o) for o in (0.0, 1.0)][:n_par]
    caps = [25.0, 50.0][:n_caps]
    seeds = tuple(range(n_seeds))
    legacy = sweep_grid(trace, caps, names, params, seeds=seeds,
                        lane_bucket=8)
    fab = sweep_grid(trace, caps, names, params, seeds=seeds,
                     lane_bucket=8, mesh=make_data_mesh(1))
    assert legacy.result.total_latency.shape == \
        fab.result.total_latency.shape == (1, n_pol, n_par, n_caps, n_seeds)
    assert _grids_equal(legacy, fab)


if HAVE_HYPOTHESIS:
    @given(n_pol=st.integers(1, 2), n_par=st.integers(1, 2),
           n_caps=st.integers(1, 2), n_seeds=st.integers(1, 2))
    @settings(deadline=None, max_examples=8)
    def test_any_grid_shape_device_invisible(trace, n_pol, n_par, n_caps,
                                             n_seeds):
        _check_shape(trace, n_pol, n_par, n_caps, n_seeds)
else:
    @pytest.mark.parametrize("n_pol,n_par,n_caps,n_seeds",
                             [(1, 1, 1, 1), (1, 2, 2, 1), (2, 1, 1, 2),
                              (2, 2, 2, 2), (1, 2, 1, 2)])
    def test_any_grid_shape_device_invisible(trace, n_pol, n_par, n_caps,
                                             n_seeds):
        _check_shape(trace, n_pol, n_par, n_caps, n_seeds)


# --- knob resolution and error paths (no compiles) ----------------------

def test_resolve_fabric_knobs():
    assert resolve_fabric() is None
    assert resolve_fabric(devices=1) is None          # exact legacy graph
    m = make_data_mesh(1)
    assert resolve_fabric(mesh=m) is m                # explicit mesh always
    assert fabric_lane_multiple(None) == 1
    assert fabric_lane_multiple(m) == 1


def test_resolve_fabric_errors():
    with pytest.raises(ValueError, match="must be >= 1"):
        resolve_fabric(devices=0)
    with pytest.raises(ValueError, match="not both"):
        resolve_fabric(devices=2, mesh=make_data_mesh(1))
    bad = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError, match="'data' axis"):
        resolve_fabric(mesh=bad)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        resolve_fabric(devices=1024)   # more than any forced host count


def test_chunked_grid_rejects_fabric(trace):
    with pytest.raises(ValueError, match="chunk_size is not supported"):
        sweep_grid(trace, 40.0, "lru", [PolicyParams()], chunk_size=64,
                   mesh=make_data_mesh(1))

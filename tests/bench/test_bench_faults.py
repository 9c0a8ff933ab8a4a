"""The comparison that decides ``correct`` finds faults out.

Each test drives a whole run of a cell at a small size on the CPU through
``bench.run.run`` (the harness's look for a chip is skipped by calling it
directly), with the program's timed path broken underneath, and sees
``correct`` come out false; a sound run at the same size comes out true.
The control — the plain reference at the precision one step below what
the configuration states, in the program's place — fails too.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import control
from bench.cell import load_cell

ROOT = Path(__file__).resolve().parents[2]


def fabric_cell():
    """The four-chip fabric cell, from its files: its traffic mix is ready
    and its BENCHMARK.json entry waits for runs on four chips."""
    from bench.cell import Cell, config_path, traffic_path
    return Cell(name="fig2_grid_fabric4", chips=4,
                config=json.load(open(config_path("paper_fig2"))),
                traffic=json.load(open(traffic_path("omega_grid_fabric4"))),
                end_to_end=[], per_layer=[])


SMALL = {
    "fig2_replay_stoch": (
        dict(n_requests=8192),
        dict(segment_requests=4096, chunk_size=2048, use_kernel="interpret")),
    "fig2_roster_pareto": (dict(n_requests=6000),
                           dict(segment_requests=2000)),
    "fig2_replay_lru": (dict(n_requests=8192),
                        dict(segment_requests=4096, chunk_size=2048)),
    "fig2_grid_fabric4": (dict(n_requests=6000),
                          dict(segment_requests=2000, devices=1)),
}


def small_cell(name):
    cell = fabric_cell() if name == "fig2_grid_fabric4" else load_cell(name)
    cfg, traffic = SMALL[name]
    cell.config.update(cfg)
    cell.traffic.update(traffic)
    return cell


def run_small(cell, seed=5):
    from bench.run import run
    return run(cell, seed, seconds=0.0, trace=False,
               devices=jax.devices()[:1], workers=1)


@pytest.fixture
def fresh_jit():
    """Planted faults change what jitted programs trace to: start and end
    each such test with empty jit caches."""
    jax.clear_caches()
    yield
    jax.clear_caches()


# --- faults, planted in the program ------------------------------------------
def _unchanged_state(mp):
    from repro.core import simulator as S
    mp.setattr(S, "_chunk_step_jit", lambda state, *a, **k: state)

    def frozen(b, trace, capacity, key, params, estimate_z):
        st = S.init_state(trace.n_objects, capacity, key, trace.z_mean)
        return S._result_of_state(st)
    mp.setattr(S, "_run_scan", frozen)


def _half_batch(mp):
    from repro.core import simulator as S
    chunk_step, run_scan = S._chunk_step_jit, S._run_scan

    def half_chunk(state, times, objs, z_draw, valid, *rest, **kw):
        n = times.shape[0]
        return chunk_step(state, times, objs, z_draw,
                          jnp.arange(n) < n // 2, *rest, **kw)

    def half_scan(b, trace, *rest):
        n = trace.times.shape[0] // 2
        return run_scan(b, trace._replace(
            times=trace.times[:n], objs=trace.objs[:n],
            z_draw=trace.z_draw[:n]), *rest)
    mp.setattr(S, "_chunk_step_jit", half_chunk)
    mp.setattr(S, "_run_scan", half_scan)


def _answer_altered(mp):
    from repro.core import simulator as S
    result_of_state, run_scan = S._result_of_state, S._run_scan

    def swap(r):
        return r._replace(n_hits=r.n_misses, n_misses=r.n_hits,
                          total_latency=r.total_latency * 1.05)
    mp.setattr(S, "_result_of_state", lambda st: swap(result_of_state(st)))
    mp.setattr(S, "_run_scan", lambda *a: swap(run_scan(*a)))


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


CELLS = ["fig2_replay_stoch", "fig2_roster_pareto", "fig2_replay_lru"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, fresh_jit):
    out = run_small(small_cell(name))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch, fresh_jit):
    FAULTS[fault](monkeypatch)
    out = run_small(small_cell(name))
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_is_not_correct(name):
    cell = small_cell(name)
    for seed in (1, 2, 3):
        v = control.readings(cell, seed, calls=2, workers=1)
        assert not v["correct"], (seed, v["checks"])


FABRIC = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from bench.run import run
from repro.launch import fabric
sys.path.insert(0, {tests!r})
from test_bench_faults import fabric_cell
cell = fabric_cell()
cell.config.update(n_requests=4000)
cell.traffic.update(segment_requests=2000)
sound = run(cell, 9, 0.0, False, jax.devices()[:4], workers=1)
orig = fabric.fabric_sweep_single
def no_exchange(mesh, *a, **k):
    out = orig(mesh, *a, **k)
    d = mesh.shape["data"]
    return jax.tree.map(lambda x: jnp.tile(x[:, :x.shape[1] // d], (1, d)),
                        out)
fabric.fabric_sweep_single = no_exchange
jax.clear_caches()
broken = run(cell, 9, 0.0, False, jax.devices()[:4], workers=1)
print(json.dumps([sound["correct"], broken["correct"], broken["checks"]]))
"""


def test_fabric_without_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c",
         FABRIC.format(root=str(ROOT), src=str(ROOT / "src"),
                       tests=str(ROOT / "tests" / "bench"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    sound, broken, checks = json.loads(out.stdout.strip().splitlines()[-1])
    assert sound and not broken, checks

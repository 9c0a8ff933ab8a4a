"""The exact CDN replay cell (``cdn_replay_exact``) decides ``correct``.

Whole runs of the cell at a small size on the CPU through
``bench.run.run``, the scoring kernel in interpret mode: a sound run is
correct; each fault of ``test_bench_faults.py``, planted in the slot
engine's chunk step or its result, is not, nor is a replay that reclaims
a slot, nor the bfloat16 control.  The generator gives every seed the
same requests in another order, and one seed the same requests in any
process.
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control
from bench.cell import load_cell
from bench.generate import requests

ROOT = Path(__file__).resolve().parents[2]
CELL = "cdn_replay_exact"


def small_cell():
    cell = load_cell(CELL)
    cell.config.update(n_requests=8192, n_keys=20000, n_slots=8192)
    cell.traffic.update(segment_requests=4096, chunk_size=2048,
                        use_kernel="interpret")
    return cell


def run_small(cell, seed=5):
    from bench.run import run
    return run(cell, seed, seconds=0.0, trace=False,
               devices=jax.devices()[:1], workers=1)


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(fresh_jit):
    out = run_small(small_cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["checks"]["counter_gap"]["value"] == 0.0


# --- faults, planted in the slot engine --------------------------------------
def _unchanged_state(mp):
    from repro.core import simulator as S
    mp.setattr(S, "_slot_chunk_step_jit", lambda state, *a, **k: state)


def _half_batch(mp):
    from repro.core import simulator as S
    chunk_step = S._slot_chunk_step_jit

    def half_chunk(state, times, objs, z_draw, valid, *rest, **kw):
        n = times.shape[0]
        return chunk_step(state, times, objs, z_draw,
                          jnp.arange(n) < n // 2, *rest, **kw)
    mp.setattr(S, "_slot_chunk_step_jit", half_chunk)


def _answer_altered(mp):
    from repro.core import simulator as S
    result_of_state = S._slot_result_of_state

    def swap(st):
        r = result_of_state(st)
        return r._replace(n_hits=r.n_misses, n_misses=r.n_hits,
                          total_latency=r.total_latency * 1.05)
    mp.setattr(S, "_slot_result_of_state", swap)


def _forced_reclaim(mp):
    """Every first touch finds the table full and takes an occupied slot."""
    from repro.core import simulator as S
    probe = S.slot_probe

    def full(key_tab, obj, seed):
        slot, found, _ = probe(key_tab, obj, seed)
        return slot, found, jnp.asarray(False)
    mp.setattr(S, "slot_probe", full)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "forced_reclaim": _forced_reclaim}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, fresh_jit):
    FAULTS[fault](monkeypatch)
    out = run_small(small_cell())
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]


def test_control_is_not_correct():
    cell = small_cell()
    for seed in (1, 2, 3):
        v = control.readings(cell, seed, calls=2, workers=1)
        assert not v["correct"], (seed, v["checks"])


# --- the generator -----------------------------------------------------------
def test_seeds_change_the_order_of_the_work_not_its_amount():
    cell = load_cell(CELL)
    a = requests(cell.config, cell.traffic, 3160000001)
    b = requests(cell.config, cell.traffic, 2 ** 31 + 17)
    assert not np.array_equal(a["objs"], b["objs"])
    for k in ("objs", "unit"):
        np.testing.assert_array_equal(np.sort(a[k]), np.sort(b[k]))
    # the same gaps, each to within the float64 clock's step at 1.7e9 s
    np.testing.assert_allclose(np.sort(np.diff(a["times"])),
                               np.sort(np.diff(b["times"])), rtol=0,
                               atol=1e-6)
    for k in ("sizes", "z_mean"):
        np.testing.assert_array_equal(a[k], b[k])
    n = int(cell.config["n_requests"])
    assert a["objs"].dtype == np.int32 and len(a["objs"]) == n
    assert 0 <= a["objs"].min() and a["objs"].max() < cell.config["n_keys"]
    assert a["times"][0] > cell.config["start_time"]
    assert a["sizes"].max() <= cell.config["size_max"]
    np.testing.assert_allclose(n / (a["times"][-1] - a["times"][0]),
                               cell.traffic["arrival"]["rate"], rtol=0.01)


SAME = """
import hashlib, sys
sys.path.insert(0, {root!r})
from bench.cell import load_cell
from bench.generate import requests
cell = load_cell({cell!r})
r = requests(cell.config, cell.traffic, {seed})
print(hashlib.sha256(b"".join(r[k].tobytes() for k in sorted(r))).hexdigest())
"""


def test_same_seed_same_requests_across_processes():
    code = SAME.format(root=str(ROOT), cell=CELL, seed=3160000002)
    outs = {subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120, cwd=ROOT,
                           check=True).stdout.strip() for _ in range(2)}
    assert len(outs) == 1 and len(next(iter(outs))) == 64


def test_segments_fit_the_table():
    """Every segment's keys sit in the deployment's table at load <= 0.75,
    and the driver refuses a table that cannot hold them."""
    from bench import drivers
    cell = load_cell(CELL)
    drv = drivers.build(cell.config, cell.traffic, 11)
    drv.generate()
    assert drv.n_objects == cell.config["n_slots"]
    objs = drv.ref_in["objs"]
    for k in range(drv.n_segments):
        keys = np.unique(objs[drv._slice(k)]).size
        assert keys <= 0.75 * cell.config["n_slots"]
    small = dict(cell.config, n_slots=4096)
    with pytest.raises(ValueError, match="table"):
        drivers.build(small, cell.traffic, 11).generate()

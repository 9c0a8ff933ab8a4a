"""The benchmark's requests come from --seed alone: the same seed gives the
same arrays in two processes, whatever Python's hash salt is."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import generate as G

ROOT = Path(__file__).resolve().parents[2]
SMALL = dict(generator="synthetic", n_requests=20000, n_objects=300,
             zipf_alpha=0.9, size_min=1.0, size_max=100.0, objects_seed=0,
             latency_base=0.005, latency_per_mb=2e-4,
             latency_law="exponential")
POISSON = {"arrival": {"law": "poisson", "rate": 2000.0}}
PARETO = {"arrival": {"law": "pareto", "rate": 2000.0, "shape": 1.5}}
SEED = 3_000_000_017          # more than 32 signed bits hold

DIGEST = """
import hashlib, json, sys
sys.path.insert(0, {root!r})
from bench import generate as G
cfg, poisson, pareto, seed = json.loads(sys.argv[1])
h = hashlib.sha256()
for d in (G.requests(cfg, poisson, seed), G.requests(cfg, pareto, seed)):
    for k in sorted(d):
        h.update(k.encode()); h.update(d[k].tobytes())
h.update(str(G.coin_seed(seed)).encode())
print(h.hexdigest())
"""


def _digest_in_process(hash_seed: str) -> str:
    env = dict(os.environ, PYTHONHASHSEED=hash_seed)
    out = subprocess.run(
        [sys.executable, "-c", DIGEST.format(root=str(ROOT)),
         json.dumps([SMALL, POISSON, PARETO, SEED])],
        env=env, capture_output=True, text=True, check=True, timeout=120)
    return out.stdout.strip()


def test_same_seed_same_requests_across_processes():
    assert _digest_in_process("1") == _digest_in_process("2")


def test_seeds_differ_and_sizes_follow_their_laws():
    a, b = G.requests(SMALL, POISSON, SEED), G.requests(SMALL, POISSON,
                                                        SEED + 1)
    assert not np.array_equal(a["objs"], b["objs"])
    assert a["sizes"].min() >= 1.0 and a["sizes"].max() <= 100.0
    # drawn continuously: no size collapses onto an integer
    assert np.unique(a["sizes"]).size == a["sizes"].size
    assert np.all(np.diff(a["times"]) >= 0.0)
    assert a["times"].dtype == np.float64
    np.testing.assert_array_equal(
        a["z_draw"], (a["z_mean"][a["objs"]] * a["unit"]).astype(np.float32))


def test_seeds_change_the_order_of_the_work_not_its_amount():
    a, b = G.requests(SMALL, PARETO, SEED), G.requests(SMALL, PARETO, 7)
    # the same objects, requested as often, with the same gaps and draws
    np.testing.assert_array_equal(a["sizes"], b["sizes"])
    np.testing.assert_array_equal(np.bincount(a["objs"], minlength=300),
                                  np.bincount(b["objs"], minlength=300))
    gaps = lambda t: np.sort(np.diff(t, prepend=0.0))
    np.testing.assert_allclose(gaps(a["times"]), gaps(b["times"]),
                               rtol=1e-9)
    np.testing.assert_array_equal(np.sort(a["unit"]), np.sort(b["unit"]))
    # popularity follows the ranks' Zipf shares
    counts = np.bincount(a["objs"], minlength=300)
    assert counts.sum() == 20000 and np.all(np.diff(counts) <= 0)
    p = np.arange(1, 301) ** -0.9
    assert np.abs(counts - 20000 * p / p.sum()).max() < 1.0


@pytest.mark.parametrize("traffic", [POISSON, PARETO],
                         ids=["poisson", "pareto"])
def test_arrivals_keep_their_mean_rate(traffic):
    times = G.requests(dict(SMALL, n_requests=200000), traffic, SEED)["times"]
    assert times[-1] / times.size == pytest.approx(1 / 2000.0, rel=0.05)


def test_unknown_laws_are_errors():
    with pytest.raises(ValueError, match="generator"):
        G.requests(dict(SMALL, generator="cdn"), POISSON, SEED)
    with pytest.raises(ValueError, match="latency law"):
        G.requests(dict(SMALL, latency_law="erlang"), POISSON, SEED)
    with pytest.raises(ValueError, match="arrival law"):
        G.requests(SMALL, {"arrival": {"law": "diurnal", "rate": 1.0}}, SEED)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40])
def test_coin_seed_fits_a_prng_key(seed):
    assert 0 <= G.coin_seed(seed) < 2 ** 31

"""Request generation for the benchmark, from a seed alone (numpy).

A configuration names its generator (``generator``, ``bench/configs``),
which reads the deployment's laws from the configuration and the arrival
process from the traffic mix (``bench/traffic``).  The built-in one:

* ``synthetic`` — requests over a fixed set of objects: Zipf popularity
  over ``n_objects``, sizes uniform between ``size_min`` and ``size_max``
  (MB), and Poisson or Pareto gaps at the traffic's mean ``rate``.

Fetch durations are ``L + c * size`` scaled by a unit-mean Exponential
draw per request (the paper's stochastic miss latency).

Every seed gets the same work in another order.  The objects are the
deployment's: their sizes are the stratified sample of the size law (the
quantiles at ``(i + 1/2) / n``) given to the objects in one order drawn
from the configuration's ``objects_seed``.  Each other law is laid out as
its stratified sample too (for the objects requested, each object's Zipf
share of the requests rounded to whole requests), and the run's seed
shuffles which request comes when and which gap and fetch draw falls
where.  So runs of different seeds differ by the order of the work and
not by its amount.  Every shuffle comes from its own child of
``numpy.random.SeedSequence(seed)``, so the same seed gives the same
requests in any process, and nothing depends on Python's salted ``hash``.

Any other generator name is the file ``bench/generators/<name>.py`` and
its ``requests(cfg, traffic, seed)`` (``bench/cell.py``).
"""
from __future__ import annotations

import numpy as np

from bench.cell import load_plugin

_STREAMS = ("keys", "gaps", "fetch", "coins")


def rngs(seed: int) -> dict:
    """One independent generator per stream of the run."""
    kids = np.random.SeedSequence(int(seed)).spawn(len(_STREAMS))
    return {name: np.random.default_rng(k) for name, k in zip(_STREAMS, kids)}


def coin_seed(seed: int) -> int:
    """A seed below 2**31 for the simulator's PRNG key (admission coins)."""
    return int(rngs(seed)["coins"].integers(0, 2 ** 31))


def strata(n: int) -> np.ndarray:
    """The ``n`` midpoints ``(i + 1/2) / n`` of equal strata of (0, 1)."""
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def zipf_counts(n_keys: int, alpha: float, n: int) -> np.ndarray:
    """Requests per rank: Zipf(alpha) shares of ``n`` requests, rounded to
    whole requests by largest remainder (ties to the lower rank)."""
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(alpha)
    share = n * p / p.sum()
    counts = np.floor(share).astype(np.int64)
    rest = np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return counts


def gaps(u: np.ndarray, arrival: dict) -> np.ndarray:
    """Inter-arrival gaps (s) at probabilities ``u`` of a traffic mix's
    ``arrival`` law: ``{"law": "poisson", "rate": r}`` or ``{"law":
    "pareto", "rate": r, "shape": a}``, each with mean ``1 / r``."""
    law, mean_gap = arrival["law"], 1.0 / float(arrival["rate"])
    if law == "poisson":
        return -mean_gap * np.log1p(-u)
    if law == "pareto":
        a = float(arrival["shape"])
        x_m = mean_gap * (a - 1.0) / a       # mean a*x_m/(a-1) == mean_gap
        return x_m * (1.0 - u) ** (-1.0 / a)
    raise ValueError(f"unknown arrival law {law!r}")


def synthetic(cfg: dict, traffic: dict, seed: int) -> dict:
    """Requests over a fixed object set: f64 ``times`` (s, from 0), i32
    ``objs``, f32 ``sizes`` and ``z_mean`` per object, f32 ``unit`` fetch
    draws and ``z_draw = z_mean[objs] * unit`` per request."""
    if cfg["latency_law"] != "exponential":
        raise ValueError(f"unknown latency law {cfg['latency_law']!r}")
    g = rngs(seed)
    n, n_obj = int(cfg["n_requests"]), int(cfg["n_objects"])
    counts = zipf_counts(n_obj, cfg["zipf_alpha"], n)
    objs = g["keys"].permutation(np.repeat(np.arange(n_obj), counts))
    times = np.cumsum(g["gaps"].permutation(gaps(strata(n),
                                                 traffic["arrival"])))
    lo, hi = float(cfg["size_min"]), float(cfg["size_max"])
    objects = np.random.default_rng(int(cfg["objects_seed"]))
    sizes = objects.permutation(lo + (hi - lo) * strata(n_obj))
    sizes = sizes.astype(np.float32)
    z_mean = (float(cfg["latency_base"])
              + float(cfg["latency_per_mb"]) * sizes).astype(np.float32)
    unit = g["fetch"].permutation(-np.log1p(-strata(n))).astype(np.float32)
    objs = objs.astype(np.int32)
    return dict(times=times, objs=objs, sizes=sizes, z_mean=z_mean,
                unit=unit, z_draw=(z_mean[objs] * unit).astype(np.float32))


GENERATORS = {"synthetic": synthetic}


def requests(cfg: dict, traffic: dict, seed: int) -> dict:
    """The requests of a cell, from the generator its configuration names:
    a built-in, else ``bench/generators/<name>.py``."""
    name = cfg["generator"]
    if name in GENERATORS:
        return GENERATORS[name](cfg, traffic, seed)
    return load_plugin("generator", name, GENERATORS).requests(
        cfg, traffic, seed)

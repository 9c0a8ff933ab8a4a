"""Requests of the exact CDN replay: a CDN-like trace over a large key
universe, every distinct key its own object (numpy only).

The deployment's laws come from the configuration
(``bench/configs/cdn_realworld.json``): Zipf popularity over ``n_keys``
keys, object sizes lognormal in MB and capped, epoch-scale float64
arrival times from ``start_time``, fetches of ``L + c * size`` drawn
Exponential.  The arrival process is the traffic mix's.

As ``bench/generate.py`` lays out every law, each seed gets the same work
in another order:

* request ranks are the Zipf inverse CDF over the ``n_keys`` ranks at the
  ``n_requests`` stratum midpoints;
* ranks map to int32 ids in ``[0, n_keys)`` through one permutation drawn
  from ``objects_seed``, so ids look hashed and the program's table probes
  are not sequential;
* the ``n_keys`` sizes are the stratified quantiles of the capped
  lognormal, given to the ids in one ``objects_seed`` order;
* gaps and unit fetch draws are the stratified samples of their laws.

The run's seed shuffles which request comes when and which gap and fetch
draw falls where.  Nothing here imports the program, so no change to it
can move the benchmark's data.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from bench.generate import gaps, rngs, strata


def zipf_ranks(n_keys: int, alpha: float, u: np.ndarray) -> np.ndarray:
    """The Zipf(alpha) rank (0 the most popular) at each probability in
    ``u``: the first rank whose cumulative share reaches it."""
    cdf = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64)
                    ** -float(alpha))
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, u), n_keys - 1)


def lognormal_quantiles(n: int, mu: float, sigma: float,
                        cap: float) -> np.ndarray:
    """The ``n`` stratum-midpoint quantiles of lognormal(mu, sigma),
    capped at ``cap``."""
    inv = NormalDist(float(mu), float(sigma)).inv_cdf
    return np.minimum(np.exp([inv(u) for u in strata(n)]), float(cap))


def requests(cfg: dict, traffic: dict, seed: int) -> dict:
    """f64 ``times`` (s, from ``start_time``), i32 ``objs`` (raw ids),
    f32 ``sizes`` and ``z_mean`` per id of the universe, f32 ``unit``
    fetch draws and ``z_draw = z_mean[objs] * unit`` per request."""
    if cfg["latency_law"] != "exponential":
        raise ValueError(f"unknown latency law {cfg['latency_law']!r}")
    g = rngs(seed)
    n, n_keys = int(cfg["n_requests"]), int(cfg["n_keys"])
    objects = np.random.default_rng(int(cfg["objects_seed"]))
    ids = objects.permutation(n_keys).astype(np.int32)
    sizes = objects.permutation(lognormal_quantiles(
        n_keys, cfg["size_log_mu"], cfg["size_log_sigma"],
        cfg["size_max"])).astype(np.float32)
    z_mean = (float(cfg["latency_base"])
              + float(cfg["latency_per_mb"]) * sizes).astype(np.float32)
    objs = g["keys"].permutation(
        ids[zipf_ranks(n_keys, cfg["zipf_alpha"], strata(n))])
    times = float(cfg["start_time"]) + np.cumsum(
        g["gaps"].permutation(gaps(strata(n), traffic["arrival"])))
    unit = g["fetch"].permutation(-np.log1p(-strata(n))).astype(np.float32)
    return dict(times=times, objs=objs, sizes=sizes, z_mean=z_mean,
                unit=unit, z_draw=(z_mean[objs] * unit).astype(np.float32))

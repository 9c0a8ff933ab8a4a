"""Plain reference of the delayed-hit cache, for deciding ``correct``.

A straightforward event loop over the requests, in numpy, that imports
nothing of the program under test.  It is a frozen copy of the semantics
the simulator states (arXiv:2505.15531 §2.2 and eq. 16, with the
simulator's online estimators), written so that a later change to the
program's ranking or estimator code cannot move it:

* before serving the request at time ``t``, every outstanding fetch with
  completion time ``<= t`` commits, earliest first (ties by object id);
* a commit finalises the fetch episode's statistics, then admits the object,
  evicting the lowest-ranked cached objects (ties by object id) while there
  is no room; policies with rank-compare admission evict only victims ranked
  strictly below the incomer and abort otherwise;
* a request is a hit (latency 0), a delayed hit (the remaining fetch time)
  or a miss (issues a fetch of the pre-drawn duration).

All per-object state and arithmetic are float32 (the configuration's stated
state precision, ``dtype``), the same operation order as the compiled
simulator, so a sound program agrees counter for counter.  Streamed replays
rebase each chunk's float64 request times to the chunk's first arrival, as
the program's streaming engine states; ``time_dtype=np.float32`` without a
rebase is the float32-clock control.  AdaptSize's admission coin is the
threefry2x32 stream the program states (a fresh ``split`` per commit), in
plain integer arithmetic here.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

ROSTER = ("lru", "lfu", "lhd", "adaptsize", "lru_mad", "lhd_mad", "lac",
          "cala", "vacdh", "lrb_lite", "stoch_vacdh")

# (greedydual, gd cost uses the arrival rate, adaptsize coin,
#  rank-compare admission) per policy
_FLAGS = {
    "lru": (False, False, False, False),
    "lfu": (False, False, False, False),
    "lhd": (False, False, False, False),
    "adaptsize": (False, False, True, False),
    "lru_mad": (True, False, False, True),
    "lhd_mad": (True, True, False, True),
    "lac": (False, False, False, True),
    "cala": (False, False, False, True),
    "vacdh": (False, False, False, True),
    "lrb_lite": (False, False, False, True),
    "stoch_vacdh": (False, False, False, True),
}

_M32 = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# threefry2x32 (Salmon et al., SC'11), as JAX's default PRNG applies it
# ---------------------------------------------------------------------------
def _rotl(v, r):
    return ((v << r) | (v >> (32 - r))) & _M32


def threefry2x32(k1: int, k2: int, x1: int, x2: int) -> tuple[int, int]:
    """One 2x32 block of Threefry-20 on python ints."""
    ks = (k1, k2, k1 ^ k2 ^ 0x1BD11BDA)
    x1 = (x1 + ks[0]) & _M32
    x2 = (x2 + ks[1]) & _M32
    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    for i in range(5):
        for r in rots[i % 2]:
            x1 = (x1 + x2) & _M32
            x2 = _rotl(x2, r) ^ x1
        x1 = (x1 + ks[(i + 1) % 3]) & _M32
        x2 = (x2 + ks[(i + 2) % 3] + i + 1) & _M32
    return x1, x2


def key_of_seed(seed: int) -> tuple[int, int]:
    """``jax.random.key(seed)`` for a seed below 2**31."""
    if not 0 <= seed < 2 ** 31:
        raise ValueError(f"coin seed {seed} must be in [0, 2**31)")
    return 0, seed


def coin_stream(key: tuple[int, int]):
    """Yields ``uniform(sub)`` after each ``key, sub = split(key)``."""
    k1, k2 = key
    while True:
        nk = threefry2x32(k1, k2, 0, 0)
        sub = threefry2x32(k1, k2, 0, 1)
        b1, b2 = threefry2x32(sub[0], sub[1], 0, 0)
        bits = ((b1 ^ b2) >> 9) | 0x3F800000
        yield np.uint32(bits).view(np.float32) - np.float32(1.0)
        k1, k2 = nk


# ---------------------------------------------------------------------------
# one cache
# ---------------------------------------------------------------------------
class Params:
    """The policy hyperparameters the cells use (the program's defaults,
    with the recency residual and the Exponential fetch law)."""

    def __init__(self, omega=1.0, window=64, cala_beta=0.5, adapt_c=25.0,
                 cold_rate=1e-3):
        self.omega = omega
        self.window = window
        self.cala_beta = cala_beta
        self.adapt_c = adapt_c
        self.cold_rate = cold_rate


class Cache:
    """One delayed-hit cache over ``n`` objects in float type ``F``."""

    def __init__(self, sizes, z_prior, capacity, policy, params=None,
                 estimate_z=False, coin_seed=0, F=np.float32):
        if policy not in _FLAGS:
            raise ValueError(f"unknown policy {policy!r}")
        self.F = F
        self.policy = policy
        self.p = params or Params()
        (self.greedydual, self.gd_rate, self.adaptsize,
         self.compare) = _FLAGS[policy]
        self.estimate_z = estimate_z
        n = len(sizes)
        full = lambda v: np.full(n, v, F)
        self.sizes = np.asarray(sizes).astype(F)
        self.size_eps = np.maximum(self.sizes, F(1e-6))
        self.cached = np.zeros(n, bool)
        self.in_flight = np.zeros(n, bool)
        self.complete_t = full(np.inf)
        self.issue_t = full(0.0)
        self.last_access = full(-np.inf)
        self.first_access = full(-np.inf)
        self.gap_mean = full(0.0)
        self.count = full(0.0)
        self.z_est = np.asarray(z_prior).astype(F).copy()
        self.agg_sum = full(0.0)
        self.agg_sq_sum = full(0.0)
        self.agg_cnt = full(0.0)
        self.episode_delay = full(0.0)
        self.gd_h = full(0.0)
        self.free = F(capacity)
        self.gd_clock = F(0.0)
        self.heap: list[tuple[float, int]] = []
        self.coins = coin_stream(key_of_seed(coin_seed))
        self.total = 0.0
        self.hits = self.delayed = self.misses = self.evictions = 0
        P = self.p
        self._eps = F(1e-6)
        self._inv_window = F(1.0) / F(P.window)
        self._cold = F(P.cold_rate)
        self._cold_resid = F(1.0) / np.maximum(F(P.cold_rate), F(1e-6))
        self._omega = F(P.omega)
        self._beta = F(P.cala_beta)
        self._adapt_c = F(P.adapt_c)

    # --- estimators --------------------------------------------------------
    def _lam(self):
        F = self.F
        lam = F(1.0) / np.maximum(self.gap_mean, self._eps)
        return np.where(self.count >= F(2.0), lam, self._cold).astype(F)

    def _lam_at(self, j):
        F = self.F
        if self.count[j] >= F(2.0):
            return F(1.0) / max(self.gap_mean[j], self._eps)
        return self._cold

    def _hist_mean_at(self, j):
        F = self.F
        if self.agg_cnt[j] > F(0.0):
            return self.agg_sum[j] / max(self.agg_cnt[j], F(1.0))
        return self.z_est[j]

    def _gd_cost_at(self, j):
        cost = self._hist_mean_at(j)
        if self.gd_rate:
            cost = cost * self._lam_at(j)
        return cost / self.size_eps[j]

    def ranks(self, t):
        """Score of every object at time ``t`` (higher = keep)."""
        F, pol = self.F, self.policy
        if pol in ("lru", "adaptsize"):
            return self.last_access
        if pol == "lfu":
            return self.count
        if pol in ("lru_mad", "lhd_mad"):
            return self.gd_h
        lam = self._lam()
        if pol == "lhd":
            return lam / self.size_eps
        age = F(t) - self.last_access
        just = np.where((self.count >= F(2.0)) & (self.gap_mean > self._eps),
                        self.gap_mean, self._cold_resid).astype(F)
        resid = np.where(age > self._eps, age, just).astype(F)
        denom = resid * self.size_eps
        z = self.z_est
        if pol == "stoch_vacdh":
            z2 = z * z
            mean = z + lam * z2
            var = (z2 + F(6.0) * lam * z2 * z
                   + F(5.0) * lam * lam * z2 * z2)
            return (mean + self._omega * np.sqrt(var)) / denom
        det_mean = z * (F(1.0) + F(0.5) * lam * z)
        if pol == "lac":
            return det_mean / denom
        if pol == "vacdh":
            det_std = np.sqrt(lam * (z * (z * z)) / F(3.0))
            return (det_mean + self._omega * det_std) / denom
        n = np.maximum(self.agg_cnt, F(1.0))
        hist_mean = np.where(self.agg_cnt > F(0.0), self.agg_sum / n,
                             self.z_est).astype(F)
        if pol == "cala":
            est = self._beta * hist_mean + (F(1.0) - self._beta) * det_mean
            return est / denom
        if pol == "lrb_lite":
            pred = F(1.0) / np.maximum(lam, self._eps) + F(0.5) * resid
            return -pred / self.size_eps * hist_mean
        raise AssertionError(pol)

    # --- fetch commit ------------------------------------------------------
    def commit(self, j: int) -> None:
        F = self.F
        t_c = self.complete_t[j]
        realized = t_c - self.issue_t[j]
        ep = self.episode_delay[j]
        self.agg_sum[j] += ep
        self.agg_sq_sum[j] += ep * ep
        self.agg_cnt[j] += F(1.0)
        self.episode_delay[j] = 0.0
        self.in_flight[j] = False
        self.complete_t[j] = np.inf
        if self.estimate_z:
            self.z_est[j] = F(0.7) * self.z_est[j] + F(0.3) * realized
        admit = True
        if self.adaptsize:
            u = next(self.coins)
            admit = bool(u < np.exp(-self.sizes[j] / self._adapt_c))
        if self.greedydual:
            self.gd_h[j] = self.gd_clock + self._gd_cost_at(j)
        s_j = self.sizes[j]
        ok = admit
        if admit and self.free < s_j:
            with np.errstate(all="ignore"):
                ranks = self.ranks(t_c)
            cmp = ranks[j] if self.compare else np.inf
            masked = np.where(self.cached, ranks, np.inf)
            while ok and self.free < s_j:
                v = int(np.argmin(masked))
                vv = masked[v]
                if vv < cmp:
                    self.cached[v] = False
                    masked[v] = np.inf
                    self.free = self.free + self.sizes[v]
                    self.evictions += 1
                    if self.greedydual:
                        self.gd_clock = max(self.gd_clock, F(vv))
                else:
                    ok = False
        if ok and self.free >= s_j:
            self.cached[j] = True
            self.free = self.free - s_j

    def commit_due(self, t: float) -> None:
        heap = self.heap
        while heap and heap[0][0] <= t:
            _, j = heapq.heappop(heap)
            self.commit(j)

    # --- request -----------------------------------------------------------
    def serve(self, t, i: int, z) -> None:
        """Serve the request for object ``i`` at local time ``t`` (type F);
        ``z`` is its fetch duration if it misses."""
        F = self.F
        if self.cached[i]:
            lat = 0.0
            self.hits += 1
            hit = True
        else:
            hit = False
            if self.in_flight[i]:
                d = self.complete_t[i] - t
                d = d if d > F(0.0) else F(0.0)
                self.episode_delay[i] = self.episode_delay[i] + d
                lat = float(d)
                self.delayed += 1
            else:
                comp = F(t + z)
                self.in_flight[i] = True
                self.complete_t[i] = comp
                self.issue_t[i] = t
                self.episode_delay[i] = z
                heapq.heappush(self.heap, (float(comp), i))
                lat = float(z)
                self.misses += 1
        cnt = self.count[i]
        if cnt > F(0.0):
            gap = t - self.last_access[i]
            if cnt == F(1.0):
                self.gap_mean[i] = gap
            else:
                a = max(self._inv_window, F(1.0) / max(cnt, F(1.0)))
                g = self.gap_mean[i]
                self.gap_mean[i] = g + a * (gap - g)
        else:
            self.first_access[i] = t
        self.last_access[i] = t
        self.count[i] = cnt + F(1.0)
        if self.greedydual and hit:
            self.gd_h[i] = self.gd_clock + self._gd_cost_at(i)
        self.total += lat

    def shift(self, delta) -> None:
        """Move every absolute time of the state by ``-delta``."""
        for a in (self.complete_t, self.issue_t, self.last_access,
                  self.first_access):
            a -= delta
        self.heap = [(float(self.complete_t[j]), j) for _, j in self.heap]
        heapq.heapify(self.heap)

    def counters(self) -> dict:
        return dict(total_latency=self.total, n_hits=self.hits,
                    n_delayed=self.delayed, n_misses=self.misses,
                    n_evictions=self.evictions)


def replay(times, objs, z_draw, sizes, z_mean, capacity: float, policy: str,
           params: Params | None = None, estimate_z: bool = False,
           chunk: int | None = None, coin_seed: int = 0,
           F=np.float32, time_dtype=np.float32) -> dict:
    """Replay one request sequence from an empty cache; returns counters.

    ``chunk`` set: the streamed replay, float64 ``times`` rebased to each
    ``chunk``-request block's first arrival.  ``chunk=None``: times are
    taken as they come, in ``time_dtype`` (the in-memory trace's float32
    clock).
    """
    c = Cache(sizes, z_mean, capacity, policy, params, estimate_z,
              coin_seed, F)
    objs = np.asarray(objs, np.int64)
    z_draw = np.asarray(z_draw).astype(F)
    n = len(objs)
    if chunk is None:
        blocks = [(0, n, np.asarray(times).astype(time_dtype).astype(F))]
    else:
        times = np.asarray(times, np.float64)
        blocks, base = [], 0.0
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            new_base = float(times[lo])
            blocks.append((lo, hi, (times[lo:hi] - new_base).astype(F),
                           F(new_base - base)))
            base = new_base
    for blk in blocks:
        lo, hi, local = blk[0], blk[1], blk[2]
        if chunk is not None:
            c.shift(blk[3])
        for k in range(lo, hi):
            t = local[k - lo]
            tf = float(t)
            if c.heap and c.heap[0][0] <= tf:
                c.commit_due(tf)
            c.serve(t, int(objs[k]), z_draw[k])
    return c.counters()


def gaps(got: dict, ref: dict, n_requests: int) -> tuple[float, float]:
    """(counter gap, latency gap) of one replay against the reference: the
    widest counter difference as a share of the requests, and the relative
    difference of total latency."""
    cg = max(abs(float(got[k]) - float(ref[k]))
             for k in ("n_hits", "n_delayed", "n_misses", "n_evictions"))
    lat_ref = float(ref["total_latency"])
    lg = abs(float(got["total_latency"]) - lat_ref) / max(abs(lat_ref),
                                                           1e-30)
    if not (math.isfinite(cg) and math.isfinite(lg)):
        return math.inf, math.inf
    return cg / max(n_requests, 1), lg


def _float_type(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(name).type


def run_job(job: dict) -> dict:
    """One lane of one segment, from a job the drivers describe.  A job may
    carry ``dtype`` (the state's float type) and ``time_dtype``/``chunk``
    to run the lower-precision control."""
    job = dict(job)
    params = Params(omega=job.pop("omega"))
    F = _float_type(job.pop("dtype", "float32"))
    time_dtype = _float_type(job.pop("time_dtype", "float32"))
    return replay(params=params, F=F, time_dtype=time_dtype, **job)

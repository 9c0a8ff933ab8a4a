"""Eviction ranking functions.

Every ranking function maps the per-object statistics to a score vector
(shape [N]); **higher score = more valuable = keep**. The simulator evicts
``argmin`` over cached objects and admits an incoming object only while the
victim's score is strictly below the incomer's (paper §2.2 toy-example
semantics).

The paper's contribution is :func:`rank_stochastic_vacdh` (eq. 16), built on
Theorem 2; every baseline from §5.1 is implemented alongside, under the same
online-estimation substrate, so comparisons are apples-to-apples.

**Hot-path layout (DESIGN.md §10).**  Every rank in the registry shares one
estimator pass: arrival rate, residual time, the aggregate-delay moments
(analytic and historical), and the ``R * size`` normalizer.  That pass is
factored into :func:`make_substrate`, computed ONCE per commit into a
:class:`Substrate` (fields lazy + memoized, so callers trace or compute
only what they read); each policy's rank is then a few-op *epilogue*
over it (``epi_*``, registered as ``Policy.epilogue``).  The unified
multi-policy graph scores P policies as one substrate + P epilogues
instead of P full rank stacks — O(N + P·N_cheap) instead of O(P·N) — and
a single-policy graph (jitted or eager) computes exactly the fields its
epilogue reads.  The legacy ``rank(o, sizes, t, p)``
signature survives as the substrate+epilogue composition (the event-driven
oracle :mod:`repro.core.refsim` calls it directly), so both entry points are
the same arithmetic by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from . import delay_stats as ds
from .distributions import Deterministic, Exponential, MissLatency
from .state import ObjStats

EPS = 1e-6

# The deterministic-latency moment model assumed by the VA-CDH / LAC / CALA
# baselines (their published setting), independent of the trace's true law.
_DET = Deterministic()


@dataclasses.dataclass(frozen=True)
class PolicyParams:
    """Hyperparameters shared by the ranking functions.

    omega      — variance-sensitivity weight (paper's w; eq. 15/16).
    window     — per-object estimation window W (samples): the inter-arrival
                 mean is a running mean for the first W gaps, then an
                 EWMA(1/W).  Emulates the paper's sliding window S
                 (W ~ S * p_i for an object with popularity p_i).
    resid      — residual-time estimator for eq. 15/16's R_i:
                 'rate'    : R = 1/lambda (exact for Poisson, memoryless);
                 'recency' : R = t - last_access (LRU proxy).
    cala_beta  — CALA's weight between historical AggDelay and the analytic
                 mean-based estimate.
    adapt_c    — AdaptSize admission scale (admit w.p. exp(-size/adapt_c)).
    cold_rate  — arrival-rate prior for objects with <2 observations.
    dist       — miss-latency distribution assumed by the variance-aware
                 ranking (repro.core.distributions).  Exponential() makes
                 rank_stochastic_vacdh exactly the paper's eq. 16; Erlang /
                 Hyperexponential generalize it beyond both papers.

    Registered as a JAX pytree (numeric fields are leaves — including the
    window length and the distribution's parameters — so the sweep engine
    (core/sweep.py) vmaps whole hyperparameter grids through one trace;
    only ``resid`` and the distribution's *type* are static metadata).
    """

    omega: float = 1.0
    cala_beta: float = 0.5
    adapt_c: float = 25.0
    cold_rate: float = 1e-3
    window: int = 64
    resid: dataclasses.InitVar[str] = "recency"
    dist: MissLatency = Exponential()
    # Derived from ``resid`` ('rate' -> 1.0, 'recency' -> 0.0); a traced
    # leaf so the residual-estimator ablation shares one compiled graph.
    resid_rate: float | None = None

    def __post_init__(self, resid):
        if self.resid_rate is None:
            if resid not in ("rate", "recency"):
                raise ValueError(f"unknown residual estimator {resid!r}")
            object.__setattr__(self, "resid_rate",
                               1.0 if resid == "rate" else 0.0)

    @property
    def gap_alpha(self) -> float:
        return 1.0 / self.window


jax.tree_util.register_dataclass(
    PolicyParams,
    data_fields=["omega", "cala_beta", "adapt_c", "cold_rate", "window",
                 "dist", "resid_rate"],
    meta_fields=[])


# ---------------------------------------------------------------------------
# Online estimators (shared substrate)
# ---------------------------------------------------------------------------
def lambda_hat(o: ObjStats, p: PolicyParams) -> jax.Array:
    """Per-object arrival-rate estimate: inverse windowed mean inter-arrival."""
    lam = 1.0 / jnp.maximum(o.gap_mean, EPS)
    return jnp.where(o.count >= 2.0, lam, p.cold_rate)


def residual_hat(o: ObjStats, t: jax.Array,
                 p: PolicyParams | None = None) -> jax.Array:
    """Estimated residual time until the next request (paper §4's R_i).

    Default 'recency': the LRU proxy t - last_access — what VA-CDH [16]
    and the paper use ("R_i ... using LRU", §4); the paper-faithful setting.
    'rate' (1/lambda_hat — the memoryless MLE for Poisson) is this repo's
    beyond-paper improvement: it lifts the whole ranking family by ~8pp on
    synthetic workloads (EXPERIMENTS.md §Beyond).  The selector
    ``p.resid_rate`` is a traced leaf (both estimators are a handful of
    N-vector ops), so 'rate' vs 'recency' can ride a sweep-engine lane axis.
    Calling with ``p=None`` keeps the legacy rate-estimator behavior.

    Cold-start gate: an object scored at the very instant of its own
    ``last_access`` update — a same-timestamp request, or a fetch committing
    in the same f32 time slot as the miss that issued it (routine on long
    real traces, where ``t + z`` rounds back to ``t``) — has age ≈ 0.  The
    old ``max(age, EPS)`` clamp turned that into a ~1e6x rank inflation
    that steamrolled the §2.2 compare-admission check (a just-touched
    incomer evicted arbitrarily good victims).  A just-touched object's
    expected residual is its mean inter-arrival gap once that is observed
    (``count >= 2``), and the cold-rate prior ``1/cold_rate`` before; ages
    above EPS keep the paper's plain recency proxy."""
    if p is None:
        return 1.0 / jnp.maximum(lambda_hat(o, PolicyParams()), EPS)
    rate_r = 1.0 / jnp.maximum(lambda_hat(o, p), EPS)
    age = t - o.last_access
    # the observed mean gap is only a trustworthy residual when it is
    # itself non-degenerate: an object seen solely at duplicate timestamps
    # (second-granularity traces) has count >= 2 with gap_mean == 0, which
    # would reintroduce the EPS inflation through the fallback
    just_touched = jnp.where((o.count >= 2.0) & (o.gap_mean > EPS),
                             o.gap_mean,
                             1.0 / jnp.maximum(p.cold_rate, EPS))
    recency_r = jnp.where(age > EPS, age, just_touched)
    return jnp.where(jnp.asarray(p.resid_rate) > 0.5, rate_r, recency_r)


def agg_mean_hat(o: ObjStats) -> jax.Array:
    """Historical mean aggregate delay; falls back to z_est before any episode."""
    m = o.agg_sum / jnp.maximum(o.agg_cnt, 1.0)
    return jnp.where(o.agg_cnt > 0.0, m, o.z_est)


def agg_std_hat(o: ObjStats) -> jax.Array:
    """Population std of historical aggregate delay (0 before 2 episodes)."""
    n = jnp.maximum(o.agg_cnt, 1.0)
    m = o.agg_sum / n
    var = jnp.maximum(o.agg_sq_sum / n - m * m, 0.0)
    return jnp.where(o.agg_cnt >= 2.0, jnp.sqrt(var), 0.0)


# ---------------------------------------------------------------------------
# Shared scoring substrate (computed once per commit; DESIGN.md §10).
# ---------------------------------------------------------------------------
class Substrate:
    """The shared estimator state every registered rank reads from.

    Fields are [N] arrays, computed **lazily on first access** and memoized
    per instance: a statically specialized single-policy graph traces only
    the fields its epilogue touches (LRU's graph never computes a moment —
    enforced by laziness, not left to XLA dead-code elimination, so eager
    callers like the event-driven oracle and the serving engine pay only
    what they read too), while the unified multi-policy graph amortizes
    each field across every lane's epilogue that reads it.  Field
    arithmetic is lifted verbatim from the pre-substrate rank functions, so
    epilogue(substrate) is bit-for-bit the historical rank value.

    lam / resid     — lambda_hat(o, p) / residual_hat(o, t, p)
    size_eps, denom — max(sizes, EPS) and resid * size_eps (eq. 15/16's
                      normalizer)
    det_mean/std    — Theorem-1 moments (VA-CDH / LAC / CALA's model)
    dist_mean/std   — moments under ``p.dist`` (eq. 16, generalized)
    hist_mean/std   — historical episode moments (CALA / toy policies)
    last_access, count, gd_h, z_est — pass-throughs from ``ObjStats``
    """

    def __init__(self, o: ObjStats, sizes, t, p: PolicyParams):
        self.obj = o
        self.sizes = sizes
        self.t = t
        self.p = p
        self.last_access = o.last_access
        self.count = o.count
        self.gd_h = o.gd_h
        self.z_est = o.z_est

    @functools.cached_property
    def lam(self):
        return lambda_hat(self.obj, self.p)

    @functools.cached_property
    def resid(self):
        return residual_hat(self.obj, self.t, self.p)

    @functools.cached_property
    def size_eps(self):
        return jnp.maximum(self.sizes, EPS)

    @functools.cached_property
    def denom(self):
        return self.resid * self.size_eps

    @functools.cached_property
    def det_mean(self):
        return _DET.agg_mean(self.lam, self.z_est)

    @functools.cached_property
    def det_std(self):
        return _DET.agg_std(self.lam, self.z_est)

    @functools.cached_property
    def dist_mean(self):
        return self.p.dist.agg_mean(self.lam, self.z_est)

    @functools.cached_property
    def dist_std(self):
        return self.p.dist.agg_std(self.lam, self.z_est)

    @functools.cached_property
    def hist_mean(self):
        return agg_mean_hat(self.obj)

    @functools.cached_property
    def hist_std(self):
        return agg_std_hat(self.obj)


def make_substrate(o: ObjStats, sizes, t, p: PolicyParams) -> Substrate:
    """The shared (lazy, memoized) estimator pass at time ``t``."""
    return Substrate(o, sizes, t, p)


# ---------------------------------------------------------------------------
# Rank epilogues.  Signature: (substrate, params) -> scores [N] — a few
# vector ops each; everything O(N)-expensive lives in make_substrate.
# ---------------------------------------------------------------------------
EpilogueFn = Callable[[Substrate, PolicyParams], jax.Array]


def epi_lru(s, p):
    """LRU — most recently used is most valuable."""
    return s.last_access


def epi_lfu(s, p):
    """LFU — request count."""
    return s.count


def epi_lhd(s, p):
    """LHD-lite: hit density = expected hit rate per byte.

    The full LHD maintains age-binned hit/eviction histograms; under Poisson
    arrivals its hit density converges to lambda/size, which is what the
    online estimate here computes.  Documented approximation (DESIGN.md §4).
    """
    return s.lam / s.size_eps


def epi_adaptsize(s, p):
    """AdaptSize ranks like LRU; its contribution is the size-aware admission
    filter (handled by the simulator via ``admission='adaptsize'``)."""
    return s.last_access


def epi_greedydual(s, p):
    """GreedyDual H value — used by LRU-MAD / LHD-MAD; H maintained by the
    simulator (clock + cost/size on access, clock <- H_victim on eviction)."""
    return s.gd_h


def epi_lac(s, p):
    """LAC: mean aggregate delay under *deterministic* latency, per byte and
    per unit residual time (variance-blind; omega = 0)."""
    return s.det_mean / s.denom


def epi_cala(s, p):
    """CALA: weighted blend of historical AggDelay and the analytic estimate
    (balances imprecise averages vs conservative bounds, per §1)."""
    est = p.cala_beta * s.hist_mean + (1.0 - p.cala_beta) * s.det_mean
    return est / s.denom


def epi_vacdh(s, p):
    """VA-CDH [16]: eq. 15 with Theorem 1 (deterministic-latency) moments."""
    return (s.det_mean + p.omega * s.det_std) / s.denom


def epi_stochastic_vacdh(s, p):
    """THE PAPER, generalized: eq. 16 with the moments of ``p.dist``.

    With the default ``dist=Exponential()`` this is bit-for-bit the paper's
    eq. 16 (Theorem-2 closed forms); Erlang / Hyperexponential / MonteCarlo
    swap in their aggregate-delay moments via the same compound-Poisson
    identity (DESIGN.md §3)."""
    return (s.dist_mean + p.omega * s.dist_std) / s.denom


def epi_lrb_lite(s, p):
    """LRB-lite: learned-baseline stand-in — score by predicted next-use
    proximity blending recency and rate (a fixed linear model over the same
    features LRB learns; see DESIGN.md §4)."""
    # Expected remaining time to next arrival for a Poisson process given the
    # age r is 1/lam regardless; blend with recency to mimic LRB's learned mix.
    pred_next = 1.0 / jnp.maximum(s.lam, EPS) + 0.5 * s.resid
    return -pred_next / s.size_eps * s.hist_mean


def epi_toy_mean(s, p):
    """Fig.1 Policy 1 — empirical mean aggregate delay, unnormalized."""
    return s.hist_mean


def epi_toy_meanstd(s, p):
    """Fig.1 Policy 2 — empirical mean + population std, unnormalized."""
    return s.hist_mean + s.hist_std


# ---------------------------------------------------------------------------
# Legacy rank entry points.  Signature: (obj, sizes, t, params) -> [N] —
# the substrate+epilogue composition under the historical name (the
# event-driven oracle and external callers use these; same arithmetic).
# ---------------------------------------------------------------------------
RankFn = Callable[[ObjStats, jax.Array, jax.Array, PolicyParams], jax.Array]


def _rank_of(epilogue: EpilogueFn, name: str) -> RankFn:
    def rank(o, sizes, t, p):
        return epilogue(make_substrate(o, sizes, t, p), p)
    rank.__name__ = name
    rank.__qualname__ = name
    rank.__doc__ = epilogue.__doc__
    return rank


rank_lru = _rank_of(epi_lru, "rank_lru")
rank_lfu = _rank_of(epi_lfu, "rank_lfu")
rank_lhd = _rank_of(epi_lhd, "rank_lhd")
rank_adaptsize = _rank_of(epi_adaptsize, "rank_adaptsize")
rank_greedydual = _rank_of(epi_greedydual, "rank_greedydual")
rank_lac = _rank_of(epi_lac, "rank_lac")
rank_cala = _rank_of(epi_cala, "rank_cala")
rank_vacdh = _rank_of(epi_vacdh, "rank_vacdh")
rank_stochastic_vacdh = _rank_of(epi_stochastic_vacdh,
                                 "rank_stochastic_vacdh")
rank_lrb_lite = _rank_of(epi_lrb_lite, "rank_lrb_lite")
rank_toy_mean = _rank_of(epi_toy_mean, "rank_toy_mean")
rank_toy_meanstd = _rank_of(epi_toy_meanstd, "rank_toy_meanstd")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Policy:
    name: str
    rank: RankFn
    epilogue: EpilogueFn
    greedydual: bool = False       # maintain gd_h / clock
    gd_cost: str = "agg"           # 'agg' (LRU-MAD) | 'agg_rate' (LHD-MAD)
    admission: str = "always"      # 'always' | 'adaptsize'
    # Rank-compare admission (paper §2.2: only evict victims ranked strictly
    # below the incomer; abort otherwise).  True for the delayed-hit ranking
    # family (incl. GreedyDual-style MAD); False reproduces the classical
    # baselines' published always-admit behavior.
    compare_admission: bool = True


POLICIES: dict[str, Policy] = {
    "lru": Policy("lru", rank_lru, epi_lru, compare_admission=False),
    "lfu": Policy("lfu", rank_lfu, epi_lfu, compare_admission=False),
    "lhd": Policy("lhd", rank_lhd, epi_lhd, compare_admission=False),
    "adaptsize": Policy("adaptsize", rank_adaptsize, epi_adaptsize,
                        admission="adaptsize", compare_admission=False),
    "lru_mad": Policy("lru_mad", rank_greedydual, epi_greedydual,
                      greedydual=True, gd_cost="agg"),
    "lhd_mad": Policy("lhd_mad", rank_greedydual, epi_greedydual,
                      greedydual=True, gd_cost="agg_rate"),
    "lac": Policy("lac", rank_lac, epi_lac),
    "cala": Policy("cala", rank_cala, epi_cala),
    "vacdh": Policy("vacdh", rank_vacdh, epi_vacdh),
    "stoch_vacdh": Policy("stoch_vacdh", rank_stochastic_vacdh,
                          epi_stochastic_vacdh),  # ours
    "lrb_lite": Policy("lrb_lite", rank_lrb_lite, epi_lrb_lite),
    "toy_mean": Policy("toy_mean", rank_toy_mean, epi_toy_mean),
    "toy_meanstd": Policy("toy_meanstd", rank_toy_meanstd, epi_toy_meanstd),
}

OURS = "stoch_vacdh"
BASELINES = ["lru", "lfu", "lhd", "adaptsize", "lru_mad", "lhd_mad",
             "lac", "cala", "vacdh", "lrb_lite"]

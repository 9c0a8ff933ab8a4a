"""Two-tier sharded cache hierarchy: L1 edge shards fronting a shared L2.

Requests are routed across ``n_shards`` L1 caches (consistent object hash or
per-request random routing); each shard runs the full delayed-hit machinery
with its own eviction policy state.  An L1 miss becomes an *arrival at the
shared L2*, which is itself a delayed-hit cache whose misses fetch from the
origin under a pluggable :class:`repro.core.distributions.MissLatency`.  The
effective L1 fetch latency is

    Z_L1 = hop + R_L2(t),    R_L2(t) in {0, l2_complete_t - t, Z_origin}

— a round-trip hop delay plus the L2's *resolution time* at the arrival
instant (0 on an L2 hit, the residual fetch time on an L2 delayed hit, a
fresh origin draw on an L2 miss).  Delayed-hit waiter queues therefore
genuinely compose across tiers: requests queueing at an L1 shard wait on a
completion time that already embeds the L2's own queueing.  Z_L1 is *not*
exponential even when the origin fetch is — it is ``hop`` plus a state-
dependent mixture with an atom at zero — which is exactly why variance-aware
L1 ranking is interesting here (DESIGN.md §8, EXPERIMENTS.md §Hierarchy).

Implementation: one ``lax.scan`` over the interleaved request stream.  The
L1 tier is a stacked :class:`SimState` with the shard axis vmapped; lazy
fetch commits run per tier (L2's plain while-loop, the shards' lockstep
while-loop with per-shard due masks).  Everything reuses the commit/evict/
serve core from :mod:`repro.core.simulator` — :func:`_commit_one`,
:func:`_commit_due`, :func:`_serve` — parameterized by the same
:class:`_Behavior`, so per-tier semantics are the single-tier semantics by
construction (parity: :func:`repro.core.refsim.simulate_hier_ref`,
tests/test_hierarchy.py), and both tiers inherit the overhauled hot path
(shared-substrate scoring, scalar serve-path gathers, fused
rank-and-select eviction — DESIGN.md §10) for free.

Randomness (origin draws, hop draws, shard routing) is pre-drawn into
:class:`HierTrace`, so the scan, the event-driven oracle, and the sweep
engine (:func:`repro.core.sweep.sweep_hier_grid`) see bit-identical inputs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .distributions import Deterministic, MissLatency
from .ranking import POLICIES, PolicyParams
from .simulator import (SimResult, _behavior_multi, _behavior_static,
                        _commit_due, _commit_one, _serve, _tree_sel,
                        batched_update_mode)
from .state import SimState, get_col, init_state, obj_record
from .trace import Trace

__all__ = ["HierTrace", "HierResult", "make_hier_trace", "simulate_hier",
           "simulate_hier_chunked"]

# Knuth multiplicative hash — a stand-in for a consistent-hash ring: the
# shard of an object is a fixed pseudo-random function of its id, stable
# under everything but n_shards.  The shard is taken from the *high* bits
# of the 32-bit product: multiplicative hashing only mixes upward, so a
# plain modulo would reduce to ``objs % n_shards`` (the multiplier is
# ≡ 1 mod every small shard count) and colocate structured id sets.
_HASH_MULT = 2654435761


class HierTrace(NamedTuple):
    """A request trace annotated for the two-tier hierarchy.

    times     f32[T] — non-decreasing absolute request times
    objs      i32[T] — requested object id
    shards    i32[T] — L1 shard serving the request (see ``route``)
    sizes     f32[N] — object sizes
    z_mean    f32[N] — mean *origin* fetch latency per object
    z_draw    f32[T] — realized origin fetch duration if request k causes an
                       L2 miss (same pre-drawn stream as single-tier traces)
    hop_draw  f32[T] — realized round-trip L1<->L2 hop delay if request k
                       causes an L1 miss
    hop_mean  f32[]  — mean hop delay (seeds the L1 z_est prior)
    """

    times: jax.Array
    objs: jax.Array
    shards: jax.Array
    sizes: jax.Array
    z_mean: jax.Array
    z_draw: jax.Array
    hop_draw: jax.Array
    hop_mean: jax.Array

    @property
    def n_requests(self) -> int:
        return self.times.shape[0]

    @property
    def n_objects(self) -> int:
        return self.sizes.shape[0]


def make_hier_trace(trace: Trace, n_shards: int, *, key=None,
                    hop_mean: float = 0.0,
                    hop_dist: MissLatency = Deterministic(),
                    route: str = "hash") -> HierTrace:
    """Annotate a single-tier :class:`Trace` for the hierarchy.

    route — 'hash': consistent object hash; every object lives on exactly
            one L1 shard (a CDN with a hashing load balancer).
            'random': uniform per-request routing; popular objects appear on
            every shard and the L2 absorbs the cross-shard duplication (a
            skew-oblivious balancer — the regime where L2 delayed hits from
            *different* shards overlap).
    hop_dist — unit-mean shape of the hop delay, scaled by ``hop_mean``
            (any :mod:`repro.core.distributions` law; Deterministic default).
    """
    if key is None:
        key = jax.random.key(0)
    k_route, k_hop = jax.random.split(key)
    if route == "hash":
        mixed = (trace.objs.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)) >> 16
        shards = mixed % jnp.uint32(n_shards)
    elif route == "random":
        shards = jax.random.randint(k_route, (trace.n_requests,), 0, n_shards)
    else:
        raise ValueError(f"unknown route {route!r}; expected 'hash'|'random'")
    hop_draw = hop_dist.sample(
        k_hop, jnp.full((trace.n_requests,), hop_mean, jnp.float32))
    return HierTrace(trace.times, trace.objs, shards.astype(jnp.int32),
                     trace.sizes, trace.z_mean, trace.z_draw,
                     jnp.asarray(hop_draw, jnp.float32),
                     jnp.float32(hop_mean))


class HierResult(NamedTuple):
    """Per-tier outcome of a hierarchy simulation.

    ``per_shard`` fields are shaped [n_shards] (request-facing L1 view:
    latencies are end-to-end); ``l2`` is scalar — its ``total_latency`` is
    the summed L2 *resolution* time (hop excluded), a diagnostic for how
    much of the end-to-end latency the L2 absorbed.
    """

    per_shard: SimResult
    l2: SimResult

    @property
    def total_latency(self):
        return jnp.sum(self.per_shard.total_latency, axis=-1)

    @property
    def n_hits(self):
        return jnp.sum(self.per_shard.n_hits, axis=-1)

    @property
    def n_delayed(self):
        return jnp.sum(self.per_shard.n_delayed, axis=-1)

    @property
    def n_misses(self):
        return jnp.sum(self.per_shard.n_misses, axis=-1)

    @property
    def n_requests(self):
        return self.n_hits + self.n_delayed + self.n_misses

    @property
    def mean_latency(self):
        return self.total_latency / jnp.maximum(self.n_requests, 1.0)

    @property
    def hit_ratio(self):
        return self.n_hits / jnp.maximum(self.n_requests, 1.0)


def check_shards(trace: HierTrace, n_shards: int) -> None:
    """Reject shard-id/shard-count mismatches before they silently drop
    requests (a shard id with no matching lane would never be served)."""
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards} must be >= 1")
    try:
        smax = int(jnp.max(trace.shards))
    except (jax.errors.ConcretizationTypeError, TypeError):
        return      # traced inside a caller's jit — shapes checked there
    if smax >= n_shards:
        raise ValueError(
            f"trace routes to shard {smax} but n_shards={n_shards}; "
            f"rebuild the trace with make_hier_trace(trace, {n_shards})")


def _commit_due_stacked(b, p, estimate_z, stacked: SimState, sizes, t):
    """Lazy-commit for the vmapped shard axis.

    The loop runs while *any* shard has a due fetch; the body commits one
    fetch per shard, masked to shards actually due — lockstep, like the
    sweep engine's batched while-loops (DESIGN.md §7).  A masked-out shard's
    state (including its PRNG key) is untouched, so per-shard streams match
    an unstacked per-shard simulation exactly.
    """
    def one(st):
        new = _commit_one(b, p, estimate_z, st, sizes)
        return _tree_sel(st.min_complete <= t, new, st)

    return jax.lax.while_loop(
        lambda ss: jnp.any(ss.min_complete <= t),
        lambda ss: jax.vmap(one)(ss),
        stacked)


def _hier_init(trace: HierTrace, l1_capacity, l2_capacity, key,
               n_shards: int):
    """Fresh (stacked-L1, L2) carry for a hierarchy run — shared by the
    single-scan body and the chunked streaming driver so both start from
    bit-identical states (same key split, same priors)."""
    keys = jax.random.split(key, n_shards + 1)
    # L1's fetch-latency prior: hop + origin mean (the true mean lies below
    # once the L2 starts hitting; estimate_z adapts it online).
    l1_prior = trace.hop_mean + trace.z_mean
    l1 = jax.vmap(lambda k: init_state(trace.n_objects, l1_capacity, k,
                                       l1_prior))(keys[:n_shards])
    l2 = init_state(trace.n_objects, l2_capacity, keys[n_shards],
                    trace.z_mean)
    return l1, l2


def _hier_step(b1, b2, p1, p2, estimate_z, sizes, shard_ids, carry,
               t, i, s, z, hop, valid=True):
    """One interleaved-request step of the two-tier machinery.

    ``valid`` is a python ``True`` on the single-scan path (constant-folds
    to exactly the pre-chunking graph) or a traced bool on the chunked
    path, where padded steps must not serve either tier — their commits
    are already no-ops because padded steps carry ``t = -inf``
    (DESIGN.md §9)."""
    l1, l2 = carry

    # --- lazy commits, per tier (independent states, any order) ----------
    l2 = _commit_due(b2, p2, estimate_z, l2, sizes, t)
    l1 = _commit_due_stacked(b1, p1, estimate_z, l1, sizes, t)

    # --- does the request miss at its L1 shard? --------------------------
    r = obj_record(jax.vmap(lambda b: get_col(b, i))(l1.block)[s])
    is_l1_miss = ~(r.cached | r.in_flight)

    # --- conditional L2 arrival: resolution time R_L2(t) -----------------
    # the serve's write gate carries the condition (O(1) no-op writes when
    # the request hits L1 — DESIGN.md §11; the historical whole-state
    # select here cost O(state) per request); the resolution latency is
    # computed unconditionally either way.
    serve_l2 = is_l1_miss if valid is True else valid & is_l1_miss
    l2, l2_lat = _serve(b2, p2, l2, sizes, t, i, z, valid=serve_l2)
    z_eff = hop + jnp.where(is_l1_miss, l2_lat, 0.0)

    # --- serve at the owning L1 shard (gated over the shard axis) --------
    def serve_one(st, active):
        new, _ = _serve(b1, p1, st, sizes, t, i, z_eff, valid=active)
        return new

    owner = shard_ids == s
    l1 = jax.vmap(serve_one)(l1, owner if valid is True else owner & valid)
    return l1, l2


def _simulate_hier_impl(trace: HierTrace, l1_capacity, l2_capacity, key,
                        b1, b2, p1: PolicyParams, p2: PolicyParams,
                        estimate_z: bool, n_shards: int) -> HierResult:
    """Unjitted hierarchy body over prebuilt per-tier behaviors.

    The shard axis always uses a batched update lowering (one-hot or the
    lane scatter, by universe size — DESIGN.md §11): shard-local writes
    are lane-varying under the shard vmap, and the choice keeps
    sweep-engine batching bitwise-transparent on top.
    """
    sizes = trace.sizes
    l1, l2 = _hier_init(trace, l1_capacity, l2_capacity, key, n_shards)
    shard_ids = jnp.arange(n_shards)

    def step(carry, req):
        t, i, s, z, hop = req
        return _hier_step(b1, b2, p1, p2, estimate_z, sizes, shard_ids,
                          carry, t, i, s, z, hop), None

    (l1, l2), _ = jax.lax.scan(
        step, (l1, l2),
        (trace.times, trace.objs.astype(jnp.int32),
         trace.shards.astype(jnp.int32), trace.z_draw, trace.hop_draw))
    res = lambda st: SimResult(st.lat_sum, st.n_hits, st.n_delayed,
                               st.n_misses, st.n_evictions)
    return HierResult(per_shard=res(l1), l2=res(l2))


def _hier_impl_named(trace, l1_capacity, l2_capacity, key, policy_name,
                     l2_policy, params, l2_params, estimate_z, n_shards):
    """Static-policy composition point (also vmapped by sweep_hier_grid).

    Both tiers use the N-dependent batched update lowering
    (:func:`repro.core.simulator.batched_update_mode`, DESIGN.md §11):
    shard-local writes are lane-varying under the shard vmap, and the
    choice keeps sweep-engine batching bitwise-transparent on top."""
    update = batched_update_mode(trace.n_objects)
    b1 = _behavior_static(POLICIES[policy_name], params, "rank",
                          update=update)
    b2 = _behavior_static(POLICIES[l2_policy], l2_params, "rank",
                          update=update)
    return _simulate_hier_impl(trace, l1_capacity, l2_capacity, key, b1, b2,
                               params, l2_params, estimate_z, n_shards)


def _hier_multi_impl(trace, l1_capacity, l2_capacity, key, policy_idx,
                     policy_names, l2_policy, params, l2_params,
                     estimate_z, n_shards):
    """Multi-policy composition point: the L1 policy is a traced lane index
    (the L2 policy stays static — it is an environment, not a swept axis)."""
    update = batched_update_mode(trace.n_objects)
    b1 = _behavior_multi(policy_names, policy_idx, params, update=update)
    b2 = _behavior_static(POLICIES[l2_policy], l2_params, "rank",
                          update=update)
    return _simulate_hier_impl(trace, l1_capacity, l2_capacity, key, b1, b2,
                               params, l2_params, estimate_z, n_shards)


_simulate_hier = jax.jit(
    _hier_impl_named,
    static_argnames=("policy_name", "l2_policy", "estimate_z", "n_shards"))


def simulate_hier(trace: HierTrace, n_shards: int, l1_capacity: float,
                  l2_capacity: float, policy: str = "stoch_vacdh",
                  l2_policy: str = "lru",
                  params: PolicyParams | None = None,
                  l2_params: PolicyParams | None = None,
                  key=None, estimate_z: bool = True) -> HierResult:
    """Run the two-tier hierarchy over an annotated trace.

    Each L1 shard has ``l1_capacity``; the shared L2 has ``l2_capacity``.
    ``policy`` ranks every L1 shard, ``l2_policy`` the L2.  ``estimate_z``
    defaults to True here (unlike single-tier :func:`simulate`) because the
    L1's effective fetch law is composition-dependent — no analytic prior
    exists and the online estimate is the operational setting (DESIGN.md §8).

    ``l2_params`` defaults to stock :class:`PolicyParams` — NOT to
    ``params`` — so a swept L1-params axis never implicitly re-parameterizes
    the shared L2 (the sweep engine holds one L2 per grid; keeping the
    default decoupled is what makes sweep points bitwise-reproducible by
    this function).  Pass it explicitly to couple the tiers.

    Degenerate check: with ``n_shards=1``, ``l2_capacity=0`` and a zero hop,
    results are bit-identical to single-tier :func:`repro.core.simulate`
    (tests/test_hierarchy.py).
    """
    if params is None:
        params = PolicyParams()
    if l2_params is None:
        l2_params = PolicyParams()
    if key is None:
        key = jax.random.key(0)
    check_shards(trace, n_shards)
    for name in (policy, l2_policy):
        if name not in POLICIES:
            raise ValueError(f"unknown policy {name!r}; known: "
                             f"{sorted(POLICIES)}")
    return _simulate_hier(trace, jnp.float32(l1_capacity),
                          jnp.float32(l2_capacity), key, policy, l2_policy,
                          params, l2_params, estimate_z, int(n_shards))


# ---------------------------------------------------------------------------
# Chunked streaming hierarchy (DESIGN.md §9): the (stacked-L1, L2) carry
# crosses fixed-size trace chunks with donated device buffers, exactly like
# the single-tier simulate_chunked.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("policy_name", "l2_policy", "estimate_z",
                                    "n_shards"),
                   donate_argnums=(0,))
def _hier_chunk_jit(carry, times, objs, shards, z_draw, hop_draw, valid,
                    sizes, params, l2_params, policy_name, l2_policy,
                    estimate_z, n_shards):
    """``valid`` is ``None`` (static) on full chunks — the step then
    constant-folds to exactly the single-scan graph; a padded tail chunk
    threads the mask into the gated serves (DESIGN.md §11)."""
    update = batched_update_mode(sizes.shape[0])
    b1 = _behavior_static(POLICIES[policy_name], params, "rank",
                          update=update)
    b2 = _behavior_static(POLICIES[l2_policy], l2_params, "rank",
                          update=update)
    shard_ids = jnp.arange(n_shards)

    def step(carry, req):
        t, i, s, z, hop = req[:5]
        v = req[5] if len(req) == 6 else True
        return _hier_step(b1, b2, params, l2_params, estimate_z, sizes,
                          shard_ids, carry, t, i, s, z, hop, valid=v), None

    xs = (times, objs, shards, z_draw, hop_draw)
    carry, _ = jax.lax.scan(
        step, carry, xs if valid is None else xs + (valid,))
    return carry


def simulate_hier_chunked(trace: HierTrace, n_shards: int,
                          l1_capacity: float, l2_capacity: float,
                          policy: str = "stoch_vacdh",
                          l2_policy: str = "lru",
                          params: PolicyParams | None = None,
                          l2_params: PolicyParams | None = None,
                          key=None, estimate_z: bool = True,
                          chunk_size: int = 65536) -> HierResult:
    """Chunked-carry :func:`simulate_hier`: bitwise-identical results with
    O(n_shards * n_objects + chunk_size) device residency.  The tail chunk
    is padded with ``valid=False`` / ``t=-inf`` sentinels (commit loops see
    a vacuous condition; serves are masked tree-wide), so every chunk runs
    the same compiled graph and padding never perturbs the carry
    (tests/test_streaming.py pins equality across chunk sizes)."""
    if params is None:
        params = PolicyParams()
    if l2_params is None:
        l2_params = PolicyParams()
    if key is None:
        key = jax.random.key(0)
    if chunk_size < 1:
        raise ValueError(f"chunk_size={chunk_size} must be >= 1")
    check_shards(trace, n_shards)
    for name in (policy, l2_policy):
        if name not in POLICIES:
            raise ValueError(f"unknown policy {name!r}; known: "
                             f"{sorted(POLICIES)}")
    times = np.asarray(trace.times, np.float32)
    objs = np.asarray(trace.objs, np.int32)
    shards = np.asarray(trace.shards, np.int32)
    z_draw = np.asarray(trace.z_draw, np.float32)
    hop_draw = np.asarray(trace.hop_draw, np.float32)
    sizes = jnp.asarray(trace.sizes)

    carry = _hier_init(trace, jnp.float32(l1_capacity),
                       jnp.float32(l2_capacity), key, int(n_shards))
    n = times.shape[0]
    for lo in range(0, max(n, 1), chunk_size):
        hi = min(lo + chunk_size, n)
        pad = chunk_size - (hi - lo)
        ext = lambda x, fill, dt: np.concatenate(
            [x[lo:hi], np.full(pad, fill, dt)])
        carry = _hier_chunk_jit(
            carry,
            jnp.asarray(ext(times, -np.inf, np.float32)),
            jnp.asarray(ext(objs, 0, np.int32)),
            jnp.asarray(ext(shards, 0, np.int32)),
            jnp.asarray(ext(z_draw, 0.0, np.float32)),
            jnp.asarray(ext(hop_draw, 0.0, np.float32)),
            None if pad == 0 else jnp.asarray(np.concatenate(
                [np.ones(hi - lo, bool), np.zeros(pad, bool)])),
            sizes, params, l2_params, policy, l2_policy, estimate_z,
            int(n_shards))
    l1, l2 = carry
    res = lambda st: SimResult(st.lat_sum, st.n_hits, st.n_delayed,
                               st.n_misses, st.n_evictions)
    return HierResult(per_shard=res(l1), l2=res(l2))

"""Serving engine: delayed-hit prefix cache semantics."""
import numpy as np
import pytest

from repro.serving.engine import (DelayedHitPrefixCache, EngineStats,
                                  LatencyModel, PrefixEntry, ServeEngine)


def test_engine_hit_delayed_miss_accounting():
    eng = ServeEngine(capacity=10.0, policy="lru",
                      latency=LatencyModel(base_s=1.0, per_token_s=0.0,
                                           stochastic=False),
                      state_size_fn=lambda n: 1.0, hedging=False)
    # t=0 miss (fetch completes t=1); t=0.5 delayed hit (0.5s); t=2 hit.
    l0 = eng.request(0.0, "p1", 100)
    l1 = eng.request(0.5, "p1", 100)
    l2 = eng.request(2.0, "p1", 100)
    assert l0 == pytest.approx(1.0)
    assert l1 == pytest.approx(0.5)
    assert l2 == 0.0
    s = eng.stats.as_dict()
    assert (s["misses"], s["delayed_hits"], s["hits"]) == (1, 1, 1)
    assert s["total_latency"] == pytest.approx(1.5)


def test_engine_eviction_respects_capacity():
    eng = ServeEngine(capacity=2.0, policy="lru",
                      latency=LatencyModel(base_s=0.1, per_token_s=0.0,
                                           stochastic=False),
                      state_size_fn=lambda n: 1.0, hedging=False)
    t = 0.0
    for i, k in enumerate(["a", "b", "c"]):
        eng.request(t + i, k, 10)
    eng.request(10.0, "d", 10)      # commits a,b,c; d misses; evictions occur
    assert eng.cache.free >= 0
    occupied = sum(e.size for e in eng.cache.entries.values())
    assert occupied <= 2.0 + 1e-6


def test_engine_variance_aware_beats_lru_on_zipf_workload():
    """End-to-end A/B: paper's policy vs LRU on a skewed prefix workload
    with stochastic prefill latency."""
    rng = np.random.default_rng(0)
    n_prefix = 60
    probs = (np.arange(1, n_prefix + 1) ** -1.0)
    probs /= probs.sum()
    t, times, keys, lens = 0.0, [], [], []
    lengths = rng.integers(64, 2048, n_prefix)
    for _ in range(8000):
        t += rng.exponential(0.004)
        k = rng.choice(n_prefix, p=probs)
        times.append(t)
        keys.append(f"p{k}")
        lens.append(int(lengths[k]))

    def run(policy):
        eng = ServeEngine(capacity=6000.0, policy=policy,
                          latency=LatencyModel(base_s=0.02,
                                               per_token_s=5e-5),
                          state_size_fn=lambda n: float(n), seed=7)
        return eng.run_trace(times, keys, lens).as_dict()

    ours = run("stoch_vacdh")
    lru = run("lru")
    assert ours["total_latency"] < lru["total_latency"]


def test_engine_hedging_reduces_tail_latency():
    def run(hedging):
        rng_times = np.arange(0.0, 50.0, 0.05)
        eng = ServeEngine(capacity=1.0, policy="lru",
                          latency=LatencyModel(base_s=0.2, per_token_s=0.0,
                                               stochastic=True),
                          state_size_fn=lambda n: 2.0,  # never admissible
                          hedging=hedging, seed=3)
        for i, t in enumerate(rng_times):
            eng.request(float(t), f"k{i}", 10)   # all unique -> all misses
        return eng.stats
    base = run(False)
    hedged = run(True)
    assert hedged.hedges > 0
    assert hedged.total_latency < base.total_latency


def test_hedging_model_is_min_of_first_draw_and_hedged_retry():
    """The sim-clock hedging model is min(Z1, t_hedge + Z2'): reproduce the
    engine's draw stream with an identically-seeded rng and check the served
    miss latency equals the formula exactly."""
    lm = LatencyModel(base_s=0.2, per_token_s=0.0, stochastic=True,
                      hedge_quantile=0.95)
    deadline = lm.hedge_deadline(10)
    # Exp quantile: -m * ln(1 - q)
    assert deadline == pytest.approx(-0.2 * np.log(0.05))
    hedged = unhedged = 0
    for seed in range(40):
        eng = ServeEngine(capacity=1.0, policy="lru", latency=lm,
                          state_size_fn=lambda n: 1.0, hedging=True,
                          seed=seed)
        lat = eng.request(0.0, "k", 10)
        shadow = np.random.default_rng(seed)
        z1 = lm.draw(shadow, 10)
        if z1 > deadline:
            z2 = lm.draw(shadow, 10)
            assert lat == pytest.approx(min(z1, deadline + z2))
            assert eng.stats.hedges == 1
            hedged += 1
        else:
            assert lat == pytest.approx(z1)
            assert eng.stats.hedges == 0
            unhedged += 1
    assert hedged > 0 and unhedged > 0   # both branches exercised


def test_hedged_fetch_never_slower_than_first_draw():
    lm = LatencyModel(base_s=0.1, per_token_s=0.0, stochastic=True)
    for seed in range(30):
        eng = ServeEngine(capacity=1.0, policy="lru", latency=lm,
                          state_size_fn=lambda n: 1.0, hedging=True,
                          seed=seed)
        lat = eng.request(0.0, "k", 5)
        z1 = lm.draw(np.random.default_rng(seed), 5)
        assert lat <= z1 + 1e-12


def test_engine_hierarchy_mode_composes_delayed_hit_queues():
    """Two L1 edge engines sharing one L2: an L1 miss resolves as
    hop + R_L2(t), and concurrent misses from *different* L1s overlap on
    the same L2 in-flight fetch (cross-shard L2 delayed hit)."""
    det = LatencyModel(base_s=1.0, per_token_s=0.0, stochastic=False)
    l2 = ServeEngine(capacity=100.0, policy="lru", latency=det,
                     state_size_fn=lambda n: 1.0, hedging=False)
    mk_l1 = lambda: ServeEngine(capacity=100.0, policy="lru",
                                state_size_fn=lambda n: 1.0,
                                l2=l2, hop_s=0.01)
    l1a, l1b = mk_l1(), mk_l1()
    # t=0: a misses; L2 misses (origin fetch completes at t=1).
    assert l1a.request(0.0, "p", 10) == pytest.approx(1.01)
    # t=0.4: b misses; L2 delayed hit — residual 0.6 plus the hop.
    assert l1b.request(0.4, "p", 10) == pytest.approx(0.61)
    # after both L1 prefill completions, both serve hits locally.
    assert l1a.request(2.0, "p", 10) == 0.0
    assert l1b.request(2.0, "p", 10) == 0.0
    s2 = l2.stats.as_dict()
    assert (s2["misses"], s2["delayed_hits"], s2["hits"]) == (1, 1, 0)
    assert l1a.stats.hedges == 0        # hedging disabled in hierarchy mode


def test_engine_hierarchy_warm_l2_serves_fast_refetch():
    """Once the L2 holds the prefix, a fresh L1 miss costs only the hop."""
    det = LatencyModel(base_s=1.0, per_token_s=0.0, stochastic=False)
    l2 = ServeEngine(capacity=100.0, policy="lru", latency=det,
                     state_size_fn=lambda n: 1.0, hedging=False)
    l1 = ServeEngine(capacity=1.0, policy="lru",
                     state_size_fn=lambda n: 2.0,   # never L1-admissible
                     l2=l2, hop_s=0.05)
    assert l1.request(0.0, "p", 10) == pytest.approx(1.05)
    # L2 admits at t=1; the L1 copy was never admitted (size > capacity),
    # so the re-request misses at L1 again but hits the warm L2.
    assert l1.request(5.0, "p", 10) == pytest.approx(0.05)
    assert l2.stats.hits == 1


def test_hedged_miss_enqueues_loser_event_then_drops_it_stale():
    """A hedged fetch issues TWO completion events (winner + loser); the
    loser must be dropped by _commit_due's stale guard, committing the
    entry exactly once."""
    lm = LatencyModel(base_s=0.2, per_token_s=0.0, stochastic=True)
    saw_hedge = False
    for seed in range(40):
        eng = ServeEngine(capacity=10.0, policy="lru", latency=lm,
                          state_size_fn=lambda n: 1.0, hedging=True,
                          seed=seed)
        eng.request(0.0, "k", 10)
        if not eng.stats.hedges:
            assert len(eng.events) == 1
            continue
        saw_hedge = True
        assert len(eng.events) == 2
        eng.request(1e9, "other", 10)     # drains both events
        assert "k" not in eng.pending
        i = eng.cache.key_to_idx["k"]
        assert bool(eng.cache.obj.cached[i])
        assert not bool(eng.cache.obj.in_flight[i])
        # exactly one admission: occupancy = k + other's in-flight zero
        assert eng.cache.free == pytest.approx(9.0)
    assert saw_hedge


def test_stale_event_does_not_destroy_newer_pending_entry():
    """Regression (the bench_serving KeyError): a hedged loser event that
    fires AFTER its key re-missed must not evict the newer fetch's pending
    entry — a later delayed hit would otherwise find in_flight set with no
    pending entry."""
    from repro.serving.engine import PrefixEntry
    eng = ServeEngine(capacity=10.0, policy="lru",
                      latency=LatencyModel(base_s=1.0, per_token_s=0.0,
                                           stochastic=False),
                      state_size_fn=lambda n: 1.0, hedging=False)
    lat0 = eng.request(0.0, "k", 10)          # miss, completes at t=1
    assert lat0 == pytest.approx(1.0)
    # inject a stale duplicate event (as a lost hedge would leave behind)
    import heapq
    eng._seq += 1
    heapq.heappush(eng.events, (0.5, eng._seq, "k"))
    lat1 = eng.request(0.6, "k", 10)          # pops the stale event first
    assert "k" in eng.pending                 # newer entry survived
    assert lat1 == pytest.approx(0.4)         # delayed hit on the real fetch
    assert eng.stats.delayed_hits == 1
    assert eng.request(2.0, "k", 10) == 0.0   # real completion committed
    assert eng.stats.hits == 1


def test_hedged_loser_after_re_miss_keeps_queue_consistent():
    """End-to-end version of the stale-drop regression: with an engine
    whose admissions always fail (size > capacity), a hedged loser event
    interleaves with a re-miss of the same key; subsequent delayed hits
    must still find their pending entry."""
    lm = LatencyModel(base_s=0.3, per_token_s=0.0, stochastic=True)
    exercised = 0
    for seed in range(60):
        eng = ServeEngine(capacity=1.0, policy="lru", latency=lm,
                          state_size_fn=lambda n: 2.0,  # never admissible
                          hedging=True, seed=seed)
        eng.request(0.0, "k", 10)
        if not eng.stats.hedges:
            continue
        (w_t, _, _), (l_t, _, _) = sorted(eng.events)[:2]
        # re-miss between winner and loser, then touch after the loser:
        # the stale loser event must not destroy the re-miss's entry
        eng.request(0.5 * (w_t + l_t), "k", 10)
        eng.request(l_t + 1e-6, "k", 10)      # delayed hit or fresh miss
        assert ("k" in eng.pending) == bool(
            eng.cache.obj.in_flight[eng.cache.key_to_idx["k"]])
        exercised += 1
    assert exercised > 0


def test_hierarchy_hedging_disabled_at_l1_only_l2_origin_hedges():
    """In hierarchy mode the L1's 'fetch' is a queue position at the L2 —
    duplicating it cannot win, so hedging must stay off at the L1 even
    when requested, while the L2's origin fetches hedge normally."""
    lm = LatencyModel(base_s=0.2, per_token_s=0.0, stochastic=True)
    l2 = ServeEngine(capacity=1.0, policy="lru", latency=lm,
                     state_size_fn=lambda n: 2.0,    # L2 never admits
                     hedging=True, seed=11)
    l1 = ServeEngine(capacity=1.0, policy="lru",
                     state_size_fn=lambda n: 2.0,    # L1 never admits
                     hedging=True,                   # requested, but inert
                     l2=l2, hop_s=0.01, seed=12)
    for i, t in enumerate(np.arange(0.0, 30.0, 0.05)):
        l1.request(float(t), f"k{i}", 10)            # all unique -> misses
    assert l1.stats.hedges == 0
    assert l2.stats.hedges > 0
    assert l2.stats.misses == l1.stats.misses


def test_latency_scale_hook_scales_mean_and_hedge_deadline():
    """The brownout hook (DESIGN.md §12): mean and hedge deadline at issue
    time t are both multiplied by scale_fn(t)."""
    scale = lambda t: 5.0 if 10.0 <= t < 20.0 else 1.0
    lm = LatencyModel(base_s=1.0, per_token_s=0.0, stochastic=False,
                      scale_fn=scale)
    assert lm.mean(10, t=0.0) == pytest.approx(1.0)
    assert lm.mean(10, t=15.0) == pytest.approx(5.0)
    assert lm.hedge_deadline(10, t=15.0) == pytest.approx(
        5.0 * lm.hedge_deadline(10, t=0.0))
    assert lm.mean(10) == pytest.approx(1.0)      # no t: hook bypassed
    eng = ServeEngine(capacity=100.0, policy="lru", latency=lm,
                      state_size_fn=lambda n: 1.0, hedging=False)
    assert eng.request(0.0, "a", 10) == pytest.approx(1.0)
    assert eng.request(15.0, "b", 10) == pytest.approx(5.0)
    assert eng.request(25.0, "c", 10) == pytest.approx(1.0)


def test_hierarchy_hop_callable_composes_with_brownout():
    """hop_s may be time-varying: an L1 miss at t pays hop_s(t) plus the
    L2 resolution — the hierarchy leg of the brownout composition."""
    det = LatencyModel(base_s=1.0, per_token_s=0.0, stochastic=False)
    l2 = ServeEngine(capacity=100.0, policy="lru", latency=det,
                     state_size_fn=lambda n: 1.0, hedging=False)
    l1 = ServeEngine(capacity=1.0, policy="lru",
                     state_size_fn=lambda n: 2.0,    # never L1-admissible
                     l2=l2, hop_s=lambda t: 0.01 if t < 5.0 else 0.07)
    assert l1.request(0.0, "p", 10) == pytest.approx(1.01)
    # warm L2 after t=1; second L1 miss pays only the (degraded) hop
    assert l1.request(6.0, "p", 10) == pytest.approx(0.07)


def test_prefix_cache_stats_mirror_core_ranking():
    c = DelayedHitPrefixCache(10.0, "stoch_vacdh")
    for t in (1.0, 2.0, 3.0):
        c.touch("a", t)
    i = c.key_to_idx["a"]
    assert c.obj.count[i] == 3.0
    assert c.obj.gap_mean[i] == pytest.approx(1.0)


def test_prefix_table_reclaims_dead_slots_instead_of_raising():
    """Regression (ISSUE 10 satellite): keys that were touched but never
    cached (admission failed, or never fetched) used to hold their
    key_to_idx slot forever — long one-hit-heavy traces exhausted
    max_objects and crashed with "prefix table full".  Dead slots are
    now reclaimed, stalest first."""
    cache = DelayedHitPrefixCache(capacity=1.0, policy="lru", max_objects=4)
    for i in range(20):                     # 5x the table size
        cache.touch(f"k{i}", float(i))
    assert len(cache.key_to_idx) <= 4
    # the survivors are the most recently touched keys
    assert "k19" in cache.key_to_idx
    assert "k0" not in cache.key_to_idx
    # a reclaimed slot restarts with clean statistics
    i19 = cache.key_to_idx["k19"]
    assert cache.obj.count[i19] == 1.0
    assert not cache.obj.cached[i19]


def test_prefix_table_raises_only_when_every_slot_is_live():
    cache = DelayedHitPrefixCache(capacity=2.0, policy="lru", max_objects=2)
    stats = EngineStats()
    for j, k in enumerate(["a", "b"]):
        i = cache.touch(k, float(j))
        cache.obj.in_flight[i] = True
        cache.obj.issue_t[i] = float(j)
        entry = PrefixEntry(k, 10, 1.0, complete_t=10.0 + j)
        assert cache.admit(entry, 10.0 + j, stats)
    with pytest.raises(RuntimeError, match="prefix table full"):
        cache.touch("c", 20.0)


def test_engine_survives_one_hit_flood_at_small_max_objects():
    """End-to-end: far more distinct never-reused prefixes than table
    slots, with admissions failing (entries larger than capacity) — the
    engine must keep serving instead of crashing."""
    eng = ServeEngine(capacity=0.5, policy="lru",
                      latency=LatencyModel(base_s=0.01, per_token_s=0.0,
                                           stochastic=False),
                      state_size_fn=lambda n: 1.0, hedging=False,
                      max_objects=8)
    for i in range(200):
        eng.request(0.1 * i, f"one_hit_{i}", 10)
    assert eng.stats.misses == 200
    assert len(eng.cache.key_to_idx) <= 8

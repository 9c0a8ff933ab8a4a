"""Vectorized delayed-hit cache simulator.

One ``lax.scan`` step per request; fetch completions are committed lazily —
before serving the request at time t, every outstanding fetch with
``complete_t <= t`` is committed *in completion-time order* (a while_loop),
each with its own admission/eviction decision evaluated at its exact
completion time.  This makes the scan semantics identical to a classical
event-driven simulation (verified against :mod:`repro.core.refsim`).

Eviction follows the paper's §2.2 semantics: evict the lowest-ranked cached
object while its rank is strictly below the incoming object's rank; if space
still cannot be freed, the incoming object is not admitted.

The per-commit scoring hot path is one shared-substrate pass
(:func:`repro.core.ranking.make_substrate`) with the policy's rank as a
cheap epilogue, fused with a masked top-E victim-order select that the
evict-until-fit loop consumes in O(1) per victim (DESIGN.md §10); it can
run through the fused Pallas kernel (:mod:`repro.kernels.ranking_score`)
via ``use_kernel`` — compiled on TPU; interpret-mode or the jnp reference
on CPU, named explicitly (DESIGN.md §3).  The unjitted
:func:`_simulate_impl` is the composition point for :mod:`repro.core.sweep`, which vmaps it over whole
hyperparameter grids.

The commit/evict/serve core is deliberately exposed as free functions over
``(_Behavior, PolicyParams, SimState)`` — :func:`_commit_one`,
:func:`_commit_due`, and :func:`_serve` — so the two-tier hierarchy
simulator (:mod:`repro.core.hierarchy`, DESIGN.md §8) composes the exact
same machinery per tier instead of forking it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from .distributions import Exponential
from .ranking import (POLICIES, Policy, PolicyParams, agg_mean_hat,
                      epi_stochastic_vacdh, lambda_hat, make_substrate)
from .spans import scope, span
from .state import (ROW, SimState, SlotState, SlotView, fresh_record,
                    get_col, init_slot_state, init_state, kahan_add,
                    lane_set_col, obj_record, obj_rows, onehot_col,
                    pack_record, set_col, shift_times, slot_home, slot_probe,
                    slot_table_size)
from .trace import RequestStream, Trace, stream_of_trace

_EPS = 1e-6

# How many victims the fused rank-and-select pass pre-orders per commit
# (DESIGN.md §10).  Evicting more than EVICT_TOP objects for one admission
# falls back to the legacy per-eviction argmin loop (bitwise-identical
# continuation); 0 disables the precomputed order entirely — the pre-overhaul
# graph, kept as the parity suite's reference (tests/test_hotpath.py).
EVICT_TOP = 8


def _tree_sel(flag, new, old):
    """Pytree-wide flag select (works on typed PRNG key leaves)."""
    return jax.tree.map(lambda a, b: jnp.where(flag, a, b), new, old)

# Scoring backends for the commit-time ranking pass (static per simulation):
#   'rank'             — the policy's jnp rank function (default)
#   'kernel'           — fused Pallas kernel, compiled (TPU)
#   'kernel_interpret' — fused Pallas kernel, interpret mode (any backend)
#   'ref'              — kernels.ref jnp oracle (same math, any backend)
_SCORE_MODES = ("rank", "kernel", "kernel_interpret", "ref")

# State-update lowerings (_Behavior.update; DESIGN.md §11): 'scatter' for
# unbatched graphs, 'lane' for batched ones (the custom_vmap diagonal-
# scatter seam), 'onehot' as the historical parity oracle.
_UPDATE_MODES = ("scatter", "onehot", "lane")

# Batched-graph crossover (DESIGN.md §11): a one-hot write costs O(N)
# elements per lane but lowers to one fused select; a diagonal scatter
# touches O(1) elements per lane but costs a gather+scatter op pair whose
# fixed per-op overhead dominates tiny tables on XLA:CPU.  Measured on the
# 11-policy roster (EXPERIMENTS.md §Perf iteration 6): one-hot wins at
# N <= 1000, the lane scatter wins 1.4x at N = 3000 — the threshold sits
# at the measured crossover.  Results are bitwise identical either way;
# this picks dispatch shape only.
LANE_UPDATE_MIN_OBJECTS = 2048


def batched_update_mode(n_objects: int) -> str:
    """The default state-update lowering for a *batched* graph over a
    universe of ``n_objects`` (unbatched graphs always use 'scatter')."""
    return "lane" if n_objects >= LANE_UPDATE_MIN_OBJECTS else "onehot"


# Commit-scoring dispatch for the multi-policy sweep engine (DESIGN.md §14):
#   'lockstep' — the historical vmapped graph: one graph over the whole lane
#                axis, every lane runs the commit body whenever any lane has
#                a due commit, and the vmapped lax.cond makes every lane pay
#                the full substrate + all P epilogues per iteration (the
#                recorded 0.54x canary).
#   'compact'  — static policy-grouped dispatch: the lane->policy map is
#                static python in sweep_grid, so lanes are grouped by policy
#                and each group runs a statically specialized behavior
#                (exactly one epilogue in the graph).  Singleton groups run
#                the unbatched per-point body, where lax.cond genuinely
#                skips scoring on fit-without-eviction commits; larger
#                groups vmap same-policy lanes, scoping the cond-union
#                penalty to lanes that share a policy.  Per-lane arithmetic
#                is exactly the per-point simulate graph, so results are
#                bitwise identical (tests/test_hotpath.py).
# Two gather-compact structures (serialize commits through one unbatched
# switch body; bucket the K earliest-completing lanes per iteration) were
# measured SLOWER than lockstep at N=3000 — the batch-level while_loop's
# per-iteration state gather/scatter exceeds the union savings on this
# dispatch-bound container (EXPERIMENTS.md §Perf iteration 8).
_COMMIT_MODES = ("lockstep", "compact")

# Commit-dispatch crossover: grouped dispatch compiles one graph per policy
# (vs one for the whole set) and gives up cross-policy batching.  At small N
# batching is the win — the N=100 roster keeps its measured 2.75x unified
# advantage — while at N >= this threshold the per-commit substrate dominates
# and the lockstep union penalty flips the sign (EXPERIMENTS.md §Perf
# iteration 8), so grouped dispatch pays.
COMPACT_COMMIT_MIN_OBJECTS = 2048


def batched_commit_mode(n_objects: int) -> str:
    """The default commit-scoring dispatch for a batched multi-policy graph
    over ``n_objects`` (single-policy and fabric graphs are lockstep)."""
    return ("compact" if n_objects >= COMPACT_COMMIT_MIN_OBJECTS
            else "lockstep")


def _sel(flag, a, b):
    """Flag-select that constant-folds python bools at trace time.

    Policy behavior (GreedyDual upkeep, AdaptSize admission, rank-compare
    eviction) is expressed through this so ONE simulation body serves both
    the static per-policy path (flags are python bools — the graph is
    exactly the specialized one) and the sweep engine's multi-policy path
    (flags are per-lane traced scalars indexed by a policy id)."""
    if isinstance(flag, (bool, np.bool_)):
        return a if flag else b
    return jnp.where(flag, a, b)


class _Behavior(NamedTuple):
    """How one simulation lane ranks, admits, and writes — possibly traced.

    ``select(o, sizes, t, top) -> (ranks [N], idx [top], vals [top])`` is
    the fused rank-and-select pass: the full score vector plus the masked
    ascending victim order (DESIGN.md §10), closing over policy/params;
    ``greedydual``/``gd_rate``/``adaptsize``/``compare_admission`` mirror
    :class:`repro.core.ranking.Policy` flags, as python bools (static path)
    or traced 0-d bools (multi-policy path).  Static python-False flags
    fold the corresponding machinery out of the traced graph altogether
    (:func:`_static_false`); traced flags keep the lockstep selects.  Three
    fields are always python-static:

    ``split_key`` — whether the admission coin stream is advanced every
    commit (always True in multi mode so lanes stay in lockstep; only
    AdaptSize consumes the coin either way).

    ``update`` — state-update lowering, one of :data:`_UPDATE_MODES`
    (DESIGN.md §11): how a column of the ``[F, N]`` state block is written.
    'scatter': one dynamic-update-slice, the unbatched fast path.  'lane':
    the ``custom_vmap`` lane seam — the same write unbatched, ONE diagonal
    scatter over the stacked ``[L, F, N]`` state when the graph is vmapped
    (O(F) per lane; the batched lowering for large universes).  'onehot':
    one masked select over the block, the historical batched lowering,
    kept as the parity oracle.  All three write bit-identical states, so
    the choice never shows up in results (tests/test_hotpath.py,
    tests/test_sweep.py).

    ``evict_top`` — length of the precomputed victim order consumed by the
    evict-until-fit loop (module default :data:`EVICT_TOP`; 0 = legacy
    per-eviction argmin only).  Any value yields bitwise-identical results
    (tests/test_hotpath.py) — it is purely a dispatch-shape knob.

    The writes take ``valid`` (python ``True``, constant-folded to the
    plain write, or a traced bool): an invalid write stores the column's
    own bits back — an O(1) no-op in the scatter/lane lowerings, a mask
    term in the one-hot one — which is what lets the streaming engine's
    padded tail steps run the normal step graph instead of a whole-state
    select tree (DESIGN.md §11).
    """

    select: object
    greedydual: object
    gd_rate: object
    adaptsize: object
    compare_admission: object
    split_key: bool
    update: str
    evict_top: int

    # --- state writes (see ``update``) -----------------------------------
    def pack(self, r):
        """A record of scalars as the column :meth:`write_col` writes."""
        return pack_record(r, unbatched=self.update == "scatter")

    def write_col(self, block, j, col, valid=True):
        """``block[:, j] = col`` where ``valid``."""
        if self.update == "onehot":
            hot = onehot_col(block, j)
            if valid is not True:
                hot = hot & valid
            col = jnp.expand_dims(col, tuple(range(1, block.ndim)))
            return jnp.where(hot, col, block)
        if valid is not True:
            col = jnp.where(valid, col, get_col(block, j))
        return lane_set_col(block, j, col) if self.update == "lane" \
            else set_col(block, j, col)

    def cond_set_flag(self, block, row, j, cond, val):
        """``block[row, j] = val`` where ``cond`` (the eviction/admission
        writes to the ``cached`` row): one column read and one column
        write, bit-identical to the historical whole-table select.  The
        one-hot lowering selects over that row alone."""
        if self.update == "onehot":
            hot = onehot_col(block, j)[0] & cond
            return jax.lax.dynamic_update_index_in_dim(
                block, jnp.where(hot, val, block[row]), row, 0)
        col = get_col(block, j)
        at_row = jnp.arange(col.shape[-1]) == row
        return self.write_col(block, j, jnp.where(at_row & cond, val, col))


def _static_false(flag) -> bool:
    """True iff ``flag`` is a *python-static* False — the machinery it
    guards can then be omitted from the traced graph altogether (stronger
    than ``_sel``'s constant fold: not even a self-assignment is traced)."""
    return isinstance(flag, (bool, np.bool_)) and not bool(flag)


def _empty_order(top: int):
    return jnp.zeros((top,), jnp.int32), jnp.zeros((top,), jnp.float32)


def _kernelable(q: Policy, p: PolicyParams, score_mode: str) -> bool:
    """May this (policy, dist, mode) score through the kernel family?
    The kernel hard-codes Theorem-2 (Exponential) moments — everything
    else scores via its epilogue.  The ONE eligibility rule for both the
    static and multi-policy paths."""
    return (score_mode != "rank" and q.epilogue is epi_stochastic_vacdh
            and isinstance(p.dist, Exponential))


def _kernel_row(q: Policy, p: PolicyParams, score_mode: str, sub, o, sizes):
    """Eq.-16 score row via the kernel family, or None when this policy
    must score via its epilogue (shared by the static and multi paths so
    backend routing cannot drift between them)."""
    if not _kernelable(q, p, score_mode):
        return None
    if score_mode == "ref":
        from repro.kernels.ref import ranking_scores_ref
        ranks, _, _ = ranking_scores_ref(sub.lam, sub.z_est, sub.resid,
                                         sizes, o.cached, p.omega)
        return ranks
    from repro.kernels.ranking_score import ranking_scores
    ranks, _, _ = ranking_scores(
        sub.lam, sub.z_est, sub.resid, sizes, o.cached, omega=p.omega,
        interpret=(score_mode == "kernel_interpret"))
    return ranks


def _rank_select_static(policy: Policy, p: PolicyParams, score_mode: str,
                        o, sizes, t, top: int):
    """Statically specialized fused scoring pass (the commit hot path).

    One :func:`repro.core.ranking.make_substrate` pass, the policy's
    epilogue over it, and the masked ascending victim order.  ``score_mode``
    routes the eq.-16 policy through the fused Pallas kernel
    (:func:`repro.kernels.ranking_score.ranking_victim_order`) or its jnp
    oracle; every other policy scores via its epilogue (substrate fields
    are lazy — only the ones the epilogue reads are ever computed).
    """
    sub = make_substrate(o, sizes, t, p)
    if (top and _kernelable(policy, p, score_mode)
            and score_mode in ("kernel", "kernel_interpret")):
        from repro.kernels.ranking_score import ranking_victim_order
        return ranking_victim_order(
            sub.lam, sub.z_est, sub.resid, sizes, o.cached,
            omega=p.omega, top=top,
            interpret=(score_mode == "kernel_interpret"))
    ranks = _kernel_row(policy, p, score_mode, sub, o, sizes)
    if ranks is None:
        ranks = policy.epilogue(sub, p)
    if not top:
        return (ranks, *_empty_order(0))
    from repro.kernels.ref import victim_order_ref
    idx, vals = victim_order_ref(ranks, o.cached, top)
    return ranks, idx, vals


def _behavior_static(policy: Policy, p: PolicyParams, score_mode: str,
                     update: str = "scatter",
                     evict_top: int | None = None) -> _Behavior:
    if update not in _UPDATE_MODES:
        raise ValueError(f"update={update!r}; expected one of {_UPDATE_MODES}")
    return _Behavior(
        select=lambda o, sizes, t, top: _rank_select_static(
            policy, p, score_mode, o, sizes, t, top),
        greedydual=policy.greedydual,
        gd_rate=policy.gd_cost == "agg_rate",
        adaptsize=policy.admission == "adaptsize",
        compare_admission=policy.compare_admission,
        split_key=policy.admission == "adaptsize",
        update=update,
        evict_top=EVICT_TOP if evict_top is None else int(evict_top))


def _behavior_multi(policy_names: tuple, policy_idx, p: PolicyParams,
                    score_mode: str = "rank",
                    evict_top: int | None = None,
                    update: str = "lane") -> _Behavior:
    """One lane of the unified multi-policy graph.

    The shared estimator substrate is computed ONCE per commit; every
    registered policy's rank is then a few-op *epilogue* over it and the
    lane's traced ``policy_idx`` gathers its row — O(N + P·N_cheap) per
    commit instead of the historical P full rank stacks (DESIGN.md §10).
    Behavior flags come from constant lookup tables indexed the same way.
    ``score_mode`` routes the eq.-16 lane's row through the kernel family
    (used by :func:`latency_improvement`; the sweep engine keeps 'rank')."""
    pols = [POLICIES[n] for n in policy_names]
    flag = lambda f: jnp.asarray(np.array([f(q) for q in pols]))[policy_idx]

    def row(q, sub, o, sizes):
        r = _kernel_row(q, p, score_mode, sub, o, sizes)
        return q.epilogue(sub, p) if r is None else r

    def select(o, sizes, t, top):
        sub = make_substrate(o, sizes, t, p)
        ranks = jnp.stack([row(q, sub, o, sizes) for q in pols])[policy_idx]
        if not top:
            return (ranks, *_empty_order(0))
        from repro.kernels.ref import victim_order_ref
        idx, vals = victim_order_ref(ranks, o.cached, top)
        return ranks, idx, vals

    if update not in _UPDATE_MODES:
        raise ValueError(f"update={update!r}; expected one of {_UPDATE_MODES}")
    return _Behavior(
        select=select,
        greedydual=flag(lambda q: q.greedydual),
        gd_rate=flag(lambda q: q.gd_cost == "agg_rate"),
        adaptsize=flag(lambda q: q.admission == "adaptsize"),
        compare_admission=flag(lambda q: q.compare_admission),
        split_key=True,
        update=update,
        evict_top=EVICT_TOP if evict_top is None else int(evict_top))


class SimResult(NamedTuple):
    total_latency: jax.Array
    n_hits: jax.Array
    n_delayed: jax.Array
    n_misses: jax.Array
    n_evictions: jax.Array

    @property
    def n_requests(self):
        return self.n_hits + self.n_delayed + self.n_misses

    @property
    def mean_latency(self):
        return self.total_latency / jnp.maximum(self.n_requests, 1.0)

    @property
    def hit_ratio(self):
        return self.n_hits / jnp.maximum(self.n_requests, 1.0)


def _gd_cost(b: _Behavior, r, size, p: PolicyParams):
    """GreedyDual cost term (MAD-style aggregate-delay costs) of one
    object's record ``r`` of size ``size`` — scalar arithmetic, never an
    [N] vector (DESIGN.md §10; elementwise ops on one object's values are
    bit-identical to indexing the full-table estimators)."""
    cost = agg_mean_hat(r)
    cost = _sel(b.gd_rate, cost * lambda_hat(r, p), cost)
    return cost / jnp.maximum(size, _EPS)


def _argmin_id(vals, ids):
    """The commit loop's victim/next-commit pick.  ``ids=None`` (dense
    state) is a plain ``jnp.argmin`` — position IS the object id, so ties
    break by id already.  The slot-table engine passes its ``key_tab`` so
    ties break by *object id* instead of hash-dependent slot index
    (:func:`repro.kernels.ref.tiebreak_argmin_ref`), which is what keeps
    slot-mode results bitwise identical to dense and hash-seed invariant."""
    if ids is None:
        return jnp.argmin(vals)
    from repro.kernels.ref import tiebreak_argmin_ref
    return tiebreak_argmin_ref(vals, ids)


def _commit_one(b: _Behavior, p: PolicyParams, estimate_z: bool,
                state: SimState, sizes: jax.Array, ids=None) -> SimState:
    """Commit the earliest completed outstanding fetch (admission+eviction).

    Hot-path structure (DESIGN.md §10): the fused rank-and-select pass —
    one substrate + epilogue scoring sweep plus the masked ascending victim
    order — is ``lax.cond``-gated on the commit actually needing space, so
    fit-without-eviction commits (and, under the traced AdaptSize coin,
    rejected admissions) skip the whole O(N) scoring pass in unbatched
    graphs.  The evict-until-fit loop then walks the precomputed order in
    O(1) per victim (phase 1, up to ``b.evict_top`` victims) and only falls
    back to the legacy per-eviction full-table argmin beyond that (phase 2
    — a bitwise-identical continuation, since evicting only ever removes
    entries from the masked table the order was computed over).
    """
    n = sizes.shape[0]
    block = state.block
    o = obj_rows(block)
    done_t = jnp.where(o.in_flight, o.complete_t, jnp.inf)
    j = _argmin_id(done_t, ids)
    r = obj_record(get_col(block, j))
    t_c = r.complete_t
    realized = t_c - r.issue_t
    ep = r.episode_delay

    # --- finalize the miss episode's statistics -------------------------
    r = r._replace(agg_sum=r.agg_sum + ep, agg_sq_sum=r.agg_sq_sum + ep * ep,
                   agg_cnt=r.agg_cnt + 1.0, episode_delay=0.0,
                   in_flight=False, complete_t=jnp.inf)
    if estimate_z:
        r = r._replace(z_est=0.7 * r.z_est + 0.3 * realized)

    # --- admission coin (AdaptSize) --------------------------------------
    key = state.key
    s_j = sizes[j]
    if b.split_key:
        key, sub = jax.random.split(key)
        p_admit = jnp.exp(-s_j / p.adapt_c)
        admit_ok = _sel(b.adaptsize, jax.random.uniform(sub) < p_admit,
                        jnp.asarray(True))
    else:
        admit_ok = jnp.asarray(True)

    # --- GreedyDual H refresh at the exact completion time ---------------
    gd_clock = state.gd_clock
    if not _static_false(b.greedydual):
        hj = gd_clock + _gd_cost(b, r, s_j, p)
        r = r._replace(gd_h=_sel(b.greedydual, hj, r.gd_h))
    block = b.write_col(block, j, b.pack(r))
    o = obj_rows(block)
    min_complete = jnp.min(jnp.where(o.in_flight, o.complete_t, jnp.inf))
    top = min(b.evict_top, n)

    # --- fused rank-and-select, gated on the commit needing space --------
    def rank_select():
        with scope("rank_select"):
            return b.select(o, sizes, t_c, top)

    def skip_select():
        return (jnp.zeros((n,), jnp.float32), *_empty_order(top))

    ranks, order_idx, order_vals = jax.lax.cond(
        admit_ok & (state.free < s_j), rank_select, skip_select)
    rank_j = ranks[j]
    cmp = _sel(b.compare_admission, rank_j, jnp.inf)

    # --- evict-until-fit (only victims ranked strictly below incomer) ----
    # phase 1: walk the precomputed ascending victim order, O(1) each
    def cond1(carry):
        block, free, clock, ok, nev, k = carry
        return ok & (free < s_j) & (k < top)

    def body1(carry):
        block, free, clock, ok, nev, k = carry
        v = order_idx[k]
        vv = order_vals[k]
        can = vv < cmp
        block = b.cond_set_flag(block, ROW.cached, v, can, 0.0)
        free = jnp.where(can, free + sizes[v], free)
        nev = jnp.where(can, nev + 1.0, nev)
        clock = _sel(b.greedydual,
                     jnp.where(can, jnp.maximum(clock, vv), clock), clock)
        return block, free, clock, can, nev, k + 1

    if top:
        with scope("evict"):
            block, free, gd_clock, fit_ok, n_ev, _ = jax.lax.while_loop(
                cond1, body1, (block, state.free, gd_clock, admit_ok,
                               state.n_evictions, jnp.int32(0)))
    else:       # evict_top=0: the legacy graph — phase 2 does all the work
        free, fit_ok, n_ev = state.free, admit_ok, state.n_evictions

    # phase 2: legacy per-eviction argmin — runs only when one admission
    # needs more than ``top`` victims (rare; zero iterations otherwise)
    def cond2(carry):
        block, free, clock, ok, nev = carry
        return ok & (free < s_j)

    def body2(carry):
        block, free, clock, ok, nev = carry
        v = _argmin_id(jnp.where(obj_rows(block).cached, ranks, jnp.inf), ids)
        # the victim's masked rank from its own column, not from the
        # masked table (which the argmin then need not keep)
        vv = jnp.where(obj_record(get_col(block, v)).cached, ranks[v], jnp.inf)
        can = vv < cmp
        block = b.cond_set_flag(block, ROW.cached, v, can, 0.0)
        free = jnp.where(can, free + sizes[v], free)
        nev = jnp.where(can, nev + 1.0, nev)
        clock = _sel(b.greedydual,
                     jnp.where(can, jnp.maximum(clock, vv), clock), clock)
        return block, free, clock, can, nev

    with scope("evict"):
        block, free, gd_clock, fit_ok, n_ev = jax.lax.while_loop(
            cond2, body2, (block, free, gd_clock, fit_ok, n_ev))

    do_admit = admit_ok & fit_ok & (free >= s_j)
    block = b.cond_set_flag(block, ROW.cached, j, do_admit, 1.0)
    free = jnp.where(do_admit, free - s_j, free)

    return state._replace(block=block, free=free, gd_clock=gd_clock,
                          min_complete=min_complete, key=key,
                          n_evictions=n_ev)


@scope("commit")
def _commit_due(b: _Behavior, p: PolicyParams, estimate_z: bool,
                state: SimState, sizes: jax.Array, t, ids=None) -> SimState:
    """Commit every outstanding fetch with ``complete_t <= t``, in
    completion-time order (the lazy-commit loop run before serving each
    request; see the module docstring).  ``ids`` is the slot-table engine's
    id map (:func:`_argmin_id`); dense callers leave it None."""
    return jax.lax.while_loop(
        lambda s: s.min_complete <= t,
        lambda s: _commit_one(b, p, estimate_z, s, sizes, ids),
        state)


@scope("serve")
def _serve(b: _Behavior, p: PolicyParams, state: SimState,
           sizes: jax.Array, t, i, z_realized, valid=True):
    """Serve the request (t, i); z_realized is used only if it's a miss.

    Returns ``(state, latency)``: the latency is also accumulated into the
    state's Kahan sum, but callers that feed one tier's resolution time into
    another tier's fetch (the hierarchy, DESIGN.md §8) need it directly.

    This path is O(1) per request in unbatched graphs — the object's
    column of the state block is read in one slice, its new values are
    computed as scalars, and the column is written back in one update; the
    GreedyDual upkeep (the one historical O(N) full-table cost build) is
    scalar arithmetic on that record and is folded out of the graph
    entirely for statically non-GreedyDual policies (DESIGN.md §10).

    ``valid`` gates every state write (DESIGN.md §11): python ``True``
    constant-folds to the plain serve; a traced bool makes the serve a
    bitwise no-op on the state when False — the column write stores the
    column's own bits back (O(1)), scalar accumulators are selected —
    while the returned latency is computed either way (the hierarchy reads
    it off conditional L2 serves).  This replaces the historical
    whole-state select tree for padded streaming steps and the
    hierarchy's owner/L2 masks, whose per-step O(state) cost was the
    measured ~3x padded-tail penalty (EXPERIMENTS.md §Perf iteration 6).
    """
    gate = (lambda f: f) if valid is True else (lambda f: f & valid)
    r = obj_record(get_col(state.block, i))
    is_hit = r.cached
    is_delayed = r.in_flight
    is_miss = ~(is_hit | is_delayed)

    lat_delayed = jnp.maximum(r.complete_t - t, 0.0)
    lat = jnp.where(is_hit, 0.0, jnp.where(is_delayed, lat_delayed, z_realized))

    # --- miss: issue fetch ------------------------------------------------
    comp = jnp.where(is_miss, t + z_realized, r.complete_t)
    min_complete = jnp.minimum(state.min_complete,
                               jnp.where(gate(is_miss), comp, jnp.inf))

    # --- access statistics (every request) --------------------------------
    cnt = r.count
    gap = t - r.last_access
    # running mean for the first `window` gaps, then EWMA(1/window):
    a_eff = jnp.maximum(1.0 / p.window, 1.0 / jnp.maximum(cnt, 1.0))
    gm = jnp.where(cnt <= 0.0, r.gap_mean,
                   jnp.where(cnt == 1.0, gap,
                             r.gap_mean + a_eff * (gap - r.gap_mean)))
    r = r._replace(
        in_flight=is_miss | r.in_flight,
        complete_t=comp,
        issue_t=jnp.where(is_miss, t, r.issue_t),
        episode_delay=jnp.where(
            is_miss, z_realized,
            r.episode_delay + jnp.where(is_delayed, lat, 0.0)),
        gap_mean=gm,
        first_access=jnp.where(cnt == 0.0, t, r.first_access),
        last_access=t,
        count=cnt + 1.0,
    )
    if not _static_false(b.greedydual):
        hi = state.gd_clock + _gd_cost(b, r, sizes[i], p)
        r = r._replace(gd_h=_sel(b.greedydual, jnp.where(is_hit, hi, r.gd_h),
                                 r.gd_h))
    block = b.write_col(state.block, i, b.pack(r), valid)

    lat_sum, lat_comp = kahan_add(state.lat_sum, state.lat_comp, lat)
    if valid is not True:
        lat_sum = jnp.where(valid, lat_sum, state.lat_sum)
        lat_comp = jnp.where(valid, lat_comp, state.lat_comp)
    state = state._replace(
        block=block, min_complete=min_complete,
        lat_sum=lat_sum, lat_comp=lat_comp,
        n_hits=state.n_hits + gate(is_hit),
        n_delayed=state.n_delayed + gate(is_delayed),
        n_misses=state.n_misses + gate(is_miss),
    )
    return state, lat


def _run_scan(b: _Behavior, trace: Trace, capacity, key,
              params: PolicyParams, estimate_z: bool) -> SimResult:
    state = init_state(trace.n_objects, capacity, key, trace.z_mean)

    def step(state: SimState, req):
        t, i, z = req
        state = _commit_due(b, params, estimate_z, state, trace.sizes, t)
        state, _ = _serve(b, params, state, trace.sizes, t, i, z)
        return state, None

    state, _ = jax.lax.scan(
        step, state, (trace.times, trace.objs.astype(jnp.int32), trace.z_draw))
    return SimResult(state.lat_sum, state.n_hits, state.n_delayed,
                     state.n_misses, state.n_evictions)


def _run_chunk(b: _Behavior, params: PolicyParams, estimate_z: bool,
               state: SimState, sizes: jax.Array, chunk) -> SimState:
    """Scan one chunk of requests, carrying ``SimState``.

    ``chunk`` is ``(times, objs, z_draw)`` for a full chunk — the step is
    then *exactly* :func:`_run_scan`'s, so a sequence of chunks is bitwise
    identical to one scan over the concatenation — or
    ``(times, objs, z_draw, valid)`` for the padded tail chunk.  Padded
    steps carry ``valid=False`` and ``t=-inf``: the commit loop's
    condition ``min_complete <= -inf`` is vacuously false (a bitwise no-op
    on the state), and the serve's writes are gated O(1) no-ops
    (:func:`_serve` ``valid``).  The historical whole-state select tree
    here cost ~3x per padded step (measured — it was most of the PR-4
    "dispatch-bound" streaming loss, EXPERIMENTS.md §Perf iteration 6);
    full chunks still compile the gate-free graph.
    """
    def step(state: SimState, req):
        t, i, z = req[:3]
        new = _commit_due(b, params, estimate_z, state, sizes, t)
        new, _ = _serve(b, params, new, sizes, t, i, z,
                        valid=req[3] if len(req) == 4 else True)
        return new, None

    state, _ = jax.lax.scan(step, state, chunk)
    return state


@functools.partial(jax.jit,
                   static_argnames=("policy_name", "estimate_z",
                                    "score_mode", "evict_top"),
                   donate_argnums=(0,))
def _chunk_step_jit(state: SimState, times, objs, z_draw, valid, delta,
                    sizes, params: PolicyParams, policy_name: str,
                    estimate_z: bool, score_mode: str,
                    evict_top: int | None = None) -> SimState:
    """One donated-carry chunk dispatch: rebase the carried state's absolute
    times by ``delta`` (0.0 is a bitwise no-op), then scan the chunk.  The
    state argument is donated, so the per-object state occupies one set of
    device buffers for the whole streamed trace.  ``valid`` is ``None``
    (static: the gate-free full-chunk graph) except on a padded tail."""
    b = _behavior_static(POLICIES[policy_name], params, score_mode, "scatter",
                         evict_top)
    state = shift_times(state, delta)
    chunk = (times, objs, z_draw) if valid is None \
        else (times, objs, z_draw, valid)
    return _run_chunk(b, params, estimate_z, state, sizes, chunk)


def _result_of_state(state: SimState) -> SimResult:
    return SimResult(state.lat_sum, state.n_hits, state.n_delayed,
                     state.n_misses, state.n_evictions)


class SlotResult(NamedTuple):
    """A slot-table replay's :class:`SimResult` fields, then the table's
    own counters (:class:`repro.core.state.SlotView`)."""

    total_latency: jax.Array
    n_hits: jax.Array
    n_delayed: jax.Array
    n_misses: jax.Array
    n_evictions: jax.Array
    n_inserts: jax.Array
    n_reclaims: jax.Array

    n_requests = SimResult.n_requests
    mean_latency = SimResult.mean_latency
    hit_ratio = SimResult.hit_ratio


def _slot_result_of_state(state: SlotState) -> SlotResult:
    return SlotResult(*_result_of_state(state.sim), state.tab.n_inserts,
                      state.tab.n_reclaims)


# ---------------------------------------------------------------------------
# Sparse slot-table engine (DESIGN.md §14): the dense commit/serve machinery
# runs unchanged over an [S]-shaped slot axis; a hashed open-addressing
# table (repro.core.state.SlotView) maps raw object ids onto slots at serve
# time.  Bitwise parity with dense mode holds by construction whenever the
# table never fills: per-object arithmetic runs on the column of the
# object's slot, and every reduction over the slot axis is either
# order-independent (min) or id-tiebroken (_argmin_id), so the hash seed
# and slot layout cannot leak into results (tests/test_slots.py).
# ---------------------------------------------------------------------------
@scope("slot_lookup")
def _slot_lookup_insert(state: SlotState, obj, size, zp, valid):
    """Resolve ``obj`` to its slot, inserting on first touch.

    Returns ``(state, slot)``.  Objects keep their slot for the rest of the
    replay (dense mode retains evicted objects' statistics, so eager slot
    freeing would diverge bitwise); under table-full pressure the first
    non-in-flight slot in probe order is reclaimed instead — its occupant
    is evicted if cached and its statistics reset to first-touch values (a
    documented approximation that never fires when the table is sized to
    the universe, :func:`repro.core.state.slot_table_size`).  Every insert
    counts in the table's ``n_inserts``, a reclaim also in ``n_reclaims``.
    ``valid`` gates insertion on padded streaming steps (python True
    constant-folds).
    """
    tab = state.tab
    slot, found, has_space = slot_probe(tab.key_tab, obj, tab.seed)
    fresh = ~found
    if valid is not True:
        fresh = fresh & valid

    def insert(st: SlotState):
        sim, tb = st.sim, st.tab
        n = tb.key_tab.shape[0]

        def reclaimed():
            # table full: first non-in-flight slot in probe order from the
            # home slot (in-flight slots carry an outstanding fetch the
            # commit loop still owns); all-in-flight falls back to the home
            # slot itself, dropping that fetch.
            h = slot_home(obj, tb.seed, n)
            dist = (jnp.arange(n, dtype=jnp.int32) - h) % n
            cand = jnp.where(sim.obj.in_flight, jnp.int32(n), dist)
            d = jnp.min(cand)
            return (h + jnp.where(d < n, d, 0)) % n, jnp.int32(1)

        v, reclaim = jax.lax.cond(has_space, lambda: (slot, jnp.int32(0)),
                                  reclaimed)
        was = obj_record(get_col(sim.block, v))
        block = set_col(sim.block, v,
                        pack_record(fresh_record(zp), unbatched=True))
        free = jnp.where(was.cached, sim.free + tb.sizes[v], sim.free)
        nev = jnp.where(was.cached, sim.n_evictions + 1.0, sim.n_evictions)
        # reclaiming an in-flight slot invalidates the cached min: recompute
        # (rare; O(S) only inside this branch)
        o = obj_rows(block)
        min_c = jax.lax.cond(
            was.in_flight,
            lambda: jnp.min(jnp.where(o.in_flight, o.complete_t, jnp.inf)),
            lambda: sim.min_complete)
        tb = tb._replace(key_tab=tb.key_tab.at[v].set(obj),
                         sizes=tb.sizes.at[v].set(size),
                         n_inserts=tb.n_inserts + 1,
                         n_reclaims=tb.n_reclaims + reclaim)
        return SlotState(sim=sim._replace(block=block, free=free,
                                          n_evictions=nev,
                                          min_complete=min_c), tab=tb), v

    return jax.lax.cond(fresh, insert, lambda st: (st, slot), state)


@functools.partial(jax.jit, static_argnames=("policy_name", "estimate_z",
                                             "score_mode"),
                   donate_argnums=(0,))
def _slot_chunk_step_jit(state: SlotState, times, objs, z_draw, valid, delta,
                         sizes_full, z_prior_full, params: PolicyParams,
                         policy_name: str, estimate_z: bool,
                         score_mode: str) -> SlotState:
    """One donated-carry chunk dispatch of the slot-table engine.

    Mirrors :func:`_chunk_step_jit` with three differences: the per-step
    serve is preceded by the table lookup/insert; per-object sizes and
    z-priors are gathered per request from the full-universe host arrays
    (``sizes_full``/``z_prior_full`` — the only [N_universe] device arrays
    the engine keeps); and ``evict_top`` is pinned to 0 — the precomputed
    victim order tie-breaks by slot index, which cannot reproduce dense id
    order, while the phase-2 argmin path is id-tiebroken (evict_top is
    bitwise invisible in dense results, so nothing is lost).
    """
    b = _behavior_static(POLICIES[policy_name], params, score_mode, "scatter",
                         evict_top=0)
    state = state._replace(sim=shift_times(state.sim, delta))

    def step(st: SlotState, req):
        t, i, z = req[:3]
        v = True if valid is None else req[3]
        sim = _commit_due(b, params, estimate_z, st.sim, st.tab.sizes, t,
                          ids=st.tab.key_tab)
        st, slot = _slot_lookup_insert(st._replace(sim=sim), i,
                                       sizes_full[i], z_prior_full[i], v)
        sim, _ = _serve(b, params, st.sim, st.tab.sizes, t, slot, z, valid=v)
        return st._replace(sim=sim), None

    chunk = (times, objs, z_draw) if valid is None \
        else (times, objs, z_draw, valid)
    state, _ = jax.lax.scan(step, state, chunk)
    return state


def _stream_chunks(times64, objs, z_draw, chunk_size: int, rebase: bool):
    """Host-side chunk builder: yields ``(device_arrays, valid, delta)`` per
    chunk — the pure prep half of the stream loop, so the dispatch loop can
    run it one chunk AHEAD of the executing chunk (double buffering).
    ``jax.device_put`` enqueues the transfer without blocking, so on
    accelerator backends chunk k+1 ships while chunk k computes; on CPU it
    overlaps the numpy slicing with the async scan dispatch."""
    base = 0.0
    n = times64.shape[0]
    for lo in range(0, max(n, 1), chunk_size):
        hi = min(lo + chunk_size, n)
        new_base = float(times64[lo]) if (rebase and hi > lo) else base
        pad = chunk_size - (hi - lo)
        t_loc = (times64[lo:hi] - new_base).astype(np.float32)
        chunk_t = np.concatenate([t_loc, np.full(pad, -np.inf, np.float32)])
        chunk_i = np.concatenate([objs[lo:hi], np.zeros(pad, np.int32)])
        chunk_z = np.concatenate([z_draw[lo:hi], np.zeros(pad, np.float32)])
        valid = None if pad == 0 else jax.device_put(np.concatenate(
            [np.ones(hi - lo, bool), np.zeros(pad, bool)]))
        yield (jax.device_put(chunk_t), jax.device_put(chunk_i),
               jax.device_put(chunk_z), valid,
               jnp.float32(new_base - base))
        base = new_base


def resolve_chunk_size(chunk_size, n_requests: int) -> int:
    """Map the user-facing ``chunk_size`` to a concrete size: an int passes
    through; ``'auto'``/``None`` picks the pad-minimizing size via
    :func:`repro.core.trace.auto_chunk_size` (a padded tail step costs the
    same as a real one under the gated serve, but it still *computes*, so
    zero pad is strictly better when the trace length is known)."""
    if chunk_size is None or chunk_size == "auto":
        from .trace import auto_chunk_size
        return auto_chunk_size(n_requests)
    if isinstance(chunk_size, str):
        raise ValueError(f"chunk_size={chunk_size!r}; the only string "
                         f"value is 'auto' (or pass an int / None)")
    if chunk_size < 1:
        raise ValueError(f"chunk_size={chunk_size} must be >= 1")
    return int(chunk_size)


def simulate_stream(stream: RequestStream, capacity: float,
                    policy: str = "stoch_vacdh",
                    params: PolicyParams | None = None, key=None,
                    estimate_z: bool = False, use_kernel=False,
                    chunk_size: int | str | None = 65536,
                    rebase: bool = True,
                    evict_top: int | None = None,
                    prefetch: bool = True,
                    state_mode: str = "dense",
                    n_slots: int | None = None,
                    slot_seed: int = 0) -> SimResult | SlotResult:
    """Run one policy over a host-resident stream, one chunk at a time.

    Device residency is O(n_objects + chunk_size) regardless of trace
    length: each fixed-size chunk is shipped to the device, scanned with
    the carried (donated) :class:`SimState`, and released.  The tail chunk
    is padded with ``valid=False`` sentinels so every chunk shares one
    compiled graph; padded steps run the normal step graph with O(1)-gated
    writes (DESIGN.md §11).  ``chunk_size='auto'`` picks the
    pad-minimizing size (:func:`repro.core.trace.auto_chunk_size`).

    ``prefetch=True`` double-buffers the dispatch pipeline: chunk k+1 is
    sliced, converted, and shipped to the device while chunk k's scan
    executes, and aggregates stay device-resident (Kahan sums in the
    carried state) until the single pull at the end — the host never
    blocks on a chunk boundary.  ``prefetch=False`` runs the historical
    strictly-sequential loop; both orders feed identical arrays to the
    same compiled graph, so results are bit-for-bit equal
    (tests/test_streaming.py pins it).

    ``rebase=True`` (the long-trace default) re-anchors each chunk to its
    own start time: the f64 host timestamps are converted to f32 *offsets
    from the chunk base*, and the carried state's absolute-time fields are
    shifted by the (f64-computed) base delta at each boundary.  Gap/recency
    precision is then set by the chunk span instead of total elapsed time —
    past ~2^24 time units an unrebased f32 clock silently swallows
    inter-arrival gaps (`tests/test_streaming.py` pins shift invariance).
    ``rebase=False`` feeds absolute f32 times and is bitwise identical to
    :func:`simulate` on any trace that fits on device.

    ``state_mode='slots'`` routes through the sparse slot-table engine
    (DESIGN.md §14): per-object state lives in a hashed open-addressing
    table of ``n_slots`` slots (default: sized to the stream's distinct
    key count, :func:`repro.core.state.slot_table_size`) instead of a
    dense ``[N]`` struct, so million-object universes replay at bounded
    RSS.  Results are bitwise identical to dense mode whenever the table
    never fills (tests/test_slots.py); ``slot_seed`` picks the hash seed
    and is bitwise invisible in results.  Its result is a
    :class:`SlotResult`: the same fields, then the table's ``n_inserts``
    and ``n_reclaims``.  Both modes run the same host loop under the same
    ``repro.stream.*`` spans.
    """
    if params is None:
        params = PolicyParams()
    if key is None:
        key = jax.random.key(0)
    chunk_size = resolve_chunk_size(chunk_size, stream.n_requests)
    score_mode = resolve_score_mode(use_kernel)
    if state_mode not in ("dense", "slots"):
        raise ValueError(f"state_mode={state_mode!r}; expected 'dense' or "
                         f"'slots'")
    if state_mode == "slots" and evict_top not in (None, 0):
        raise ValueError(
            f"evict_top={evict_top} is not supported with "
            f"state_mode='slots' — the precomputed victim order "
            f"tie-breaks by slot index, which cannot reproduce dense "
            f"object-id order; the slot engine pins evict_top=0 (the "
            f"id-tiebroken argmin path, bitwise identical in dense "
            f"results)")
    if state_mode == "dense" and n_slots is not None:
        raise ValueError("n_slots applies only with state_mode='slots'")
    with span("stream.init"):
        times64 = np.asarray(stream.times, np.float64)
        objs = np.asarray(stream.objs, np.int32)
        z_draw = np.asarray(stream.z_draw, np.float32)
        # state.key is donated with the rest of the carry — keep the
        # caller's key array alive by seeding the state with a copy.
        if state_mode == "slots":
            # the slot engine's only [N_universe] device arrays
            sizes_full = jnp.asarray(stream.sizes, jnp.float32)
            z_prior_full = jnp.asarray(stream.z_mean, jnp.float32)
            if n_slots is None:
                n_slots = slot_table_size(int(np.unique(objs).size))
            state = init_slot_state(int(n_slots), jnp.float32(capacity),
                                    jnp.asarray(key).copy(), slot_seed)

            def run_chunk(state, t, i, z, valid, delta):
                return _slot_chunk_step_jit(
                    state, t, i, z, valid, delta, sizes_full, z_prior_full,
                    params, policy, estimate_z, score_mode)
            result_of = _slot_result_of_state
        else:
            sizes = jnp.asarray(stream.sizes, jnp.float32)
            state = init_state(stream.n_objects, jnp.float32(capacity),
                               jnp.asarray(key).copy(),
                               jnp.asarray(stream.z_mean, jnp.float32))

            def run_chunk(state, t, i, z, valid, delta):
                return _chunk_step_jit(state, t, i, z, valid, delta, sizes,
                                       params, policy, estimate_z,
                                       score_mode, evict_top)
            result_of = _result_of_state

    def dispatch(state, chunk):
        with span("stream.dispatch"):
            return run_chunk(state, *chunk)

    # the builder yields exactly n_chunks chunks; pulling no further keeps
    # every prep span around real work (a suspended generator holds none)
    chunks = _stream_chunks(times64, objs, z_draw, chunk_size, rebase)
    n_chunks = -(-max(times64.shape[0], 1) // chunk_size)

    def prep():
        with span("stream.prep"):
            return next(chunks)

    if prefetch:
        # one-chunk lookahead: pull chunk k+1 from the builder (host slice
        # + async device_put) BEFORE dispatching chunk k's scan, so the
        # prep/transfer of the next chunk overlaps the current execution
        # even on backends whose dispatch is not fully asynchronous.
        pending = prep()
        for k in range(n_chunks):
            cur = pending
            if k + 1 < n_chunks:
                pending = prep()
            state = dispatch(state, cur)
    else:
        for _ in range(n_chunks):
            state = dispatch(state, prep())
    return result_of(state)


def simulate_chunked(trace: Trace, capacity: float,
                     policy: str = "stoch_vacdh",
                     params: PolicyParams | None = None, key=None,
                     estimate_z: bool = False, use_kernel=False,
                     chunk_size: int = 65536,
                     evict_top: int | None = None,
                     state_mode: str = "dense",
                     n_slots: int | None = None,
                     slot_seed: int = 0) -> SimResult:
    """Chunked-carry :func:`simulate`: bitwise-identical results, O(chunk)
    trace residency.  Equivalent to ``simulate_stream(stream_of_trace(t),
    rebase=False)`` — the f64 widening round-trips every f32 time exactly
    (tests/test_streaming.py pins bitwise equality across chunk sizes).
    ``state_mode='slots'`` selects the sparse slot-table engine (see
    :func:`simulate_stream`)."""
    return simulate_stream(stream_of_trace(trace), capacity, policy, params,
                           key, estimate_z, use_kernel, chunk_size,
                           rebase=False, evict_top=evict_top,
                           state_mode=state_mode, n_slots=n_slots,
                           slot_seed=slot_seed)


def _simulate_impl(trace: Trace, capacity, key, policy_name: str,
                   params: PolicyParams, estimate_z: bool,
                   score_mode: str = "rank",
                   update: str = "scatter",
                   evict_top: int | None = None) -> SimResult:
    """Unjitted single-policy simulation body (statically specialized).

    ``update`` selects the state-update lowering (DESIGN.md §11) — the
    sweep engine passes 'lane' when the graph is actually batched."""
    b = _behavior_static(POLICIES[policy_name], params, score_mode, update,
                         evict_top)
    return _run_scan(b, trace, capacity, key, params, estimate_z)


def _simulate_multi_impl(trace: Trace, capacity, key, policy_idx,
                         params: PolicyParams, policy_names: tuple,
                         estimate_z: bool,
                         score_mode: str = "rank",
                         update: str | None = None) -> SimResult:
    """Unjitted multi-policy body: the policy is a traced lane index, so one
    compiled graph serves a whole policies x hyperparameter grid
    (:mod:`repro.core.sweep`).  ``update=None`` auto-selects the batched
    lowering by universe size (:func:`batched_update_mode`)."""
    if update is None:
        update = batched_update_mode(trace.n_objects)
    b = _behavior_multi(policy_names, policy_idx, params, score_mode,
                        update=update)
    return _run_scan(b, trace, capacity, key, params, estimate_z)


_simulate = jax.jit(_simulate_impl,
                    static_argnames=("policy_name", "estimate_z",
                                     "score_mode", "evict_top"))


def resolve_score_mode(use_kernel) -> str:
    """Map the user-facing ``use_kernel`` flag to a static scoring backend.

    False -> 'rank'; True -> the compiled kernel, which needs a TPU (off
    one it raises rather than quietly scoring elsewhere);
    'interpret'/'ref'/'kernel' force a specific backend."""
    if use_kernel is False or use_kernel is None:
        return "rank"
    if use_kernel is True:
        if jax.default_backend() != "tpu":
            raise ValueError(
                f"use_kernel=True compiles the Pallas scoring kernel for a "
                f"TPU, but the default backend is "
                f"{jax.default_backend()!r}; pass use_kernel='interpret' "
                f"(Pallas interpreter) or 'ref' (jnp oracle) off-TPU")
        return "kernel"
    if use_kernel == "interpret":
        return "kernel_interpret"
    if use_kernel in _SCORE_MODES:
        return use_kernel
    raise ValueError(f"use_kernel={use_kernel!r}; expected bool, 'interpret', "
                     f"or one of {_SCORE_MODES}")


def simulate(trace: Trace, capacity: float, policy: str = "stoch_vacdh",
             params: PolicyParams | None = None, key=None,
             estimate_z: bool = False, use_kernel=False,
             evict_top: int | None = None,
             state_mode: str = "dense",
             n_slots: int | None = None,
             slot_seed: int = 0) -> SimResult:
    """Run one policy over a trace.

    ``params`` rides through jit as a pytree (numeric fields traced — omega /
    window / distribution-parameter sweeps don't retrace).  ``use_kernel``
    routes the commit-time scoring pass through the fused Pallas kernel for
    the eq.-16 policy (see :func:`resolve_score_mode`).  ``evict_top``
    overrides the precomputed victim-order length (:data:`EVICT_TOP`; 0 =
    the legacy per-eviction argmin graph — results are bitwise identical
    for every setting, tests/test_hotpath.py).  ``state_mode='slots'``
    routes through the sparse slot-table engine — bitwise identical to
    dense whenever the table never fills (see :func:`simulate_stream`)."""
    if params is None:
        params = PolicyParams()
    if key is None:
        key = jax.random.key(0)
    if state_mode != "dense":
        return simulate_stream(stream_of_trace(trace), capacity, policy,
                               params, key, estimate_z, use_kernel,
                               chunk_size="auto", rebase=False,
                               evict_top=evict_top, state_mode=state_mode,
                               n_slots=n_slots, slot_seed=slot_seed)
    if n_slots is not None:
        raise ValueError("n_slots applies only with state_mode='slots'")
    return _simulate(trace, jnp.float32(capacity), key, policy, params,
                     estimate_z, resolve_score_mode(use_kernel),
                     evict_top=evict_top)


@functools.partial(jax.jit, static_argnames=("policy_names", "estimate_z",
                                             "score_mode"))
def _improvement_pair(trace: Trace, capacity, key, params: PolicyParams,
                      policy_names: tuple, estimate_z: bool,
                      score_mode: str) -> SimResult:
    """Policy and baseline as two lanes of ONE compiled unified graph."""
    def lane(li):
        return _simulate_multi_impl(trace, capacity, key, li, params,
                                    policy_names, estimate_z, score_mode)

    return jax.vmap(lane)(jnp.arange(len(policy_names)))


def latency_improvement(trace: Trace, capacity: float, policy: str,
                        baseline: str = "lru",
                        params: PolicyParams | None = None, key=None,
                        estimate_z: bool = False,
                        use_kernel=False) -> jax.Array:
    """Paper eq. 17: (Latency(LRU) - Latency(A)) / Latency(LRU).

    The policy and the baseline run as two lanes of one compiled
    multi-policy graph (shared substrate + two epilogues) instead of two
    independent ``simulate`` dispatches — one trace, one compile, and on
    batched backends one fused dispatch.  Per-lane arithmetic is bitwise
    identical to the per-policy ``simulate`` calls (the sweep engine's
    lane contract, tests/test_sweep.py).  ``key`` seeds both lanes (the
    AdaptSize admission coin stream); ``use_kernel`` routes an eq.-16 lane
    through the fused kernel family."""
    if params is None:
        params = PolicyParams()
    if key is None:
        key = jax.random.key(0)
    for name in (policy, baseline):
        if name not in POLICIES:
            raise ValueError(f"unknown policy {name!r}; known: "
                             f"{sorted(POLICIES)}")
    res = _improvement_pair(trace, jnp.float32(capacity), key, params,
                            (policy, baseline), estimate_z,
                            resolve_score_mode(use_kernel))
    la, lb = res.total_latency[0], res.total_latency[1]
    return (lb - la) / lb

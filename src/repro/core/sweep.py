"""Batched multi-scenario sweep engine.

The benchmarks' historical shape was one Python-level ``simulate`` call per
(policy, omega, cache-size, seed) grid point — each a separate dispatch of a
separately compiled scan.  This module runs the whole grid

    traces x policies x PolicyParams x cache sizes x seeds

through ONE jit-compiled call.  Two mechanisms make that possible:

* numeric hyperparameters (omega, window, distribution parameters, the
  residual-estimator switch) are pytree *leaves* of ``PolicyParams``, so a
  stacked params grid vmaps without retracing;
* the policy itself becomes a traced lane index: the unified simulation
  body (``_simulate_multi_impl``) computes ONE shared estimator substrate
  per commit and evaluates every requested policy as a few-op epilogue over
  it, gathering the lane's row (O(N + P·N_cheap) — the historical
  per-lane full rank stacks were the §Perf "lockstep union penalty";
  DESIGN.md §10), with behavior flags (GreedyDual upkeep, AdaptSize
  admission, rank-compare eviction) selected from constant tables.  XLA
  sees one graph for the whole policy set — the per-policy compile that
  dominated benchmark wall-clock happens once.

Per-lane arithmetic is untouched: a swept point is bit-for-bit identical to
the corresponding :func:`repro.core.simulator.simulate` call (asserted by
tests/test_sweep.py).  ``lane_bucket`` pads the flattened grid to a bucket
multiple so differently-sized sweeps (an omega grid, then a window grid)
reuse one compiled graph.

The grid is flattened and vmapped once (trace broadcast, no per-lane trace
copies), nested in an outer vmap over stacked traces when several
identically-shaped traces are passed.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .hierarchy import (HierResult, HierTrace, _hier_impl_named,
                        _hier_multi_impl, check_shards)
from .ranking import POLICIES, PolicyParams
from .simulator import (_COMMIT_MODES, SimResult, _behavior_multi,
                        _behavior_static, _result_of_state, _run_chunk,
                        _simulate_impl, _simulate_multi_impl,
                        batched_commit_mode, batched_update_mode,
                        resolve_score_mode)
from .spans import span
from .state import init_state
from .trace import Trace

__all__ = ["SweepGrid", "sweep_grid", "HierSweepGrid", "sweep_hier_grid"]


class SweepGrid(NamedTuple):
    """A swept result with its axis metadata.

    ``result`` is a :class:`SimResult` whose fields are shaped
    ``[n_traces, n_policies, n_params, n_capacities, n_seeds]``; the
    remaining fields record the grid axes in order.
    """

    result: SimResult
    policies: Sequence[str]
    params: Sequence[PolicyParams]
    capacities: jax.Array
    seeds: Sequence[int]

    def point(self, ti: int, li: int, pi: int, ci: int, si: int) -> SimResult:
        """The SimResult of one grid point (host-side convenience)."""
        return SimResult(*(f[ti, li, pi, ci, si] for f in self.result))


def _stack(pytrees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *pytrees)


# The _impl bodies below are the unjitted composition points: the jitted
# aliases serve the single-device path, and the multi-device fabric
# (repro.launch.fabric, DESIGN.md §13) shard_maps the SAME bodies over a
# device mesh's lane shards — one body, two dispatch wrappers, so the
# sharded graph cannot drift from the single-device one.
def _sweep_single_impl(tstack, caps, keys, pstack, policy_name, estimate_z,
                       score_mode, update):
    def point(tr, c, k, pp):
        return _simulate_impl(tr, c, k, policy_name, pp, estimate_z,
                              score_mode, update)

    inner = jax.vmap(point, in_axes=(None, 0, 0, 0))
    return jax.vmap(lambda tr: inner(tr, caps, keys, pstack))(tstack)


_sweep_single = jax.jit(_sweep_single_impl,
                        static_argnames=("policy_name", "estimate_z",
                                         "score_mode", "update"))


def _group_lanes(lane_policy):
    """Static lane->policy grouping for the compact dispatch: returns
    ``[(policy_index, [lane positions...]), ...]`` sorted by policy index.
    ``lane_policy`` is the concrete (python) content of ``lflat`` — the
    grouping must be static so each group compiles its own specialized
    graph; lane-bucket / fabric pad lanes are lane-0 replicas and land in
    policy 0's group, exactly as they run under lockstep."""
    groups: dict[int, list[int]] = {}
    for pos, pi in enumerate(lane_policy):
        groups.setdefault(int(pi), []).append(pos)
    return sorted(groups.items())


def _ungroup_perm(groups):
    """Inverse permutation taking group-concatenated rows back to lane
    order (static numpy argsort — group layout is static)."""
    return jnp.asarray(
        np.argsort([pos for _, lanes in groups for pos in lanes]))


def _sweep_multi_impl(tstack, caps, keys, lidx, pstack, policy_names,
                      estimate_z, update="lane", commit_mode="lockstep",
                      lane_policy=None):
    if commit_mode == "compact":
        # Static policy-grouped dispatch (DESIGN.md §14): lanes sharing a
        # policy vmap together under a statically specialized behavior
        # (one epilogue in the graph, no cross-policy cond-union);
        # singleton groups run the *unbatched* per-point body, whose
        # lax.cond genuinely skips the scoring pass on fit-without-eviction
        # commits.  Per-lane arithmetic is exactly the per-point simulate
        # graph — the sweep engine's standing bitwise contract — and the
        # trace axis is a python loop (unrolled in jit; typically 1).
        groups = _group_lanes(lane_policy)
        inv = _ungroup_perm(groups)

        def one_trace(tr):
            outs = []
            for pi, lanes in groups:
                name = policy_names[pi]
                idx = jnp.asarray(lanes, jnp.int32)
                c, k = caps[idx], keys[idx]
                pp = jax.tree.map(lambda x: x[idx], pstack)
                if len(lanes) == 1:
                    r = _simulate_impl(tr, c[0], k[0], name,
                                       jax.tree.map(lambda x: x[0], pp),
                                       estimate_z, "rank", "scatter")
                    outs.append(jax.tree.map(lambda x: x[None], r))
                else:
                    outs.append(jax.vmap(
                        lambda c1, k1, p1, name=name: _simulate_impl(
                            tr, c1, k1, name, p1, estimate_z, "rank",
                            update))(c, k, pp))
            cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
            return jax.tree.map(lambda x: x[inv], cat)

        return _stack([one_trace(Trace(*(x[ti] for x in tstack)))
                       for ti in range(tstack.times.shape[0])])

    def point(tr, c, k, li, pp):
        return _simulate_multi_impl(tr, c, k, li, pp, policy_names,
                                    estimate_z, update=update)

    inner = jax.vmap(point, in_axes=(None, 0, 0, 0, 0))
    return jax.vmap(lambda tr: inner(tr, caps, keys, lidx, pstack))(tstack)


_sweep_multi = jax.jit(_sweep_multi_impl,
                       static_argnames=("policy_names", "estimate_z",
                                        "update", "commit_mode",
                                        "lane_policy"))


# ---------------------------------------------------------------------------
# Chunked grid dispatch (DESIGN.md §9): the stacked per-lane SimStates are
# the carry of a grid-axes x chunk loop — each chunk call advances EVERY
# lane by one fixed-size trace slice with the state buffers donated, so the
# request axis never has to be device-resident in one piece.  Per-lane
# arithmetic is _run_chunk's, i.e. bitwise identical to the unchunked grid
# (and hence to per-point simulate; tests/test_streaming.py).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("policy_name", "estimate_z",
                                             "score_mode", "update"),
                   donate_argnums=(0,))
def _sweep_single_chunk(states, times, objs, z_draw, valid, sizes, pstack,
                        policy_name, estimate_z, score_mode, update):
    def lane(st, pp, chunk, sz):
        b = _behavior_static(POLICIES[policy_name], pp, score_mode, update)
        return _run_chunk(b, pp, estimate_z, st, sz, chunk)

    inner = jax.vmap(lane, in_axes=(0, 0, None, None))

    def per_trace(st, t, o, z, sz):
        chunk = (t, o, z) if valid is None else (t, o, z, valid)
        return inner(st, pstack, chunk, sz)

    return jax.vmap(per_trace)(states, times, objs, z_draw, sizes)


@functools.partial(jax.jit, static_argnames=("policy_names", "estimate_z",
                                             "update", "commit_mode",
                                             "lane_policy"),
                   donate_argnums=(0,))
def _sweep_multi_chunk(states, times, objs, z_draw, valid, sizes, lidx,
                       pstack, policy_names, estimate_z, update="lane",
                       commit_mode="lockstep", lane_policy=None):
    if commit_mode == "compact":
        # static policy-grouped dispatch, as in _sweep_multi_impl: groups
        # gather their state rows, advance one chunk under a statically
        # specialized behavior, and the rows are permuted back to lane
        # order so the carried layout is identical to lockstep's
        groups = _group_lanes(lane_policy)
        inv = _ungroup_perm(groups)

        def one_trace(st_t, t_, o_, z_, sz):
            chunk = (t_, o_, z_) if valid is None else (t_, o_, z_, valid)
            outs = []
            for pi, lanes in groups:
                name = policy_names[pi]
                idx = jnp.asarray(lanes, jnp.int32)
                st_g = jax.tree.map(lambda x: x[idx], st_t)
                pp = jax.tree.map(lambda x: x[idx], pstack)
                if len(lanes) == 1:
                    p1 = jax.tree.map(lambda x: x[0], pp)
                    b = _behavior_static(POLICIES[name], p1, "rank",
                                         "scatter")
                    out = _run_chunk(b, p1, estimate_z,
                                     jax.tree.map(lambda x: x[0], st_g),
                                     sz, chunk)
                    outs.append(jax.tree.map(lambda x: x[None], out))
                else:
                    def lane_g(st1, p1, name=name):
                        b = _behavior_static(POLICIES[name], p1, "rank",
                                             update)
                        return _run_chunk(b, p1, estimate_z, st1, sz, chunk)
                    outs.append(jax.vmap(lane_g)(st_g, pp))
            cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
            return jax.tree.map(lambda x: x[inv], cat)

        return _stack([one_trace(jax.tree.map(lambda x: x[ti], states),
                                 times[ti], objs[ti], z_draw[ti], sizes[ti])
                       for ti in range(times.shape[0])])

    def lane(st, li, pp, chunk, sz):
        b = _behavior_multi(policy_names, li, pp, update=update)
        return _run_chunk(b, pp, estimate_z, st, sz, chunk)

    inner = jax.vmap(lane, in_axes=(0, 0, 0, None, None))

    def per_trace(st, t, o, z, sz):
        chunk = (t, o, z) if valid is None else (t, o, z, valid)
        return inner(st, lidx, pstack, chunk, sz)

    return jax.vmap(per_trace)(states, times, objs, z_draw, sizes)


def _run_sweep_chunked(tstack, cflat, kflat, lflat, pflat, single,
                       policy_names, estimate_z, score_mode, update,
                       chunk_size: int,
                       commit_mode: str = "lockstep",
                       lane_policy=None) -> SimResult:
    if chunk_size < 1:
        raise ValueError(f"chunk_size={chunk_size} must be >= 1")
    n_objects = tstack.sizes.shape[1]

    def one(zm, c, k):
        return init_state(n_objects, c, k, zm)

    states = jax.vmap(lambda zm: jax.vmap(one, in_axes=(None, 0, 0))(
        zm, cflat, kflat))(tstack.z_mean)
    # donation safety: the vmapped init may hand back aliased buffers for
    # identically-zero fields; force every leaf to own its storage.
    states = jax.tree.map(lambda x: x.copy(), states)

    times = np.asarray(tstack.times, np.float32)
    objs = np.asarray(tstack.objs, np.int32)
    z_draw = np.asarray(tstack.z_draw, np.float32)
    sizes = jnp.asarray(tstack.sizes)
    n = times.shape[1]
    for lo in range(0, max(n, 1), chunk_size):
        hi = min(lo + chunk_size, n)
        pad = chunk_size - (hi - lo)
        ext = lambda x, fill, dt: jnp.asarray(np.concatenate(
            [x[:, lo:hi],
             np.full((x.shape[0], pad), fill, dt)], axis=1))
        valid = None if pad == 0 else jnp.asarray(np.concatenate(
            [np.ones(hi - lo, bool), np.zeros(pad, bool)]))
        args = (states, ext(times, -np.inf, np.float32),
                ext(objs, 0, np.int32), ext(z_draw, 0.0, np.float32),
                valid, sizes)
        if single:
            states = _sweep_single_chunk(*args, pflat, policy_names[0],
                                         estimate_z, score_mode, update)
        else:
            states = _sweep_multi_chunk(*args, lflat, pflat, policy_names,
                                        estimate_z, update, commit_mode,
                                        lane_policy)
    return _result_of_state(states)


def _bucket(n: int, bucket) -> int:
    """Round ``n`` up to the next multiple of ``bucket`` (identity if unset)."""
    if not bucket:
        return n
    return -(-n // bucket) * bucket


def _check_axes(policies, params):
    """Shared axis validation: returns (single, policy_names, params_list)."""
    single = isinstance(policies, str)
    policy_names = (policies,) if single else tuple(policies)
    unknown = [n for n in policy_names if n not in POLICIES]
    if unknown:
        raise ValueError(f"unknown policies {unknown}; known: "
                         f"{sorted(POLICIES)}")
    params_list = ([params] if isinstance(params, PolicyParams)
                   else list(params))
    structs = {jax.tree.structure(p) for p in params_list}
    if len(structs) != 1:
        raise ValueError(
            "all PolicyParams in a sweep must share static structure "
            f"(distribution type); got {structs}")
    return single, policy_names, params_list


def _lane_index(dims, n_pad: int) -> np.ndarray:
    """The lane grid's index vectors on the host: row ``a`` of the
    ``[len(dims), n_pad]`` result is each lane's position on axis ``a``,
    lanes in row-major (meshgrid ``'ij'``) order, so lanes are
    policy-major.  Pad lanes index 0 on every axis, i.e. they replicate
    lane 0."""
    idx = np.indices(dims, np.int32).reshape(len(dims), -1)
    return np.pad(idx, ((0, 0), (0, n_pad - idx.shape[1])))


@functools.partial(jax.jit, static_argnames=("dims", "n_pad"))
def _lane_setup(traces, params, cap_arrays, seeds, dims, n_pad):
    """Every device input of a grid in one program: the stacked traces,
    then the lane-flattened policy index, params, capacity axes and PRNG
    keys.  Compiles once per grid shape (``dims``, ``n_pad``, the params'
    structure, the trace count and shape)."""
    idx = _lane_index(dims, n_pad)
    pflat = jax.tree.map(lambda x: x[idx[1]], _stack(params))
    capflats = [c[i] for c, i in zip(cap_arrays, idx[2:-1])]
    kflat = jax.vmap(jax.random.key)(seeds)[idx[-1]]
    return _stack(traces), jnp.asarray(idx[0]), pflat, capflats, kflat


def _flatten_lanes(trace_list, policy_names, params_list, cap_arrays, seeds,
                   lane_bucket, multiple: int = 1):
    """Stack the traces and flatten policies x params x capacity-axes x
    seeds into padded lanes.

    Returns ``(tstack, lflat, pflat, capflats, kflat, G, lanes)`` where
    the flats are bucket-padded (repeats of lane 0), ``G`` is the true
    lane count to slice back out and ``lanes`` is ``lflat`` on the host.
    Shared by the single-tier and hierarchy grids so the flatten/pad
    pipeline cannot drift between them.  ``multiple`` rounds the padded
    lane count up to a device-count multiple for the sweep fabric
    (DESIGN.md §13) — pad lanes are dead lanes either way: replicas of
    lane 0 whose results are sliced off, never interacting with real
    lanes, so padding is invisible in results (tests/test_fabric.py).
    """
    dims = (len(policy_names), len(params_list),
            *[c.shape[0] for c in cap_arrays], len(seeds))
    G = math.prod(dims)
    Gpad = _bucket(_bucket(G, lane_bucket), multiple)
    tstack, lflat, pflat, capflats, kflat = _lane_setup(
        tuple(trace_list), tuple(params_list), tuple(cap_arrays),
        np.asarray(seeds, np.int64), dims=dims, n_pad=Gpad)
    lanes = _lane_index(dims, Gpad)[0]
    return tstack, lflat, pflat, capflats, kflat, G, lanes


def sweep_grid(traces, capacities, policies,
               params=PolicyParams(), seeds=(0,),
               estimate_z: bool = False, use_kernel=False,
               lane_bucket: int | None = None,
               chunk_size: int | None = None,
               update: str | None = None,
               commit_mode: str | None = None,
               state_mode: str = "dense",
               devices: int | None = None, mesh=None) -> SweepGrid:
    """Run the full scenario grid in one compiled call.

    traces      — one :class:`Trace` or a sequence of identically-shaped
                  traces (e.g. the same spec under different seeds).
    capacities  — scalar or sequence of cache sizes.
    policies    — one policy name (static specialization — supports
                  ``use_kernel``) or a sequence of names (unified
                  multi-policy graph; one compile for the whole set).
    params      — one :class:`PolicyParams` or a sequence; all entries must
                  share their static structure (distribution type).
    seeds       — simulation PRNG seeds (admission coins etc.).
    lane_bucket — pad the flattened grid up to this many lanes (repeats of
                  lane 0, sliced off afterwards) so sweeps of different
                  sizes share one compiled graph.
    chunk_size  — when set, run the grid as a grid-axes x chunk loop: each
                  compiled dispatch advances every lane by one fixed-size
                  trace chunk with the stacked per-lane states donated, so
                  the request axis is device-resident one chunk at a time
                  (DESIGN.md §9).  Results are bitwise identical to the
                  unchunked grid.
    update      — state-update lowering override (DESIGN.md §11).  Default
                  ``None`` auto-selects: 'scatter' for an unbatched
                  single-lane grid; for batched lanes, 'lane' (the
                  diagonal-scatter seam) at large universes and 'onehot'
                  below the measured crossover
                  (:data:`repro.core.simulator.LANE_UPDATE_MIN_OBJECTS`).
                  Every mode is bitwise identical in results
                  (tests/test_hotpath.py).
    commit_mode — multi-policy dispatch shape (DESIGN.md §14): 'lockstep'
                  (one vmapped graph over the whole lane axis — every lane
                  pays the commit substrate whenever any lane commits) or
                  'compact' (static policy-grouped dispatch — same-policy
                  lanes vmap under a statically specialized behavior,
                  singleton groups run the unbatched per-point body with
                  real cond scoring skips).  Default ``None`` auto-selects
                  'compact' at universes >=
                  :data:`repro.core.simulator.COMPACT_COMMIT_MIN_OBJECTS`
                  (single-policy and fabric grids stay lockstep).  Bitwise
                  identical either way (tests/test_hotpath.py).
    state_mode  — must be 'dense': the sweep engine's lane machinery (and
                  the fabric) batch dense [N]-state axes only.  Slot-table
                  replays (``state_mode='slots'``) run through
                  :func:`repro.core.simulator.simulate_stream`.
    devices     — shard the flattened lane axis over this many devices via
                  the sweep fabric (DESIGN.md §13).  ``None``/1 keeps
                  exactly today's single-device graph; ``d > 1`` pads the
                  lanes to a multiple of ``d`` (dead lanes, sliced off) and
                  runs each device's shard under ``shard_map`` — results
                  are bitwise identical for every device count and
                  lane->device assignment (tests/test_fabric.py).
    mesh        — an explicit 1-D ``data`` mesh instead of ``devices``
                  (e.g. :func:`repro.launch.mesh.make_data_mesh` over a
                  custom device order); always routes through the fabric,
                  even with one device.

    Returns a :class:`SweepGrid`; ``result`` fields are
    ``[T, L, P, C, S]``-shaped.  Each point is bitwise identical to the
    corresponding per-point :func:`simulate` call.
    """
    with span("sweep.prologue"):
        trace_list = [traces] if isinstance(traces, Trace) else list(traces)
        single, policy_names, params_list = _check_axes(policies, params)
        caps = jnp.atleast_1d(jnp.asarray(capacities, jnp.float32))
        seeds = np.atleast_1d(np.asarray(seeds, np.int64)).tolist()
        if state_mode != "dense":
            if state_mode == "slots":
                raise ValueError(
                    "state_mode='slots' is not supported by sweep_grid — "
                    "the sweep engine's lane machinery (and the device "
                    "fabric) batch dense [N]-state lane axes only; run "
                    "slot-table replays through simulate / simulate_stream "
                    "/ simulate_chunked")
            raise ValueError(
                f"state_mode={state_mode!r}; expected 'dense'")

        fabric_mesh = None
        if devices is not None or mesh is not None:
            from repro.launch.fabric import (fabric_lane_multiple,
                                             resolve_fabric)
            fabric_mesh = resolve_fabric(devices, mesh)

        if commit_mode is not None and commit_mode not in _COMMIT_MODES:
            raise ValueError(f"commit_mode={commit_mode!r}; expected None "
                             f"or one of {_COMMIT_MODES}")
        if commit_mode == "compact":
            if single:
                raise ValueError(
                    "commit_mode='compact' applies to multi-policy grids "
                    "(it groups lanes by policy under statically "
                    "specialized graphs); a single-policy grid is already "
                    "statically specialized")
            if fabric_mesh is not None:
                raise ValueError(
                    "commit_mode='compact' is not supported with "
                    "devices/mesh — the fabric shard_maps one lockstep lane "
                    "body over device shards (the grouped dispatch splits "
                    "the very lane axis the fabric shards); drop "
                    "devices=/mesh= or pass commit_mode='lockstep'")
        if commit_mode is None:
            # compact pays at large universes where the per-commit
            # substrate dominates; single-policy bodies and fabric shards
            # stay lockstep
            commit_mode = ("lockstep" if single or fabric_mesh is not None
                           else batched_commit_mode(trace_list[0].n_objects))

        L, P, C, S = (len(policy_names), len(params_list), caps.shape[0],
                      len(seeds))
        tstack, lflat, pflat, (cflat,), kflat, G, lanes = _flatten_lanes(
            trace_list, policy_names, params_list, [caps], seeds,
            lane_bucket, multiple=(fabric_lane_multiple(fabric_mesh)
                                   if fabric_mesh is not None else 1))

        if not single and resolve_score_mode(use_kernel) != "rank":
            raise ValueError("use_kernel is only supported for "
                             "single-policy sweeps (the kernel specializes "
                             "eq. 16)")
        # the concrete lane->policy map, passed statically so the compact
        # dispatch can group lanes at trace time (None under lockstep so
        # the jit cache key does not fragment on it)
        lane_policy = (tuple(lanes.tolist()) if commit_mode == "compact"
                       else None)
        if update is None:
            # point scatters for an unbatched single lane; once lanes
            # batch, the N-dependent batched default (DESIGN.md §11)
            update = batched_update_mode(trace_list[0].n_objects) \
                if (not single or cflat.shape[0] > 1) else "scatter"
    with span("sweep.dispatch"):
        if chunk_size is not None:
            if fabric_mesh is not None:
                raise ValueError(
                    "chunk_size is not supported with devices/mesh yet — "
                    "the chunked grid carries donated per-lane states "
                    "across a host-side loop, which the fabric does not "
                    "shard")
            res = _run_sweep_chunked(tstack, cflat, kflat, lflat, pflat,
                                     single, policy_names, estimate_z,
                                     resolve_score_mode(use_kernel),
                                     update, chunk_size, commit_mode,
                                     lane_policy)
        elif fabric_mesh is not None:
            from repro.launch.fabric import (fabric_sweep_multi,
                                             fabric_sweep_single)
            if single:
                res = fabric_sweep_single(fabric_mesh, tstack, cflat, kflat,
                                          pflat, policy_names[0],
                                          estimate_z,
                                          resolve_score_mode(use_kernel),
                                          update)
            else:
                res = fabric_sweep_multi(fabric_mesh, tstack, cflat, kflat,
                                         lflat, pflat, policy_names,
                                         estimate_z, update)
        elif single:
            res = _sweep_single(tstack, cflat, kflat, pflat, policy_names[0],
                                estimate_z, resolve_score_mode(use_kernel),
                                update)
        else:
            res = _sweep_multi(tstack, cflat, kflat, lflat, pflat,
                               policy_names, estimate_z, update, commit_mode,
                               lane_policy)
    res = SimResult(*(x[:, :G].reshape((len(trace_list), L, P, C, S))
                      for x in res))
    return SweepGrid(res, policy_names, tuple(params_list), caps,
                     tuple(seeds))


# ---------------------------------------------------------------------------
# Hierarchy sweeps: n_shards x l2_capacity x hop_dist x policy grids.
# The hop-distribution axis IS the trace axis (hop draws are pre-drawn into
# each HierTrace); n_shards is shape-changing, so it stays a caller-side
# loop (one compiled graph per shard count); everything else — the L1
# policy lane, PolicyParams, both capacity axes, and seeds — batches into
# one compiled dispatch exactly like ``sweep_grid`` (DESIGN.md §7/§8).
# ---------------------------------------------------------------------------
class HierSweepGrid(NamedTuple):
    """A swept hierarchy result with its axis metadata.

    ``result`` fields are shaped ``[n_traces, n_policies, n_params,
    n_l1_capacities, n_l2_capacities, n_seeds]`` (the ``per_shard``
    SimResult carries a trailing ``[n_shards]`` axis).
    """

    result: HierResult
    policies: Sequence[str]
    params: Sequence[PolicyParams]
    l1_capacities: jax.Array
    l2_capacities: jax.Array
    seeds: Sequence[int]
    n_shards: int

    def point(self, ti: int, li: int, pi: int, c1: int, c2: int,
              si: int) -> HierResult:
        """The HierResult of one grid point (host-side convenience)."""
        ix = (ti, li, pi, c1, c2, si)
        return HierResult(
            per_shard=SimResult(*(f[ix] for f in self.result.per_shard)),
            l2=SimResult(*(f[ix] for f in self.result.l2)))


def _sweep_hier_single_impl(tstack, c1s, c2s, keys, pstack, p2, policy_name,
                            l2_policy, estimate_z, n_shards):
    def point(tr, c1, c2, k, pp):
        return _hier_impl_named(tr, c1, c2, k, policy_name, l2_policy, pp,
                                p2, estimate_z, n_shards)

    inner = jax.vmap(point, in_axes=(None, 0, 0, 0, 0))
    return jax.vmap(lambda tr: inner(tr, c1s, c2s, keys, pstack))(tstack)


_sweep_hier_single = jax.jit(_sweep_hier_single_impl,
                             static_argnames=("policy_name", "l2_policy",
                                              "estimate_z", "n_shards"))


def _sweep_hier_multi_impl(tstack, c1s, c2s, keys, lidx, pstack, p2,
                           policy_names, l2_policy, estimate_z, n_shards):
    def point(tr, c1, c2, k, li, pp):
        return _hier_multi_impl(tr, c1, c2, k, li, policy_names, l2_policy,
                                pp, p2, estimate_z, n_shards)

    inner = jax.vmap(point, in_axes=(None, 0, 0, 0, 0, 0))
    return jax.vmap(lambda tr: inner(tr, c1s, c2s, keys, lidx, pstack))(tstack)


_sweep_hier_multi = jax.jit(_sweep_hier_multi_impl,
                            static_argnames=("policy_names", "l2_policy",
                                             "estimate_z", "n_shards"))


def sweep_hier_grid(traces, n_shards: int, l1_capacities, l2_capacities,
                    policies, params=PolicyParams(), seeds=(0,),
                    l2_policy: str = "lru",
                    l2_params: PolicyParams | None = None,
                    estimate_z: bool = True,
                    lane_bucket: int | None = None,
                    devices: int | None = None, mesh=None) -> HierSweepGrid:
    """Run a hierarchy scenario grid in one compiled call per shard count.

    traces         — one :class:`HierTrace` or identically-shaped sequence
                     (e.g. the same base trace under different hop
                     distributions — the hop axis of a fig6 grid).
    n_shards       — static L1 shard count (must match the traces' routing).
    l1_capacities  — per-shard L1 capacities (scalar or sequence).
    l2_capacities  — shared-L2 capacities (scalar or sequence).
    policies       — L1 policy name or sequence of names (unified
                     multi-policy lane graph, as in :func:`sweep_grid`).
    l2_policy      — static L2 policy: the L2 is environment, not a swept
                     axis (loop at the call site to compare L2 policies).
    l2_params      — L2 hyperparameters; defaults to stock
                     :class:`PolicyParams` (same decoupled default as
                     ``simulate_hier`` — the swept L1-params axis never
                     re-parameterizes the shared L2).
    devices / mesh — shard the flattened lane axis over a device mesh via
                     the sweep fabric, exactly as in :func:`sweep_grid`
                     (DESIGN.md §13; bitwise device-count invisibility
                     pinned by tests/test_fabric.py).

    Returns a :class:`HierSweepGrid`; each point is bitwise identical to
    the corresponding :func:`repro.core.hierarchy.simulate_hier` call
    (tests/test_sweep.py) — the hierarchy body always uses a batched
    update lowering (DESIGN.md §11), so batching never changes per-lane
    arithmetic.
    """
    trace_list = [traces] if isinstance(traces, HierTrace) else list(traces)
    single, policy_names, params_list = _check_axes(policies, params)
    if l2_policy not in POLICIES:
        raise ValueError(f"unknown policies [{l2_policy!r}]; known: "
                         f"{sorted(POLICIES)}")
    for tr in trace_list:
        check_shards(tr, n_shards)
    if l2_params is None:
        # decoupled default (stock params), matching simulate_hier — the
        # swept L1-params axis must never re-parameterize the shared L2
        l2_params = PolicyParams()
    c1 = jnp.atleast_1d(jnp.asarray(l1_capacities, jnp.float32))
    c2 = jnp.atleast_1d(jnp.asarray(l2_capacities, jnp.float32))
    seeds = np.atleast_1d(np.asarray(seeds, np.int64)).tolist()

    fabric_mesh = None
    if devices is not None or mesh is not None:
        from repro.launch.fabric import fabric_lane_multiple, resolve_fabric
        fabric_mesh = resolve_fabric(devices, mesh)

    L, P, C1, C2, S = (len(policy_names), len(params_list), c1.shape[0],
                       c2.shape[0], len(seeds))
    tstack, lflat, pflat, (c1flat, c2flat), kflat, G, _ = _flatten_lanes(
        trace_list, policy_names, params_list, [c1, c2], seeds, lane_bucket,
        multiple=(fabric_lane_multiple(fabric_mesh) if fabric_mesh is not None
                  else 1))

    if fabric_mesh is not None:
        from repro.launch.fabric import fabric_hier_multi, fabric_hier_single
        if single:
            res = fabric_hier_single(fabric_mesh, tstack, c1flat, c2flat,
                                     kflat, pflat, l2_params,
                                     policy_names[0], l2_policy, estimate_z,
                                     int(n_shards))
        else:
            res = fabric_hier_multi(fabric_mesh, tstack, c1flat, c2flat,
                                    kflat, lflat, pflat, l2_params,
                                    policy_names, l2_policy, estimate_z,
                                    int(n_shards))
    elif single:
        res = _sweep_hier_single(tstack, c1flat, c2flat, kflat, pflat,
                                 l2_params, policy_names[0], l2_policy,
                                 estimate_z, int(n_shards))
    else:
        res = _sweep_hier_multi(tstack, c1flat, c2flat, kflat, lflat, pflat,
                                l2_params, policy_names, l2_policy,
                                estimate_z, int(n_shards))
    shape = (len(trace_list), L, P, C1, C2, S)
    reshape = lambda x: x[:, :G].reshape(shape + x.shape[2:])
    res = HierResult(
        per_shard=SimResult(*(reshape(x) for x in res.per_shard)),
        l2=SimResult(*(reshape(x) for x in res.l2)))
    return HierSweepGrid(res, policy_names, tuple(params_list), c1, c2,
                         tuple(seeds), int(n_shards))

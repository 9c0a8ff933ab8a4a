"""Simulator state pytrees for the delayed-hit cache (DESIGN.md §2).

Everything is a struct-of-arrays over the object universe (size N) so the
whole simulation runs as a single ``lax.scan`` over the request trace with
``lax.while_loop`` for the (rare) fetch-commit / eviction events.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

INF = jnp.inf


class ObjStats(NamedTuple):
    """Per-object online statistics (all shape [N])."""

    cached: jax.Array        # bool — resident in cache
    in_flight: jax.Array     # bool — fetch outstanding
    complete_t: jax.Array    # f32 — absolute completion time of outstanding fetch (inf if none)
    issue_t: jax.Array       # f32 — time the outstanding fetch was issued
    last_access: jax.Array   # f32 — time of most recent request (-inf if never)
    first_access: jax.Array  # f32
    gap_mean: jax.Array      # f32 — (windowed) mean inter-arrival time
    count: jax.Array         # f32 — number of requests seen
    z_est: jax.Array         # f32 — online estimate of mean fetch latency
    agg_sum: jax.Array       # f32 — sum of per-episode aggregate delays
    agg_sq_sum: jax.Array    # f32 — sum of squared per-episode aggregate delays
    agg_cnt: jax.Array       # f32 — number of completed miss episodes
    episode_delay: jax.Array  # f32 — aggregate delay accumulated by the episode in flight
    gd_h: jax.Array          # f32 — GreedyDual H value (MAD-style policies)


class SimState(NamedTuple):
    obj: ObjStats
    free: jax.Array          # f32 scalar — free cache capacity
    gd_clock: jax.Array      # f32 scalar — GreedyDual inflation clock
    min_complete: jax.Array  # f32 scalar — min complete_t over in-flight objects
    key: jax.Array           # PRNG key (stochastic fetch draws, admission coins)
    lat_sum: jax.Array       # f32 — Kahan-compensated total latency (sum)
    lat_comp: jax.Array      # f32 — Kahan compensation term
    n_hits: jax.Array        # f32 scalars — outcome counters
    n_delayed: jax.Array
    n_misses: jax.Array
    n_evictions: jax.Array


def init_state(n_objects: int, capacity: float, key: jax.Array,
               z_prior: jax.Array) -> SimState:
    """Fresh state for a universe of ``n_objects`` and cache ``capacity``.

    ``z_prior`` [N] seeds the per-object latency estimate (the known mean of
    the fetch-latency model, as in the paper's setup)."""
    f = lambda v: jnp.full((n_objects,), v, jnp.float32)
    b = lambda: jnp.zeros((n_objects,), bool)
    obj = ObjStats(
        cached=b(), in_flight=b(),
        complete_t=f(INF), issue_t=f(0.0),
        last_access=f(-INF), first_access=f(-INF),
        gap_mean=f(0.0), count=f(0.0),
        # jnp.array (copy semantics), NOT asarray: z_est must own its buffer
        # — the streaming engine donates the state, and an aliased caller
        # array (e.g. trace.z_mean) would be invalidated with it.
        z_est=jnp.array(z_prior, jnp.float32),
        agg_sum=f(0.0), agg_sq_sum=f(0.0), agg_cnt=f(0.0),
        episode_delay=f(0.0), gd_h=f(0.0),
    )
    # Distinct zero arrays per field: the streaming engine donates the whole
    # state pytree, and XLA rejects donating one buffer behind two leaves.
    zero = lambda: jnp.float32(0.0)
    return SimState(
        obj=obj,
        free=jnp.float32(capacity),
        gd_clock=zero(),
        min_complete=jnp.float32(INF),
        key=key,
        lat_sum=zero(), lat_comp=zero(),
        n_hits=zero(), n_delayed=zero(), n_misses=zero(),
        n_evictions=zero(),
    )


def shift_times(state: SimState, delta) -> SimState:
    """Rebase every absolute-time field of the state by ``-delta``.

    The streaming engine (DESIGN.md §9) carries absolute time as an f64
    host-side chunk base plus f32 chunk-local offsets; at a chunk boundary
    the carried state's time fields move to the new base.  Only *time
    points* shift — durations (``gap_mean``, ``episode_delay``, latency
    sums) and the GreedyDual clock are shift-invariant and stay put.  With
    ``delta == 0.0`` this is a bitwise no-op (``x - 0.0 == x`` for every
    float, including the ±inf sentinels), which is what keeps the unrebased
    chunked path bit-identical to the single-scan path.
    """
    o = state.obj
    o = o._replace(
        complete_t=o.complete_t - delta,
        issue_t=o.issue_t - delta,
        last_access=o.last_access - delta,
        first_access=o.first_access - delta,
    )
    return state._replace(obj=o, min_complete=state.min_complete - delta)


# ---------------------------------------------------------------------------
# Sparse slot-table state (DESIGN.md §14).  A fixed open-addressing table
# maps raw object ids onto S slots; the dense SimState machinery then runs
# unchanged over the [S]-shaped slot axis.  Objects insert on first touch
# and *retain* their slot afterwards (retaining evicted objects' statistics
# is exactly what dense mode does — eager freeing would diverge bitwise);
# slots are reclaimed only under table-full pressure, which never fires when
# S is at least the number of distinct keys touched.
# ---------------------------------------------------------------------------
SLOT_EMPTY = -1          # key_tab sentinel: no object resides in this slot


def _hash_u32(x, seed) -> jax.Array:
    """32-bit avalanche finalizer (the lowbias32 member of the splitmix64
    finalizer family — the device is 32-bit here; the host-side trace
    compactor uses the 64-bit sibling).  Uniformly scrambles object ids so
    linear-probe runs stay short at bounded load factors."""
    x = jnp.asarray(x).astype(jnp.uint32) ^ jnp.uint32(seed)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


class SlotView(NamedTuple):
    """The id->slot mapping riding next to an [S]-shaped :class:`SimState`.

    key_tab  i32[S] — raw object id resident in each slot (SLOT_EMPTY = none)
    sizes    f32[S] — resident object's size (0 while empty)
    seed     u32    — hash seed (results are bitwise seed-invariant: every
                      reduction the simulator runs over the slot axis is
                      either order-independent or id-tiebroken —
                      :func:`repro.kernels.ref.tiebreak_argmin_ref`)
    n_inserts  i32  — keys given a slot on first touch (reclaims included)
    n_reclaims i32  — inserts that found the table full and took an
                      occupied slot (0 whenever the table holds every key)
    """

    key_tab: jax.Array
    sizes: jax.Array
    seed: jax.Array
    n_inserts: jax.Array
    n_reclaims: jax.Array


class SlotState(NamedTuple):
    """Sparse simulator state: a dense [S] :class:`SimState` over slots plus
    the :class:`SlotView` table that maps raw object ids onto them."""

    sim: SimState
    tab: SlotView


def slot_home(obj, seed, n_slots: int) -> jax.Array:
    """The probe start slot for ``obj``."""
    return (_hash_u32(obj, seed) % jnp.uint32(n_slots)).astype(jnp.int32)


def slot_probe(key_tab: jax.Array, obj, seed):
    """Linear-probe lookup: returns ``(slot, found, empty)``.

    Walks from the home slot until it hits ``obj`` (``found``) or the first
    empty slot (``empty`` — the insertion point; the classic linear-probing
    invariant holds because slots are never vacated, only replaced in
    place).  A full wrap with neither means the table is full: both flags
    False.  Expected O(1) probes at bounded load factor; worst case S.
    """
    n = key_tab.shape[0]
    h = slot_home(obj, seed, n)

    def cond(c):
        s, steps = c
        k = key_tab[s]
        return (k != obj) & (k != SLOT_EMPTY) & (steps < n)

    def body(c):
        s, steps = c
        return (s + 1) % n, steps + 1

    s, _ = jax.lax.while_loop(cond, body, (h, jnp.int32(0)))
    k = key_tab[s]
    return s, k == obj, k == SLOT_EMPTY


def slot_table_size(n_distinct: int, load: float = 0.5) -> int:
    """Default slot-table size: the next power of two holding ``n_distinct``
    keys at most at ``load`` occupancy (floor 64).  At the default 0.5 the
    table always has headroom, so reclaim never fires and slot-mode results
    stay bitwise identical to dense mode."""
    if n_distinct < 0:
        raise ValueError(f"n_distinct={n_distinct} must be >= 0")
    if not 0.0 < load <= 1.0:
        raise ValueError(f"load={load} must be in (0, 1]")
    need = max(-(-n_distinct // load) if n_distinct else 1, 1)
    return 1 << max(6, (int(need) - 1).bit_length())


def init_slot_state(n_slots: int, capacity, key: jax.Array,
                    seed: int = 0) -> SlotState:
    """Fresh sparse state with an all-empty table.  Per-slot ``z_est`` is
    seeded at insertion time (the inserting engine writes the object's
    ``z_prior`` into its slot — the same first-touch value dense mode starts
    from)."""
    if n_slots < 1:
        raise ValueError(f"n_slots={n_slots} must be >= 1")
    sim = init_state(n_slots, capacity, key,
                     jnp.zeros((n_slots,), jnp.float32))
    tab = SlotView(
        key_tab=jnp.full((n_slots,), SLOT_EMPTY, jnp.int32),
        sizes=jnp.zeros((n_slots,), jnp.float32),
        seed=jnp.uint32(seed),
        n_inserts=jnp.int32(0), n_reclaims=jnp.int32(0))
    return SlotState(sim=sim, tab=tab)


def kahan_add(total: jax.Array, comp: jax.Array, x: jax.Array):
    """Compensated accumulation — keeps 1e6-term f32 sums exact to ~1 ulp."""
    y = x - comp
    t = total + y
    comp = (t - total) - y
    return t, comp


# ---------------------------------------------------------------------------
# Point-update lowerings (DESIGN.md §11).  Three ways to write "x[j] = v"
# into per-object state, all bit-identical in results:
#
#   scatter  — ``x.at[j].set(v)``: O(1), the unbatched fast path.
#   one-hot  — masked select over the N-vector: O(N) elementwise, the
#              historical batched lowering, kept in-tree as the parity
#              oracle (a batched select leaves untouched positions
#              bit-identical by construction).
#   lane     — ``lane_set``/``lane_add`` below: a ``custom_vmap`` seam
#              whose unbatched form IS the scatter and whose batched form
#              is ONE scatter over the lane diagonal of the stacked
#              ``[L, N]`` state (or the Pallas kernel,
#              :mod:`repro.kernels.lane_scatter`) — O(1) per lane instead
#              of the one-hot's O(N) per lane.
#
# The one-hot note that used to live here ("batched scatters loop on
# XLA:CPU") conflated the loop's O(L) trip count with the select's O(L*N)
# element work; measured at N=3000 the diagonal scatter wins ~3.5x
# (EXPERIMENTS.md §Perf iteration 6), which is why `lane` is now the
# default batched lowering and one-hot is the oracle.
# ---------------------------------------------------------------------------
def onehot_set(x: jax.Array, hot: jax.Array, val) -> jax.Array:
    """x with position(s) where ``hot`` is True replaced by ``val``."""
    return jnp.where(hot, val, x)


def onehot_add(x: jax.Array, hot: jax.Array, val) -> jax.Array:
    """x with ``val`` added at position(s) where ``hot`` is True."""
    return jnp.where(hot, x + val, x)


# Lane-path backend: 'scatter' = the jnp diagonal scatter (CPU fast path
# and ground truth), 'kernel' = compiled Pallas (TPU), 'kernel_interpret' =
# the kernel under the Pallas interpreter (any backend; tests).  Read at
# TRACE time — flipping it does not invalidate already-compiled graphs
# (call ``jax.clear_caches()`` in tests).
LANE_BACKENDS = ("scatter", "kernel", "kernel_interpret")
_lane_backend = "scatter"


def set_lane_backend(mode: str) -> None:
    """Select how the batched lane path lowers (see :data:`LANE_BACKENDS`)."""
    global _lane_backend
    if mode not in LANE_BACKENDS:
        raise ValueError(f"lane backend {mode!r}; expected one of "
                         f"{LANE_BACKENDS}")
    _lane_backend = mode


def _lane_dispatch(x, j, v, add: bool):
    if _lane_backend == "scatter":
        from repro.kernels.ref import (lane_scatter_add_ref,
                                       lane_scatter_set_ref)
        fn = lane_scatter_add_ref if add else lane_scatter_set_ref
        return fn(x, j, v)
    from repro.kernels.lane_scatter import lane_scatter_add, lane_scatter_set
    fn = lane_scatter_add if add else lane_scatter_set
    return fn(x, j, v, interpret=(_lane_backend == "kernel_interpret"))


def _lane_rule(axis_size, in_batched, x, j, val, *, add: bool):
    """The batched lowering: one diagonal scatter over the ``[L, N]`` stack.

    Handles every batching combination the simulator produces: ``x`` is
    (virtually) always batched; ``j`` is batched under lane vmaps whose
    index is lane-dependent (the sweep engine's commit argmin) and
    unbatched when every lane writes the same column (the hierarchy's
    broadcast request id — lowered as a column update, no index vector at
    all); ``val`` follows the data.  Nested vmaps (traces over lanes,
    grids over shards) batch the emitted scatter with XLA's stock rules —
    still one scatter op, never a select tree.
    """
    xb, jb, vb = in_batched
    if not xb:
        x = jnp.broadcast_to(x, (axis_size,) + jnp.shape(x))
    val = jnp.asarray(val, x.dtype)
    if not vb:
        val = jnp.broadcast_to(val, (axis_size,))
    if jb:
        out = _lane_dispatch(x, j, val, add)
    elif add:
        col = x[:, j]
        new = (col | val) if x.dtype == jnp.bool_ else col + val
        out = x.at[:, j].set(new)
    else:
        out = x.at[:, j].set(val)
    return out, True


@jax.custom_batching.custom_vmap
def lane_set(x: jax.Array, j, val) -> jax.Array:
    """``x.at[j].set(val)`` whose vmapped form is a lane scatter."""
    return x.at[j].set(jnp.asarray(val, x.dtype))


@jax.custom_batching.custom_vmap
def lane_add(x: jax.Array, j, val) -> jax.Array:
    """``x[j] += val`` whose vmapped form is a lane scatter-add (the sum is
    formed on the gathered element — identical arithmetic to the one-hot
    lowering's ``where(hot, x + val, x)`` at the addressed position)."""
    if x.dtype == jnp.bool_:
        return x.at[j].set(x[j] | jnp.asarray(val, bool))
    return x.at[j].set(x[j] + jnp.asarray(val, x.dtype))


lane_set.def_vmap(functools.partial(_lane_rule, add=False))
lane_add.def_vmap(functools.partial(_lane_rule, add=True))

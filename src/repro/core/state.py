"""Simulator state pytrees for the delayed-hit cache (DESIGN.md §2).

The compiled engines carry the per-object state as one float32 block
``[F, N]`` over the object universe (size N; stored ``[F, N/128, 128]``
when N fills whole 8x128 tiles, see :func:`row_shape`): one row per
:class:`ObjStats` field, with the two flags stored as 0.0/1.0.  A request
reads its object as one column (one dynamic slice) and writes it back as
one column (one dynamic-update-slice), instead of one gather and one
scatter per field.  :class:`ObjStats` is the block's *row view* — what the
scoring code reads — and also the plain per-field container that the
eager oracle (:mod:`repro.core.refsim`) and the serving engine keep.  The
whole simulation runs as a single ``lax.scan`` over the request trace with
``lax.while_loop`` for the (rare) fetch-commit / eviction events.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.inf


class ObjStats(NamedTuple):
    """Per-object online statistics, one value per object.

    As a row view of the compiled engines' ``[F, N]`` block
    (:func:`obj_rows`) every field is an ``[N]`` row; as a record
    (:func:`obj_record`) every field is one object's scalar; the eager
    oracle and the serving engine keep it as ``[N]`` numpy arrays."""

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


# ---------------------------------------------------------------------------
# The block: row k holds ObjStats field k, stored in one of two shapes that
# the block's rank tells apart.  A table of whole 8x128 tiles (N a multiple
# of 1024) stores each row as an [N/128, 128] slab, object j at
# (j // 128, j % 128): on the TPU's tiles each field then owns whole tiles,
# so a scan of one row over a large table reads only that row's bytes,
# where rows of a flat [F, N] array share their tiles eight to one.  Any
# other N keeps the flat [F, N] block, whose column sits in two tiles.
# Packing and unpacking are exact — floats are stored as they are, flags
# as 0.0/1.0 — so every float32 operation of the engines is the one a
# per-field layout runs.  The helpers below take one state's block; under
# ``vmap`` that is each lane's.
# ---------------------------------------------------------------------------
N_FIELDS = len(ObjStats._fields)
ROW = ObjStats(*range(N_FIELDS))         # row of each field: ROW.z_est == 8
_LANES = 128
_FLAGS = (ROW.cached, ROW.in_flight)
_IS_TIME = np.isin(np.arange(N_FIELDS),           # the time-point rows
                   (ROW.complete_t, ROW.issue_t, ROW.last_access,
                    ROW.first_access))


def row_shape(n: int) -> tuple[int, ...]:
    """How one row of ``n`` objects is stored: an ``[n/128, 128]`` slab
    when ``n`` fills whole 8x128 tiles, else flat ``[n]``."""
    return (n // _LANES, _LANES) if n % (8 * _LANES) == 0 else (n,)


def _unpack(field) -> ObjStats:
    return ObjStats(*(field(k) > 0.0 if k in _FLAGS else field(k)
                      for k in range(N_FIELDS)))


def obj_rows(block: jax.Array) -> ObjStats:
    """The block as an :class:`ObjStats` of ``[N]`` rows (static slices;
    the scoring code's view)."""
    return _unpack(lambda k: block[k].reshape(-1))


def pack_rows(o: ObjStats) -> jax.Array:
    """An :class:`ObjStats` of ``[N]`` rows (scalars broadcast) as the
    block."""
    rows = jnp.broadcast_arrays(*(jnp.asarray(v, jnp.float32) for v in o))
    return jnp.stack(rows).reshape((N_FIELDS,) + row_shape(rows[0].size))


def obj_record(col: jax.Array) -> ObjStats:
    """One object's ``[F]`` column as an :class:`ObjStats` of scalars."""
    return _unpack(lambda k: col[k])


def pack_record(r: ObjStats, unbatched: bool = False) -> jax.Array:
    """A record of scalars as the ``[F]`` column :func:`set_col` writes.

    One select per field (XLA:CPU fails to compile a stacked column inside
    the hierarchy's vmapped commit).  In an unbatched graph each field's
    mask is an iota compare, which the TPU compiler fuses into the
    selects; constant masks there are hoisted out of the scan as buffers
    of their own and read from memory at every pack (about 2 µs a request
    on a v5e).  Batched graphs take constant masks: there the compares
    stay unfused, a compare op per field on the TPU."""
    col = jnp.zeros((N_FIELDS,), jnp.float32)
    masks = (jnp.arange(N_FIELDS) == k for k in range(N_FIELDS)) \
        if unbatched else np.eye(N_FIELDS, dtype=bool)
    for at, v in zip(masks, r):
        col = jnp.where(at, jnp.asarray(v, jnp.float32), col)
    return col


def _col_at(block: jax.Array, j):
    """Start index and window of object ``j``'s column in the block."""
    at = (j,) if block.ndim == 2 else (j // _LANES, j % _LANES)
    return (0,) + at, (N_FIELDS,) + (1,) * len(at)


def get_col(block: jax.Array, j) -> jax.Array:
    """Object ``j``'s column of the block: one dynamic slice."""
    start, size = _col_at(block, j)
    return jax.lax.dynamic_slice(block, start, size).reshape(N_FIELDS)


def set_col(block: jax.Array, j, col: jax.Array) -> jax.Array:
    """The block with object ``j``'s column replaced: one
    dynamic-update-slice."""
    start, size = _col_at(block, j)
    return jax.lax.dynamic_update_slice(block, col.reshape(size), start)


def onehot_col(block: jax.Array, j) -> jax.Array:
    """Mask of object ``j``'s position in every row, shaped to broadcast
    over the block."""
    row = block.shape[1:]
    return (jnp.arange(np.prod(row)) == j).reshape((1,) + row)


def fresh_record(z_prior) -> ObjStats:
    """An object's first-touch record: not cached, nothing in flight, no
    access seen, latency estimate ``z_prior``."""
    return ObjStats(
        cached=False, in_flight=False, complete_t=INF, issue_t=0.0,
        last_access=-INF, first_access=-INF, gap_mean=0.0, count=0.0,
        z_est=z_prior, agg_sum=0.0, agg_sq_sum=0.0, agg_cnt=0.0,
        episode_delay=0.0, gd_h=0.0)


@jax.jit
def _fresh_block(z_prior: jax.Array) -> jax.Array:
    return pack_rows(fresh_record(z_prior))


class SimState(NamedTuple):
    block: jax.Array         # f32 [F, N] (or slabs) — row k = ObjStats field k
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

    @property
    def obj(self) -> ObjStats:
        """The block's row view (of one state, not of a stack of them)."""
        return obj_rows(self.block)


def init_state(n_objects: int, capacity: float, key: jax.Array,
               z_prior: jax.Array) -> SimState:
    """Fresh state for a universe of ``n_objects`` and cache ``capacity``.

    ``z_prior`` [N] seeds the per-object latency estimate (the known mean of
    the fetch-latency model, as in the paper's setup)."""
    # one compiled fill: a fresh buffer, never an alias of ``z_prior`` (the
    # streaming engine donates the state, which would invalidate it)
    block = _fresh_block(jnp.broadcast_to(
        jnp.asarray(z_prior, jnp.float32), (n_objects,)))
    # Distinct zero arrays per field: the streaming engine donates the whole
    # state pytree, and XLA rejects donating one buffer behind two leaves.
    zero = lambda: jnp.float32(0.0)
    return SimState(
        block=block,
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
    block = state.block
    is_time = _IS_TIME.reshape((N_FIELDS,) + (1,) * (block.ndim - 1))
    return state._replace(block=jnp.where(is_time, block - delta, block),
                          min_complete=state.min_complete - delta)


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
# Column-write lowerings (DESIGN.md §11).  Three ways to write "block[:, j] =
# col", all bit-identical in results:
#
#   scatter  — :func:`set_col`: one dynamic-update-slice, the unbatched
#              fast path.
#   one-hot  — a masked select over the block: O(F*N) elementwise, the
#              historical batched lowering, kept in-tree as the parity
#              oracle (a batched select leaves untouched positions
#              bit-identical by construction).
#   lane     — :func:`lane_set_col` below: a ``custom_vmap`` seam whose
#              unbatched form IS the dynamic-update-slice and whose batched
#              form is ONE scatter over the lane diagonal of the stacked
#              ``[L, F, N]`` state (or the Pallas kernel,
#              :mod:`repro.kernels.lane_scatter`) — O(F) per lane instead
#              of the one-hot's O(F*N) per lane.
#
# Measured at N=3000 the diagonal scatter wins ~3.5x over the one-hot
# (EXPERIMENTS.md §Perf iteration 6), which is why `lane` is the batched
# lowering for large universes and one-hot the oracle.
# ---------------------------------------------------------------------------

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


def _lane_col_dispatch(x, j, col):
    """``x[l][:, j[l]] = col[l]`` over the stacked ``[L, F, ...]`` block:
    the stack seen as ``L*F`` rows, row ``(l, k)`` written at ``j[l]`` by
    one lane scatter."""
    lanes = x.shape[0]
    rows, idx, val = (x.reshape(lanes * N_FIELDS, -1),
                      jnp.repeat(j, N_FIELDS), col.reshape(-1))
    if _lane_backend == "scatter":
        from repro.kernels.ref import lane_scatter_set_ref
        out = lane_scatter_set_ref(rows, idx, val)
    else:
        from repro.kernels.lane_scatter import lane_scatter_set
        out = lane_scatter_set(rows, idx, val,
                               interpret=(_lane_backend == "kernel_interpret"))
    return out.reshape(x.shape)


def _lane_col_rule(axis_size, in_batched, x, j, col):
    """The batched lowering: one diagonal scatter over the stacked
    ``[L, F, ...]`` block.

    ``x`` is (virtually) always batched; ``j`` is batched under lane vmaps
    whose index is lane-dependent (the sweep engine's commit argmin) and
    unbatched when every lane writes the same column (the hierarchy's
    broadcast request id — one dynamic-update-slice of that column, no
    index vector at all); ``col`` follows the data.  Nested vmaps (traces
    over lanes, grids over shards) batch the emitted scatter with XLA's
    stock rules — still one scatter op, never a select tree.
    """
    xb, jb, cb = in_batched
    if not xb:
        x = jnp.broadcast_to(x, (axis_size,) + jnp.shape(x))
    col = jnp.asarray(col, x.dtype)
    if not cb:
        col = jnp.broadcast_to(col, (axis_size,) + jnp.shape(col))
    if jb:
        return _lane_col_dispatch(x, j, col), True
    return jax.vmap(lambda b, c: set_col(b, j, c))(x, col), True


@jax.custom_batching.custom_vmap
def lane_set_col(x: jax.Array, j, col) -> jax.Array:
    """:func:`set_col` whose vmapped form is a lane scatter."""
    return set_col(x, j, jnp.asarray(col, x.dtype))


lane_set_col.def_vmap(_lane_col_rule)

"""Pure-jnp oracles for the ranking and lane-scatter kernels (the ground truth
the tests compare against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ranking_scores_ref(lam, z, resid, sizes, cached, omega: float):
    """Paper eq. 16 scores + masked argmin victim.

    All inputs (N,) f32 except cached (N,) bool. Returns (scores, victim_idx,
    victim_score); non-cached entries score +inf for the argmin."""
    e = z + lam * z * z
    var = z * z + 6.0 * lam * z**3 + 5.0 * lam * lam * z**4
    f = (e + omega * jnp.sqrt(var)) / (jnp.maximum(resid, 1e-6)
                                       * jnp.maximum(sizes, 1e-6))
    masked = jnp.where(cached, f, jnp.inf)
    idx = jnp.argmin(masked)
    return f, idx, masked[idx]


def lane_scatter_set_ref(x, idx, val):
    """``x[l, idx[l]] = val[l]`` per lane — the jnp oracle (and the CPU
    fast path) for :mod:`repro.kernels.lane_scatter`.

    One gather/scatter over the lane diagonal: O(L) addressed elements,
    never the [L, N] one-hot select.  Bitwise identical to the one-hot
    lowering (untouched positions keep their exact bits; the addressed
    position takes ``val`` verbatim)."""
    lanes = jnp.arange(x.shape[0])
    return x.at[lanes, idx].set(jnp.asarray(val, x.dtype))


def lane_scatter_add_ref(x, idx, val):
    """``x[l, idx[l]] += val[l]`` per lane (see
    :func:`lane_scatter_set_ref`).  The sum is formed on the gathered
    element, matching the one-hot ``where(hot, x + v, x)`` bit-for-bit at
    the addressed position."""
    lanes = jnp.arange(x.shape[0])
    if x.dtype == jnp.bool_:
        return x.at[lanes, idx].set(x[lanes, idx] | jnp.asarray(val, bool))
    return x.at[lanes, idx].set(x[lanes, idx] + jnp.asarray(val, x.dtype))


def tiebreak_argmin_ref(vals, ids):
    """Argmin over ``vals`` with ties broken by the smallest ``ids`` entry.

    ``jnp.argmin`` breaks ties by *position*; that convention is load-bearing
    for the dense simulator, where position IS the object id.  The sparse
    slot-table engine (DESIGN.md §14) stores objects at hash-dependent slots,
    so a positional tie-break would leak the hash seed into results.  This
    two-stage reduction — min value, then min id among the minima — restores
    the dense convention exactly: when ``ids[s] == s`` (the dense identity
    map) it is ``jnp.argmin(vals)`` bit-for-bit, and for any slot permutation
    it picks the slot holding the same *object* the dense argmin would.
    Callers pre-mask ``vals`` (+inf at ineligible entries), so sentinel ids
    at masked slots can only win when every entry is masked — in which case
    the caller's eligibility check fails closed exactly as dense argmin-0
    does."""
    m = jnp.min(vals)
    big = jnp.iinfo(ids.dtype).max
    return jnp.argmin(jnp.where(vals == m, ids, big))


def victim_order_ref(scores, cached, top: int):
    """Masked ascending victim order — the eviction loop's precomputed diet.

    Returns ``(idx, vals)``, the indices and masked scores of the ``top``
    lowest-ranked *cached* objects in ascending ``(score, index)`` order —
    exactly the sequence an evict-until-fit loop that re-runs a masked
    argmin after every eviction would visit, because evicting only ever
    removes entries (DESIGN.md §10).  Non-cached entries are masked to
    +inf, so once the real victims run out the sequence continues with
    ``inf`` sentinels and any rank-compare admission check fails closed.
    ``lax.top_k`` breaks ties in favor of lower indices, matching
    ``argmin``'s first-minimum convention bit-for-bit.
    """
    masked = jnp.where(cached, scores, jnp.inf)
    neg, idx = jax.lax.top_k(-masked, top)
    return idx, -neg

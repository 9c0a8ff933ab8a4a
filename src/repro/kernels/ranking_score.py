"""Fused eviction-ranking kernel (Pallas, TPU target) — the paper's hot loop.

Computes eq. 16 scores for the whole object table and the block-local
ascending victim candidates in ONE streaming pass: score =
(E[D] + w*sigma[D]) / (R * s) with Theorem-2 moments, non-cached entries
masked to the finite sentinel ``INF``.  The table is memory-bound (five f32
streams, ~10 flops/element) so fusing score+mask+select keeps it at one HBM
read instead of the ~7 kernel launches the unfused jnp version costs.

TPU layout: the 1-D ``(N,)`` streams are padded and viewed as ``(rows,
128)`` lane tiles; a block is ``block // 128`` rows (a multiple of 8 rows,
i.e. ``block`` a multiple of 1024, unless one block covers the whole
table); by default it is sized from the table (``_auto_block``).
``omega`` rides in as a broadcast ``(1, 128)`` tile, and each
block's candidates are written into one lane-dense ``(8k, 128)`` tile
(candidate ``e`` at flat position ``e``) — every block shape the compiler
sees obeys the 8x128 rule, and no value is read or stored at a dynamic
index: the in-kernel argmin is a min-reduction followed by a min over the
flat indices that attain it, which is ``argmin``'s first-minimum
convention.  Block-local candidates are merged by a tiny XLA pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = 3.4e38  # python float: jnp constants would be captured by the kernel
_LANES = 128
_SUBLANES = 8
_MIN_BLOCK = _SUBLANES * _LANES     # the smallest block obeying 8x128
_BLOCK_CAP = 128 * _LANES           # 16384 slots, ~393 KB moved per step


def _auto_block(n: int) -> int:
    """Slots per grid step for an ``(N,)`` table: the whole table in one
    block up to ``_MIN_BLOCK`` slots, else the largest multiple of
    ``_MIN_BLOCK`` up to ``_BLOCK_CAP`` that the table, padded to whole
    ``_MIN_BLOCK`` blocks, holds (8 steps at 131 072 slots)."""
    return min(_BLOCK_CAP, -(-n // _MIN_BLOCK) * _MIN_BLOCK)


def _rank_select_kernel(om_ref, lam_ref, z_ref, r_ref, s_ref, c_ref, f_ref,
                        bvals_ref, bidx_ref, *, top: int):
    """Eq.-16 scores + block-local top-``top`` ascending victim candidates,
    one VMEM-resident pass.  The top-E extraction is ``top`` unrolled
    masked-min rounds over the block (top is small and static), so the
    five input streams are still read exactly once per element."""
    ib = pl.program_id(0)
    rows, lanes = lam_ref.shape
    omega = om_ref[...]
    lam = lam_ref[...]
    z = z_ref[...]
    z2 = z * z
    e = z + lam * z2
    var = z2 + 6.0 * lam * z2 * z + 5.0 * lam * lam * z2 * z2
    f = (e + omega * jnp.sqrt(var)) / (
        jnp.maximum(r_ref[...], 1e-6) * jnp.maximum(s_ref[...], 1e-6))
    f_ref[...] = f
    masked = jnp.where(c_ref[...] != 0, f, INF)
    flat = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    crow, ccol = bvals_ref.shape
    cpos = (jax.lax.broadcasted_iota(jnp.int32, (crow, ccol), 0) * ccol
            + jax.lax.broadcasted_iota(jnp.int32, (crow, ccol), 1))
    vals = jnp.full((crow, ccol), INF, jnp.float32)
    idxs = jnp.zeros((crow, ccol), jnp.int32)
    base = ib * (rows * lanes)
    for e_i in range(top):
        mn = jnp.min(masked)
        idx = jnp.min(jnp.where(masked == mn, flat, rows * lanes))
        vals = jnp.where(cpos == e_i, mn, vals)
        idxs = jnp.where(cpos == e_i, idx + base, idxs)
        masked = jnp.where(flat == idx, INF, masked)
    bvals_ref[...] = vals
    bidx_ref[...] = idxs


def _rank_select(lam, z, resid, sizes, cached, omega, top: int,
                 block: int | None, interpret: bool):
    """Run the fused pass; returns ``(scores (N,), cand_vals [G, top],
    cand_idx [G, top])`` with each block's candidates in extraction order.

    ``block`` is the slots per grid step; ``None`` sizes it from the table
    (``_auto_block``): a step costs a fixed ~0.4 us on a v5e beside its
    bytes, so 1024-slot blocks left a 131 072-slot table bound by its 128
    steps.  Scores are elementwise and the merge keeps the first minimum,
    so the results do not depend on the block."""
    n = lam.shape[0]
    if block is None:
        block = _auto_block(n)
    if block % _LANES:
        raise ValueError(f"block={block} must be a multiple of {_LANES}")
    rows = -(-n // _LANES)
    brows = min(block // _LANES, rows)
    if top > brows * _LANES:
        # a single block could then hold more of the global top than it can
        # emit, breaking the union-containment argument of the merge
        raise ValueError(f"top={top} must be <= block={brows * _LANES}")
    npad = -(-rows // brows) * brows * _LANES
    grid = (npad // (brows * _LANES),)

    def tiles(x, fill):
        x = jnp.pad(x, (0, npad - n), constant_values=fill)
        return x.reshape(npad // _LANES, _LANES)

    crows = _SUBLANES * -(-top // (_SUBLANES * _LANES))
    om = jnp.broadcast_to(jnp.asarray(omega, jnp.float32), (1, _LANES))
    stream = pl.BlockSpec((brows, _LANES), lambda i: (i, 0))
    cand = pl.BlockSpec((crows, _LANES), lambda i: (i, 0))
    f, bvals, bidx = pl.pallas_call(
        functools.partial(_rank_select_kernel, top=top),
        grid=grid,
        in_specs=[pl.BlockSpec((1, _LANES), lambda i: (0, 0))] + [stream] * 5,
        out_specs=[stream, cand, cand],
        out_shape=[
            jax.ShapeDtypeStruct((npad // _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid[0] * crows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid[0] * crows, _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(om, tiles(lam.astype(jnp.float32), 0), tiles(z.astype(jnp.float32), 0),
      tiles(resid.astype(jnp.float32), 1), tiles(sizes.astype(jnp.float32), 1),
      tiles(cached.astype(jnp.int32), 0))
    per_block = lambda c: c.reshape(grid[0], crows * _LANES)[:, :top]
    return f.reshape(-1)[:n], per_block(bvals), per_block(bidx)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ranking_scores(lam, z, resid, sizes, cached, *, omega=1.0,
                   block: int | None = None, interpret: bool = False):
    """All inputs (N,); returns (scores (N,), victim_idx, victim_score).

    ``omega`` is a scalar *operand* (python float or traced f32) so the
    simulator can thread a swept PolicyParams.omega through without
    retracing.  ``block`` (slots per grid step) defaults to one sized from
    the table.  ``interpret=True`` runs the Pallas interpreter (any
    backend); the default compiles for the TPU.
    """
    f, bvals, bidx = _rank_select(lam, z, resid, sizes, cached, omega, 1,
                                  block, interpret)
    ib = jnp.argmin(bvals[:, 0])
    return f, bidx[ib, 0], bvals[ib, 0]


@functools.partial(jax.jit, static_argnames=("top", "block", "interpret"))
def ranking_victim_order(lam, z, resid, sizes, cached, *, omega=1.0,
                         top: int = 8, block: int | None = None,
                         interpret: bool = False):
    """Fused rank-and-select: eq. 16 scores AND the masked top-``top``
    ascending victim order in one streaming pass (DESIGN.md §10).

    All inputs (N,); returns ``(scores (N,), idx (top,), vals (top,))``
    where ``idx``/``vals`` list the ``top`` lowest-ranked cached objects in
    ascending ``(score, index)`` order — the same sequence as
    :func:`repro.kernels.ref.victim_order_ref`.  Block-local candidates are
    extracted in-kernel (one HBM read for score + mask + select, vs the
    score-then-sort round trip of the unfused path) and merged with a tiny
    ``top_k`` over ``grid * top`` survivors; candidate values at or above
    the finite in-kernel ``INF`` sentinel are converted to exact ``+inf``
    (scores above 3.4e38 are treated as +inf, the kernel family's
    pre-existing convention).  A block with fewer cached entries than
    ``top`` keeps emitting sentinel-valued candidates (whose lane index is
    meaningless), so the +inf conversion must key on the *candidate
    value*, never re-derive it from the index — an index-based re-mask
    would resurrect finite scores for already-emitted victims and break
    the consumer's evict-until-fit accounting.  The global top-``top`` is
    always contained in the union of block-local top-``top``s, and both
    levels break ties toward lower indices, so the merged order matches
    the jnp oracle wherever values are finite (+inf tail positions may
    carry different — meaningless — indices).  ``interpret`` as in
    :func:`ranking_scores`.
    """
    top = max(1, min(top, lam.shape[0]))
    f, bvals, bidx = _rank_select(lam, z, resid, sizes, cached, omega, top,
                                  block, interpret)
    # merge: candidate arrays are ordered (block, extraction rank), which for
    # equal values coincides with global index order — top_k's positional
    # tie-break therefore reproduces the argmin convention across blocks.
    neg, pos = jax.lax.top_k(-bvals.reshape(-1), top)
    idx = bidx.reshape(-1)[pos]
    vals = jnp.where(-neg >= INF, jnp.inf, -neg)
    return f, idx, vals

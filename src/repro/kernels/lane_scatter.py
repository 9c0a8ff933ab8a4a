"""Batched lane-scatter kernel (Pallas, TPU target): point updates with
lane-varying indices over ``[L, N]`` state.

The simulator's per-object state lives as one ``[F, N]`` block, written a
column at a time; under the sweep engine's lane vmap (policies x params x
capacities x seeds) every column write carries a *different* index per
lane, and the simulator hands this kernel the stacked block as ``L*F``
rows (:func:`repro.core.state.lane_set_col`).  Historically that case
was lowered as a one-hot masked select — O(N) elementwise work per lane per
update, the measured N=3000 unified-roster loss (EXPERIMENTS.md §Perf
iteration 5) — because XLA:CPU executes a batched scatter as a per-lane
loop, which used to be the worse trade at small N.  The lane-update
discipline here is the MoE dispatch one (in-group scatter with
lane-varying targets, GShard-style): touch exactly the ``L`` addressed
elements, never the ``L*N`` table.

This module is the TPU lowering of that discipline: grid over lanes, the
lane indices ride in scalar prefetch (SMEM), and each program's block is
the one 128-lane window of its row that holds the addressed element — the
output aliases the input, so every other window keeps its bits without
being copied.  Inside the window the element is patched with a masked
select (no dynamic-offset store).  The ``[L, N]`` table is viewed as
``[L, 1, N]`` so a ``(1, 1, 128)`` block obeys the TPU's 8x128 tiling rule
for any ``L`` and ``N``; a partial last window is padded on read and masked
on write.  The jnp reference (:func:`repro.kernels.ref.lane_scatter_set_ref`
/ ``lane_scatter_add_ref`` — one gather/scatter over the lane diagonal) is
the CPU fast path and the allclose/bitwise ground truth; interpret mode
runs the kernel itself on any backend (tests/test_kernels.py pins all
three against the one-hot oracle across lane counts and dtypes).

Bool state leaves ride through an i32 view: TPU tiling has no native
1-bit layout, and the set/add semantics are preserved exactly (add on
bool is logical-or in the callers' usage — the simulator only ever
set/or's flags).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_WINDOW = 128


def _scatter_kernel(idx_ref, val_ref, x_ref, out_ref, *, add: bool):
    """One grid step = one lane: patch element ``idx`` of its window."""
    lane = pl.program_id(0)
    i = idx_ref[lane]
    v = val_ref[lane]
    row = x_ref[...]
    col = (jax.lax.broadcasted_iota(jnp.int32, row.shape, 2)
           + (i // _WINDOW) * _WINDOW)
    out_ref[...] = jnp.where(col == i, row + v if add else v, row)


def _lane_scatter(x, idx, val, *, add: bool, interpret: bool):
    lanes, n = x.shape
    as_i32 = x.dtype == jnp.bool_
    if as_i32:
        x, val = x.astype(jnp.int32), val.astype(jnp.int32)
    window = pl.BlockSpec((1, 1, _WINDOW),
                          lambda l, idx, val: (l, 0, idx[l] // _WINDOW))
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, add=add),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(lanes,),
            in_specs=[window], out_specs=window),
        out_shape=jax.ShapeDtypeStruct((lanes, 1, n), x.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), val, x.reshape(lanes, 1, n)).reshape(lanes, n)
    return out.astype(jnp.bool_) if as_i32 else out


@functools.partial(jax.jit, static_argnames=("interpret",))
def lane_scatter_set(x, idx, val, *, interpret: bool = False):
    """``x[l, idx[l]] = val[l]`` per lane; x ``[L, N]``, idx/val ``[L]``.

    ``interpret=True`` runs the Pallas interpreter (any backend); the
    default compiles for the TPU.  Bitwise identical to the one-hot
    lowering ``vmap(lambda r, j, v: where(arange(N) == j, v, r))`` and to
    the jnp reference — untouched positions are kept, the addressed
    position takes ``val`` exactly."""
    return _lane_scatter(x, idx, jnp.asarray(val, x.dtype), add=False,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lane_scatter_add(x, idx, val, *, interpret: bool = False):
    """``x[l, idx[l]] += val[l]`` per lane (logical-or for bool ``x``).

    ``interpret`` as in :func:`lane_scatter_set`.  The sum is computed on
    the addressed element — bit-identical to the one-hot lowering's
    ``where(hot, x + v, x)`` at that position."""
    return _lane_scatter(x, idx, jnp.asarray(val, x.dtype), add=True,
                         interpret=interpret)

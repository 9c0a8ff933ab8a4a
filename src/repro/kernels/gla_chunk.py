"""Chunked gated-linear-attention kernel (Pallas, TPU target).

One kernel serves both mLSTM (xLSTM) and Mamba-2/SSD (Hymba) — they are the
same recurrence (see models/ssm.py).  Grid: (batch*heads, n_chunks); the
chunk dim is minor/sequential, carrying the (d_k x d_v) state and (d_k,)
normalizer in f32 VMEM scratch across chunks.  Within a chunk everything is
dense MXU work: the (L x L) decay-masked score matrix, two (L x d) matmuls,
and the rank-L state update — this is the TPU-native replacement for GPU
warp-scan implementations (DESIGN.md §3).

VMEM: state (d_k x d_v) f32 + chunk tiles; e.g. d_k = d_v = 512, L = 256:
1 MB state + ~1.5 MB tiles — fits with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gla_kernel(q_ref, k_ref, v_ref, b_ref, li_ref, y_ref, sT_ref, nT_ref,
                state_scr, norm_scr, *, scale: float, normalize: bool,
                n_chunks: int, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)
        norm_scr[...] = jnp.zeros_like(norm_scr)

    q = q_ref[0].astype(jnp.float32) * scale      # (L, dk)
    k = k_ref[0].astype(jnp.float32)              # (L, dk)
    v = v_ref[0].astype(jnp.float32)              # (L, dv)
    bc = b_ref[0]                                 # (L,) cumulative log-decay
    li = li_ref[0]                                # (L,) log input gate

    S = state_scr[...]                            # (dk, dv)
    n = norm_scr[...]                             # (dk,)

    # Inter-chunk contribution (decayed read of carried state).
    dec = jnp.exp(bc)[:, None]                    # (L,1)
    qd = q * dec
    y_inter = jax.lax.dot_general(qd, S, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    n_inter = qd @ n                              # (L,)

    # Intra-chunk: A_ts = (q_t . k_s) exp(b_t - b_s + li_s), s <= t.
    gpos = bc[:, None] - bc[None, :] + li[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gmat = jnp.where(col <= row, jnp.exp(gpos), 0.0)
    A = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * gmat
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + y_inter
    if normalize:
        den = jnp.maximum(jnp.abs(A.sum(axis=1) + n_inter), 1.0)
        y = y / den[:, None]
    y_ref[0] = y.astype(y_ref.dtype)

    # State carry to next chunk.
    b_end = bc[chunk - 1]
    w = jnp.exp(b_end - bc + li)[:, None]         # (L,1)
    kw = k * w
    state_scr[...] = (jnp.exp(b_end) * S
                      + jax.lax.dot_general(kw, v, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
    norm_scr[...] = jnp.exp(b_end) * n + kw.sum(axis=0)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        sT_ref[0] = state_scr[...]
        nT_ref[0] = norm_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "normalize", "interpret"))
def gla_chunk(q, k, v, log_f, log_i, *, chunk: int = 256,
              normalize: bool = True, interpret: bool = False):
    """q,k (B,S,H,dk); v (B,S,H,dv); gates (B,S,H).
    Returns (y (B,S,H,dv), (S_state (B,H,dk,dv), n (B,H,dk)))."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # head-major flat layout (B*H, S, d)
    def fl(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    qf, kf, vf = fl(q), fl(k), fl(v)
    lf = log_f.transpose(0, 2, 1).reshape(b * h, s).astype(jnp.float32)
    li = log_i.transpose(0, 2, 1).reshape(b * h, s).astype(jnp.float32)
    # within-chunk inclusive cumulative decay
    bc = jnp.cumsum(lf.reshape(b * h, nc, chunk), axis=-1).reshape(b * h, s)

    grid = (b * h, nc)
    y, sT, nT = pl.pallas_call(
        functools.partial(_gla_kernel, scale=dk ** -0.5,
                          normalize=normalize, n_chunks=nc, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dv), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk), lambda bh, ic: (bh, ic)),
            pl.BlockSpec((1, chunk), lambda bh, ic: (bh, ic)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, dk), lambda bh, ic: (bh, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((b * h, dk), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((dk,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, bc, li)

    y = y.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
    return y, (sT.reshape(b, h, dk, dv), nT.reshape(b, h, dk))

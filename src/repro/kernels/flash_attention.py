"""FlashAttention-style fused attention kernel (Pallas, TPU target).

Grid: (batch*q_heads, Sq/block_q, Sk/block_k) with the K dimension innermost —
on TPU the minor grid dim executes sequentially per core, so the online-
softmax running state (m, l, acc) lives in VMEM scratch and is carried across
K blocks.  GQA is folded into the BlockSpec index maps (q head h reads KV
head h // group).  Causal + sliding-window + sink masking and grok-style
logit soft-capping happen on the f32 logits tile in VMEM.

Block shapes: q tile (block_q, d_head), k/v tiles (block_k, d_head), all MXU
aligned when block_* are multiples of 128 and d_head in {64, 128, 256}.
VMEM footprint ≈ (block_q + 2 block_k) * d_head * 2B + 3 * block_q * block_k
* 4B — e.g. 128/256 blocks at d_head 128: ~0.6 MB, far under the ~16 MB/core
budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *, scale: float, window: int,
               softcap: float, sink: int, n_kblocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                 # (bk, dh)
    v = v_ref[0].astype(jnp.float32)                 # (bk, dh)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)

    qp = qpos_ref[...]                               # (bq,)
    kp = kpos_ref[...]                               # (bk,)
    keep = (kp[None, :] <= qp[:, None]) & (kp >= 0)[None, :]
    if window > 0:
        in_win = kp[None, :] > (qp[:, None] - window)
        if sink > 0:
            in_win |= (kp < sink)[None, :]
        keep &= in_win
    logits = jnp.where(keep, logits, NEG_INF)

    m_prev = m_scr[...]                              # (bq,)
    m_cur = jnp.maximum(m_prev, logits.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(logits - m_cur[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_cur

    @pl.when(ik == n_kblocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "sink", "block_q", "block_k",
                     "interpret"))
def flash_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                    softcap: float = 0.0, sink: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q (B,Sq,H,dh); k,v (B,Sk,KV,dh); q_pos (Sq,), k_pos (Sk,) absolute
    positions. Returns (B,Sq,H,dh)."""
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    group = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # pad sequence dims to block multiples with masked (pos=-1) slots
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=2**30)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-1)
    sq_p, sk_p = sq + pq, sk + pk

    # (B*H, S, dh) layouts; KV head for q-head i is i // group.
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk_p, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk_p, dh)

    grid = (b * h, sq_p // block_q, sk_p // block_k)

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=dh ** -0.5, window=window,
                          softcap=softcap, sink=sink, n_kblocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q,), lambda bh, iq, ik: (iq,)),
            pl.BlockSpec((block_k,), lambda bh, iq, ik: (ik,)),
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m: running max
            pltpu.VMEM((block_q,), jnp.float32),      # l: running denom
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc: running output
        ],
        interpret=interpret,
    )(q_pos.astype(jnp.int32), k_pos.astype(jnp.int32), qf, kf, vf)
    out = out.reshape(b, h, sq_p, dh).transpose(0, 2, 1, 3)
    return out[:, :sq]

"""Decode attention kernel (single new token vs long KV) — FlashDecoding
style split-KV (Pallas, TPU target).

Decode is memory-bound: the whole KV history streams HBM->VMEM once while
compute is a (group x d_head) @ (d_head x block_k) matmul per tile.  Layout
folds batch x kv_head into the parallel grid dim and walks KV blocks on the
sequential minor dim, carrying the online-softmax state in VMEM scratch; the
q tile is the GQA *group* (all q heads of one kv head), so the MXU tile is
(group, block_k) rather than degenerate (1, block_k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale: float, window: int,
                softcap: float, sink: int, n_kblocks: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (G, dh)
    k = k_ref[0].astype(jnp.float32)                 # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (G, bk)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    qp = qpos_ref[0]
    kp = kpos_ref[...]
    keep = (kp <= qp) & (kp >= 0)
    if window > 0:
        in_win = kp > (qp - window)
        if sink > 0:
            in_win |= kp < sink
        keep &= in_win
    logits = jnp.where(keep[None, :], logits, NEG_INF)

    m_prev = m_scr[...]
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
    static_argnames=("window", "softcap", "sink", "block_k", "interpret"))
def decode_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                     softcap: float = 0.0, sink: int = 0,
                     block_k: int = 512, interpret: bool = False):
    """q (B,1,H,dh); k,v (B,Sk,KV,dh); q_pos (1,), k_pos (Sk,).
    Returns (B,1,H,dh)."""
    b, sq, h, dh = q.shape
    assert sq == 1
    sk, kv = k.shape[1], k.shape[2]
    group = h // kv
    block_k = min(block_k, sk)
    pk = (-sk) % block_k
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-1)
    sk_p = sk + pk

    # (B*KV, G, dh) query groups; (B*KV, Sk, dh) KV streams.
    qf = q[:, 0].reshape(b, kv, group, dh).reshape(b * kv, group, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk_p, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk_p, dh)

    grid = (b * kv, sk_p // block_k)
    out = pl.pallas_call(
        functools.partial(_dec_kernel, scale=dh ** -0.5, window=window,
                          softcap=softcap, sink=sink, n_kblocks=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bh, ik: (0,)),
            pl.BlockSpec((block_k,), lambda bh, ik: (ik,)),
            pl.BlockSpec((1, group, dh), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, dh), lambda bh, ik: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv, group, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos.astype(jnp.int32), k_pos.astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, kv * group, dh)[:, None].reshape(b, 1, h, dh)

"""Attention: GQA + RoPE + causal/sliding-window masks + logit softcap.

Two execution paths:
- XLA path (default): plain jnp einsum attention — what the dry-run lowers
  (portable, lets GSPMD choose collectives).
- Pallas path (``use_kernel=True``): flash-attention kernels from
  :mod:`repro.kernels` for TPU execution (validated in interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.activation import constrain
from .layers import apply_rope, init_dense

NEG_INF = -1e30


def init_attn(key, d: int, n_heads: int, n_kv: int, d_head: int,
              dtype=jnp.bfloat16) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d, n_heads * d_head, dtype),
        "wk": init_dense(kk, d, n_kv * d_head, dtype),
        "wv": init_dense(kv, d, n_kv * d_head, dtype),
        "wo": init_dense(ko, n_heads * d_head, d, dtype,
                         scale=(n_heads * d_head) ** -0.5),
    }


def _mask(q_pos, k_pos, window: int, sink: int = 0):
    """Causal (+ optional sliding-window) keep-mask: (…, S_q, S_k).

    ``sink`` positions (< sink) stay visible even outside the window —
    Hymba's meta tokens / attention sinks."""
    keep = (k_pos[..., None, :] <= q_pos[..., :, None]) & (k_pos >= 0)[..., None, :]
    if window > 0:
        in_win = k_pos[..., None, :] > (q_pos[..., :, None] - window)
        if sink > 0:
            in_win |= k_pos[..., None, :] < sink
        keep &= in_win
    return keep


# Above this many query positions the XLA path switches to the q-chunked
# online-softmax form so the S_q x S_k logits never materialize whole
# (32k prefill would otherwise need TBs of f32 logits; see §Perf).
CHUNKED_Q_THRESHOLD = 8192
CHUNK_Q = 512


def _sdpa_chunked(q, k, v, q_pos, k_pos, *, window: int, softcap: float,
                  sink: int, chunk_q: int = CHUNK_Q,
                  unroll: bool = False) -> jax.Array:
    """Exact flash-style attention in pure XLA: lax.map over q chunks with a
    full-K online pass per chunk. Peak logits memory = (B, H, chunk_q, S_k)
    instead of (B, H, S_q, S_k). KV already repeated to H heads."""
    b, sq, h, dh = q.shape
    pad = (-sq) % chunk_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=2**30)
    nq = (sq + pad) // chunk_q
    qc = q.reshape(b, nq, chunk_q, h, dh).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(nq, chunk_q)

    def one_chunk(args):
        qi, pi = args
        logits = jnp.einsum("bqhd,bshd->bhqs", qi, k,
                            preferred_element_type=jnp.float32) * dh ** -0.5
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        keep = _mask(pi, k_pos, window, sink)
        logits = jnp.where(keep[None, None], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", w, v)

    if unroll:
        # python-unrolled for the dry-run cost calibration: XLA cost analysis
        # counts while-loop bodies once, so loops must be inlined to count.
        out = jnp.stack([one_chunk((qc[i], pc[i])) for i in range(nq)])
    else:
        out = jax.lax.map(one_chunk, (qc, pc))      # (nq,B,chunk,H,dh)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sq + pad, h, dh)
    return out[:, :sq]


def sdpa(q, k, v, q_pos, k_pos, *, window: int = 0, softcap: float = 0.0,
         sink: int = 0, use_kernel: bool = False,
         interpret: bool = False, unroll: bool = False) -> jax.Array:
    """q: (B,Sq,H,dh); k,v: (B,Sk,KV,dh). Returns (B,Sq,H,dh)."""
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    assert h % kv == 0, (h, kv)
    if use_kernel and sq > 1:
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                               window=window, softcap=softcap, sink=sink,
                               interpret=interpret)
    if use_kernel and sq == 1:
        from repro.kernels.ops import decode_attention
        return decode_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                window=window, softcap=softcap, sink=sink,
                                interpret=interpret)
    if sq <= 8 and kv != h:
        # Decode: grouped einsum WITHOUT materializing the repeated KV — the
        # repeat would stream the whole cache x group (deepseek decode_32k:
        # 2.1 -> 14.6 GiB/device; §Perf decode iteration 1).  The grouped
        # logits tensor is tiny here (S_q <= 8), so the kv-vs-TP sharding
        # mismatch that rules this layout out for training doesn't bite.
        g = h // kv
        qg = q.reshape(b, sq, kv, g, dh)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                            preferred_element_type=jnp.float32) * dh ** -0.5
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        keep = _mask(q_pos, k_pos, window, sink)
        logits = jnp.where(keep[..., None, None, :, :], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
        return out.reshape(b, sq, h, dh)
    # Train/prefill: repeat KV heads to full H so the TP-sharded head axis
    # stays intact through every einsum (a 5-D (kv, group) split would force
    # GSPMD to replicate the S_q x S_k logits when TP doesn't divide kv —
    # measured 48 GiB/device on grok; see EXPERIMENTS.md §Perf iteration 1).
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    if sq >= CHUNKED_Q_THRESHOLD:
        return _sdpa_chunked(q, k, v, q_pos, k_pos, window=window,
                             softcap=softcap, sink=sink, unroll=unroll)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    logits *= dh ** -0.5
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    keep = _mask(q_pos, k_pos, window, sink)      # (B?, Sq, Sk) or (Sq, Sk)
    while keep.ndim < logits.ndim:
        keep = keep[..., None, :, :]
    logits = jnp.where(keep, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


def attn_apply(p: dict, x: jax.Array, *, n_heads: int, n_kv: int,
               d_head: int, pos: jax.Array, theta: float,
               window: int = 0, softcap: float = 0.0, sink: int = 0,
               cache: dict | None = None, use_kernel: bool = False,
               unroll: bool = False) -> tuple[jax.Array, dict | None]:
    """Full attention block (projections + rope + sdpa + output proj).

    ``cache``: None (training / stateless prefill) or a ring-buffer dict
    {k (B,Sc,KV,dh), v (B,Sc,KV,dh), kpos (Sc,) i32} — ``kpos`` records the
    absolute position stored in each slot (-1 = empty; masked out via the
    causal test).  Sliding-window archs size Sc = sink + window, full
    attention Sc = capacity.  K is stored *post-RoPE* so decode never
    re-rotates history.  ``pos`` is (S,) absolute positions of x's tokens.
    Returns (output, updated_cache).
    """
    b, s, d = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, d_head)
    k = (x @ p["wk"]).reshape(b, s, n_kv, d_head)
    v = (x @ p["wv"]).reshape(b, s, n_kv, d_head)
    q = apply_rope(q, pos, theta)
    k = apply_rope(k, pos, theta)
    q = constrain(q, "act_heads")

    if cache is None:
        out = sdpa(q, k, v, pos, pos, window=window, softcap=softcap,
                   sink=sink, use_kernel=use_kernel, unroll=unroll)
        new_cache = None
    elif s > 1:
        # Prefill: attend over the fresh full sequence, then pack the cache
        # (sink prefix + last `ring` tokens -> unique slots; XLA scatter
        # duplicate order is undefined so we never scatter overwritten slots).
        out = sdpa(q, k, v, pos, pos, window=window, softcap=softcap,
                   sink=sink, use_kernel=use_kernel, unroll=unroll)
        sc = cache["k"].shape[1]
        ring = sc - sink
        if s > ring:
            sel = (jnp.concatenate([jnp.arange(sink), jnp.arange(s - ring, s)])
                   if sink else jnp.arange(s - ring, s))
            k, v, pos_w = k[:, sel], v[:, sel], pos[sel]
        else:
            pos_w = pos
        slots = jnp.where(pos_w < sink, pos_w, sink + (pos_w - sink) % ring)
        cdt = cache["k"].dtype
        k_all = cache["k"].at[:, slots].set(k.astype(cdt))
        v_all = cache["v"].at[:, slots].set(v.astype(cdt))
        kpos = cache["kpos"].at[slots].set(pos_w.astype(jnp.int32))
        new_cache = {"k": k_all, "v": v_all, "kpos": kpos}
    else:
        # Decode: scatter the single new token, attend over the cache.
        sc = cache["k"].shape[1]
        ring = sc - sink
        slots = jnp.where(pos < sink, pos, sink + (pos - sink) % ring)
        cdt = cache["k"].dtype           # may be fp8 (cfg.kv_dtype='f8')
        k_all = cache["k"].at[:, slots].set(k.astype(cdt))
        v_all = cache["v"].at[:, slots].set(v.astype(cdt))
        kpos = cache["kpos"].at[slots].set(pos.astype(jnp.int32))
        ka = k_all.astype(k.dtype) if cdt != k.dtype else k_all
        va = v_all.astype(v.dtype) if cdt != v.dtype else v_all
        out = sdpa(q, ka, va, pos, kpos, window=window,
                   softcap=softcap, sink=sink, use_kernel=use_kernel)
        new_cache = {"k": k_all, "v": v_all, "kpos": kpos}
    out = out.reshape(b, s, n_heads * d_head)
    return out @ p["wo"], new_cache


def init_kv_cache(batch: int, capacity: int, n_kv: int, d_head: int,
                  dtype=jnp.bfloat16) -> dict:
    z = jnp.zeros((batch, capacity, n_kv, d_head), dtype)
    return {"k": z, "v": z,
            "kpos": jnp.full((capacity,), -1, jnp.int32)}

"""Linear-recurrence sequence mixers: mLSTM (xLSTM) and Mamba-2-style SSD.

Both are instances of one gated-linear-attention recurrence

    S_t = f_t * S_{t-1} + i_t * k_t v_t^T        (state: d_k x d_v per head)
    n_t = f_t * n_{t-1} + i_t * k_t              (mLSTM normalizer)
    y_t = q_t^T S_t [/ max(|q_t . n_t|, 1)]

executed CHUNKWISE: dense O(L_c^2) compute inside a chunk (MXU-friendly) and
a length-S/L_c recurrence across chunk boundaries.  This is the TPU-native
adaptation (DESIGN.md §3): no warp scans, just matmuls + a short carry chain.
``unroll=True`` unrolls the cross-chunk loop (used by the dry-run so XLA cost
analysis sees every FLOP; while-loop bodies are counted once otherwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import init_dense

# ---------------------------------------------------------------------------
# Core chunkwise gated linear attention.
# Shapes: q,k (B,S,H,dk) v (B,S,H,dv); log_f, log_i (B,S,H) (log-space gates).
# ---------------------------------------------------------------------------
def chunked_gla(q, k, v, log_f, log_i, *, chunk: int = 256,
                normalize: bool = True, init_state=None, unroll: bool = False,
                use_kernel: bool = False, interpret: bool = False):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        # Pad to a chunk multiple with no-op tokens (f=1, i=0): the carried
        # state passes through unchanged and padded outputs are discarded.
        pad = chunk - s % chunk
        padf = lambda x, val: jnp.pad(x, [(0, 0), (0, pad)] +
                                      [(0, 0)] * (x.ndim - 2),
                                      constant_values=val)
        y, st = chunked_gla(padf(q, 0), padf(k, 0), padf(v, 0),
                            padf(log_f, 0.0), padf(log_i, -30.0),
                            chunk=chunk, normalize=normalize,
                            init_state=init_state, unroll=unroll,
                            use_kernel=use_kernel, interpret=interpret)
        return y[:, :s], st
    nc = s // chunk
    scale = dk ** -0.5

    if use_kernel:
        from repro.kernels.ops import gla_chunk_kernel_apply
        return gla_chunk_kernel_apply(q, k, v, log_f, log_i, chunk=chunk,
                                      normalize=normalize,
                                      interpret=interpret)

    # (B, nc, L, H, *) chunked views, head-major for the scan.
    def to_chunks(x):
        return x.reshape(b, nc, chunk, *x.shape[2:])

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lfc, lic = to_chunks(log_f), to_chunks(log_i)

    # Within-chunk cumulative log decay (inclusive of own forget gate).
    bcum = jnp.cumsum(lfc, axis=2)                      # (B,nc,L,H)

    if init_state is None:
        s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        n0 = jnp.zeros((b, h, dk), jnp.float32)
    else:
        s0, n0 = init_state

    def one_chunk(carry, xs):
        S, n = carry                                    # (B,H,dk,dv), (B,H,dk)
        qx, kx, vx, bx, lx = xs                         # (B,L,H,*)
        qf = qx.astype(jnp.float32) * scale
        kf = kx.astype(jnp.float32)
        vf = vx.astype(jnp.float32)
        # Inter-chunk: decayed read of the carried state.
        dec_t = jnp.exp(bx)                             # (B,L,H)
        h_inter = jnp.einsum("blhk,bhkv->blhv", qf * dec_t[..., None], S)
        n_inter = jnp.einsum("blhk,bhk->blh", qf * dec_t[..., None], n)
        # Intra-chunk: A_ts = (q_t.k_s) exp(b_t - b_s + li_s), s <= t.
        gpos = bx[:, :, None, :] - bx[:, None, :, :] + lx[:, None, :, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        gmat = jnp.where(tri[None, :, :, None], jnp.exp(gpos), 0.0)
        qkt = jnp.einsum("blhk,bmhk->blmh", qf, kf)
        A = qkt * gmat                                  # (B,L,L',H)
        h_intra = jnp.einsum("blmh,bmhv->blhv", A, vf)
        n_intra = A.sum(axis=2)                         # (B,L,H)
        y = h_intra + h_inter
        if normalize:
            denom = jnp.maximum(jnp.abs(n_intra + n_inter), 1.0)
            y = y / denom[..., None]
        # State carry to the next chunk.
        b_end = bx[:, -1, :]                            # (B,H)
        w = jnp.exp(b_end[:, None, :] - bx + lx)        # (B,L,H)
        kw = kf * w[..., None]
        S = jnp.exp(b_end)[..., None, None] * S + jnp.einsum(
            "blhk,blhv->bhkv", kw, vf)
        n = jnp.exp(b_end)[..., None] * n + kw.sum(axis=1)
        return (S, n), y.astype(q.dtype)

    xs = (qc, kc, vc, bcum, lic)
    if unroll:
        carry, ys = (s0, n0), []
        for c in range(nc):
            carry, y = one_chunk(carry, jax.tree.map(lambda a: a[:, c], xs))
            ys.append(y)
        y = jnp.stack(ys, axis=1)
        (s0, n0) = carry
    else:
        xs_t = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), xs)
        (s0, n0), y = jax.lax.scan(one_chunk, (s0, n0), xs_t)
        y = jnp.moveaxis(y, 0, 1)
    return y.reshape(b, s, h, dv), (s0, n0)


def gla_decode_step(q, k, v, log_f, log_i, state, *, normalize: bool = True):
    """Single-token recurrent update. q,k (B,H,dk), v (B,H,dv), gates (B,H)."""
    S, n = state
    dk = q.shape[-1]
    f = jnp.exp(log_f.astype(jnp.float32))[..., None]
    i = jnp.exp(log_i.astype(jnp.float32))[..., None]
    kf = k.astype(jnp.float32)
    S = f[..., None] * S + (i * kf)[..., None] * v.astype(jnp.float32)[..., None, :]
    n = f * n + i * kf
    qf = q.astype(jnp.float32) * dk ** -0.5
    y = jnp.einsum("bhk,bhkv->bhv", qf, S)
    if normalize:
        denom = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", qf, n)), 1.0)
        y = y / denom[..., None]
    return y.astype(q.dtype), (S, n)


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM): up-proj -> causal conv -> heads -> GLA -> gated down.
# ---------------------------------------------------------------------------
def init_mlstm(key, d: int, n_heads: int, proj_factor: float = 2.0,
               conv_k: int = 4, dtype=jnp.bfloat16) -> dict:
    di = int(d * proj_factor)
    ks = jax.random.split(key, 8)
    return {
        "w_up": init_dense(ks[0], d, 2 * di, dtype),       # x and z gate
        "conv": (jax.random.normal(ks[1], (conv_k, di), jnp.float32)
                 * 0.1).astype(dtype),
        "wq": init_dense(ks[2], di, di, dtype),
        "wk": init_dense(ks[3], di, di, dtype),
        "wv": init_dense(ks[4], di, di, dtype),
        "w_gates": init_dense(ks[5], di, 2 * n_heads, jnp.float32),
        "skip": (jnp.ones((di,), jnp.float32)).astype(dtype),
        "w_down": init_dense(ks[6], di, d, dtype),
    }


def causal_conv(x, w, tail=None):
    """x (B,S,C), w (K,C) depthwise causal conv; ``tail`` (B,K-1,C) carries
    state across decode steps. Returns (y, new_tail)."""
    k = w.shape[0]
    pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype) if tail is None else tail
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return y, xp[:, -(k - 1):] if k > 1 else None


def mlstm_apply(p, x, *, n_heads: int, state=None, conv_tail=None,
                chunk: int = 256, unroll: bool = False,
                use_kernel: bool = False):
    """x: (B,S,d). state/conv_tail carry decode state. Returns
    (out, (state, conv_tail))."""
    b, s, d = x.shape
    up = x @ p["w_up"]
    xi, z = jnp.split(up, 2, axis=-1)
    di = xi.shape[-1]
    dh = di // n_heads
    xc, conv_tail = causal_conv(xi, p["conv"], conv_tail)
    xc = jax.nn.silu(xc)
    q = (xc @ p["wq"]).reshape(b, s, n_heads, dh)
    k = (xc @ p["wk"]).reshape(b, s, n_heads, dh)
    v = (xi @ p["wv"]).reshape(b, s, n_heads, dh)
    gates = (xc.astype(jnp.float32) @ p["w_gates"]).reshape(b, s, n_heads, 2)
    log_i = -jax.nn.softplus(-gates[..., 0])       # log sigmoid(i~)
    log_f = -jax.nn.softplus(-gates[..., 1])       # log sigmoid(f~)
    if s == 1 and state is not None:
        y, state = gla_decode_step(q[:, 0], k[:, 0], v[:, 0],
                                   log_f[:, 0], log_i[:, 0], state)
        y = y[:, None]
    else:
        y, state = chunked_gla(q, k, v, log_f, log_i, chunk=chunk,
                               init_state=state, unroll=unroll,
                               use_kernel=use_kernel)
    y = y.reshape(b, s, di) + xc * p["skip"]
    out = (y * jax.nn.silu(z)) @ p["w_down"]
    return out, (state, conv_tail)


def init_gla_state(batch: int, n_heads: int, dk: int, dv: int):
    return (jnp.zeros((batch, n_heads, dk, dv), jnp.float32),
            jnp.zeros((batch, n_heads, dk), jnp.float32))


# ---------------------------------------------------------------------------
# Mamba(-2/SSD-style) mixer for Hymba's parallel SSM heads.
# ---------------------------------------------------------------------------
def init_mamba(key, d: int, d_inner: int, n_heads: int, d_state: int,
               conv_k: int = 4, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 6)
    return {
        "w_in": init_dense(ks[0], d, 2 * d_inner, dtype),   # x and z
        "conv": (jax.random.normal(ks[1], (conv_k, d_inner), jnp.float32)
                 * 0.1).astype(dtype),
        "w_bc": init_dense(ks[2], d_inner, 2 * d_state * n_heads, dtype),
        "w_dt": init_dense(ks[3], d_inner, n_heads, jnp.float32),
        "a_log": jnp.zeros((n_heads,), jnp.float32),        # A = -exp(a_log)
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "w_out": init_dense(ks[4], d_inner, d, dtype),
    }


def mamba_apply(p, x, *, n_heads: int, d_state: int, state=None,
                conv_tail=None, chunk: int = 256, unroll: bool = False,
                use_kernel: bool = False):
    """SSD: scalar decay per head; k=B, q=C, v=dt*x (head-split channels)."""
    b, s, d = x.shape
    xi, z = jnp.split(x @ p["w_in"], 2, axis=-1)
    d_inner = xi.shape[-1]
    ph = d_inner // n_heads                                  # channels/head
    xc, conv_tail = causal_conv(xi, p["conv"], conv_tail)
    xc = jax.nn.silu(xc)
    bc = (xc @ p["w_bc"]).reshape(b, s, n_heads, 2 * d_state)
    bmat, cmat = jnp.split(bc, 2, axis=-1)                   # (B,S,H,N)
    dt = jax.nn.softplus(xc.astype(jnp.float32) @ p["w_dt"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])                                 # (H,)
    log_f = dt * a                                           # (B,S,H)
    log_i = jnp.log(jnp.maximum(dt, 1e-6))
    v = xc.reshape(b, s, n_heads, ph)
    # Note dk here = d_state, dv = channels-per-head.
    if s == 1 and state is not None:
        y, state = gla_decode_step(cmat[:, 0], bmat[:, 0], v[:, 0],
                                   log_f[:, 0], log_i[:, 0], state,
                                   normalize=False)
        y = y[:, None]
    else:
        y, state = chunked_gla(cmat, bmat, v, log_f, log_i, chunk=chunk,
                               normalize=False, init_state=state,
                               unroll=unroll, use_kernel=use_kernel)
    y = y.reshape(b, s, d_inner)
    y = y + xc * jnp.repeat(p["d_skip"], ph).astype(xc.dtype)
    out = (y * jax.nn.silu(z)) @ p["w_out"]
    return out, (state, conv_tail)


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM): scalar recurrence with exponential gating and a
# block-diagonal hidden-to-hidden recurrence.  Inherently sequential (the
# hidden state feeds the gates), so it runs as a lax.scan over time — used
# by the xlstm-350m [7:1] variant (cfg.slstm_every); the dry-run default is
# the all-mLSTM [1:0] variant so XLA cost analysis counts every FLOP
# (DESIGN.md §5).
# ---------------------------------------------------------------------------
def init_slstm(key, d: int, n_heads: int, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 3)
    dh = d // n_heads
    return {
        # input projections for i, f, z, o gates (4d)
        "w_x": init_dense(ks[0], d, 4 * d, dtype),
        # block-diagonal recurrent weights per head: (H, dh, 4*dh)
        "w_h": (jax.random.normal(ks[1], (n_heads, dh, 4 * dh), jnp.float32)
                * dh ** -0.5).astype(dtype),
        "w_out": init_dense(ks[2], d, d, dtype),
    }


def slstm_apply(p, x, *, n_heads: int, state=None):
    """x: (B,S,d). state: (c, n, h, m) each (B,H,dh) — returns (out, state).

    Exponential gating with the max-stabilizer m (xLSTM eq. 19-25):
        i = exp(i~ - m'), f = exp(log-sigmoid(f~) + m - m')
        c = f*c + i*z ; n = f*n + i ; h = o * c/n
    """
    b, s, d = x.shape
    dh = d // n_heads
    gx = (x @ p["w_x"]).reshape(b, s, n_heads, 4 * dh)

    if state is None:
        z = jnp.zeros((b, n_heads, dh), jnp.float32)
        state = (z, z + 1e-6, z, z - 1e30 * 0.0)

    w_h = p["w_h"].astype(jnp.float32)

    def step(carry, gxt):
        c, n, h, m = carry
        rec = jnp.einsum("bhd,hde->bhe", h, w_h)          # (B,H,4dh)
        g = gxt.astype(jnp.float32) + rec
        it, ft, zt, ot = jnp.split(g, 4, axis=-1)
        log_f = -jax.nn.softplus(-ft)                     # log sigmoid
        m_new = jnp.maximum(log_f + m, it)
        i = jnp.exp(it - m_new)
        f = jnp.exp(log_f + m - m_new)
        c = f * c + i * jnp.tanh(zt)
        n = f * n + i
        h = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    state, hs = jax.lax.scan(step, state, jnp.moveaxis(gx, 1, 0))
    out = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(x.dtype)
    return out @ p["w_out"], state

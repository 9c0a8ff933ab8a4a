"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (decode_attention, flash_attention, gla_chunk,
                               ranking_scores)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,sq,sk,h,kv,dh", [
    (1, 128, 128, 4, 4, 64),     # MHA square
    (2, 64, 256, 8, 2, 64),      # GQA, kv-longer (cache-style)
    (1, 256, 256, 6, 3, 128),    # odd head group
    (2, 100, 100, 4, 2, 64),     # non-block-multiple seq (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, sq, sk, h, kv, dh, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, dh), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, dh), dtype)
    # q occupies the tail of the k timeline (prefill continuation layout)
    q_pos = jnp.arange(sk - sq, sk, dtype=jnp.int32)
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    got = flash_attention(q, k, v, q_pos, k_pos, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, q_pos, k_pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,softcap,sink", [
    (0, 0.0, 0), (32, 0.0, 0), (32, 0.0, 8), (0, 30.0, 0)])
def test_flash_attention_masks_and_softcap(window, softcap, sink):
    ks = jax.random.split(jax.random.key(1), 3)
    b, s, h, dh = 1, 192, 4, 64
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, 2, dh), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    got = flash_attention(q, k, v, pos, pos, window=window, softcap=softcap,
                          sink=sink, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, pos, pos, window=window,
                                   softcap=softcap, sink=sink)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,sk,h,kv,dh", [
    (2, 256, 8, 2, 64), (1, 500, 4, 4, 128), (4, 1024, 8, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(b, sk, h, kv, dh, dtype):
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, dh), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, dh), dtype)
    q_pos = jnp.array([sk - 1], jnp.int32)
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    got = decode_attention(q, k, v, q_pos, k_pos, block_k=128,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, q_pos, k_pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_ring_buffer_masking():
    """Partially-filled ring cache: empty slots (kpos=-1) must be ignored."""
    ks = jax.random.split(jax.random.key(3), 3)
    b, sk, h, kv, dh = 1, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, kv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, kv, dh), jnp.float32)
    k_pos = jnp.where(jnp.arange(sk) < 70, jnp.arange(sk), -1).astype(jnp.int32)
    q_pos = jnp.array([69], jnp.int32)
    got = decode_attention(q, k, v, q_pos, k_pos, block_k=64,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, q_pos, k_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,s,h,dk,dv,chunk", [
    (1, 128, 2, 16, 32, 32),     # mamba-ish: small state, wide channels
    (2, 256, 2, 64, 64, 64),     # mLSTM-ish square heads
    (1, 64, 4, 8, 16, 16),
])
@pytest.mark.parametrize("normalize", [True, False])
def test_gla_chunk_matches_sequential_ref(b, s, h, dk, dv, chunk, normalize):
    ks = jax.random.split(jax.random.key(4), 5)
    q = jax.random.normal(ks[0], (b, s, h, dk), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, dk), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (b, s, h, dv), jnp.float32)
    log_f = -jax.nn.softplus(-jax.random.normal(ks[3], (b, s, h)) - 1.0)
    log_i = -jax.nn.softplus(-jax.random.normal(ks[4], (b, s, h)))
    y, (S, n) = gla_chunk(q, k, v, log_f, log_i, chunk=chunk,
                          normalize=normalize, interpret=True)
    y_ref, (S_ref, n_ref) = ref.gla_chunk_ref(q, k, v, log_f, log_i,
                                              normalize=normalize)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(n), np.asarray(n_ref),
                               atol=2e-4, rtol=2e-3)


def test_gla_chunk_equals_model_chunked_gla():
    """Kernel == the XLA chunked implementation used by the models."""
    from repro.models.ssm import chunked_gla
    ks = jax.random.split(jax.random.key(5), 5)
    b, s, h, dk, dv = 2, 128, 2, 32, 32
    q = jax.random.normal(ks[0], (b, s, h, dk), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, dk), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (b, s, h, dv), jnp.float32)
    log_f = -jax.nn.softplus(-jax.random.normal(ks[3], (b, s, h)))
    log_i = -jax.nn.softplus(-jax.random.normal(ks[4], (b, s, h)))
    y_k, (s_k, n_k) = gla_chunk(q, k, v, log_f, log_i, chunk=32,
                                interpret=True)
    y_x, (s_x, n_x) = chunked_gla(q, k, v, log_f, log_i, chunk=32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_x),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("n", [100, 1024, 5000])
@pytest.mark.parametrize("omega", [0.0, 1.0, 2.5])
def test_ranking_scores_matches_ref(n, omega):
    ks = jax.random.split(jax.random.key(6), 5)
    lam = jax.random.uniform(ks[0], (n,), minval=1e-3, maxval=50.0)
    z = jax.random.uniform(ks[1], (n,), minval=1e-3, maxval=2.0)
    resid = jax.random.uniform(ks[2], (n,), minval=1e-3, maxval=10.0)
    sizes = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=100.0)
    cached = jax.random.bernoulli(ks[4], 0.5, (n,))
    f, idx, val = ranking_scores(lam, z, resid, sizes, cached, omega=omega,
                                 block=256, interpret=True)
    f_ref, idx_ref, val_ref = ref.ranking_scores_ref(lam, z, resid, sizes,
                                                     cached, omega)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-5)
    assert int(idx) == int(idx_ref)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)


@pytest.mark.parametrize("n,top", [(100, 4), (1024, 8), (5000, 16)])
def test_ranking_victim_order_matches_ref(n, top):
    """The fused rank-and-select pass (block-local top-E + host merge) must
    reproduce the jnp oracle's ascending (score, index) victim order."""
    from repro.kernels.ranking_score import ranking_victim_order
    ks = jax.random.split(jax.random.key(9), 5)
    lam = jax.random.uniform(ks[0], (n,), minval=1e-3, maxval=50.0)
    z = jax.random.uniform(ks[1], (n,), minval=1e-3, maxval=2.0)
    resid = jax.random.uniform(ks[2], (n,), minval=1e-3, maxval=10.0)
    sizes = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=100.0)
    cached = jax.random.bernoulli(ks[4], 0.5, (n,))
    f, idx, vals = ranking_victim_order(lam, z, resid, sizes, cached,
                                        omega=1.0, top=top, block=256,
                                        interpret=True)
    f_ref, _, _ = ref.ranking_scores_ref(lam, z, resid, sizes, cached, 1.0)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-5)
    # the order must equal the oracle's order over the KERNEL's own scores
    # (scores differ from the jnp oracle only in ulps; the contract under
    # test is the selection, not the arithmetic)
    idx_ref, vals_ref = ref.victim_order_ref(f, cached, top)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(vals_ref))


def test_ranking_victim_order_sparse_cache_emits_inf_sentinels():
    """Fewer cached objects than ``top``: exhausted extraction rounds must
    surface as +inf values, NEVER as resurrected finite scores (a finite
    duplicate would make the eviction loop double-free the same object's
    size — regression test for the index-based re-mask bug)."""
    from repro.kernels.ranking_score import ranking_victim_order
    n = 256
    lam = jnp.full((n,), 1.0)
    z = jnp.full((n,), 0.1)
    resid = jnp.full((n,), 1.0)
    sizes = jnp.full((n,), 2.0)
    cached = jnp.zeros((n,), bool).at[jnp.asarray([0, 9])].set(True)
    f, idx, vals = ranking_victim_order(lam, z, resid, sizes, cached,
                                        omega=1.0, top=8, block=128,
                                        interpret=True)
    v = np.asarray(vals)
    assert np.isfinite(v[:2]).all()
    assert set(np.asarray(idx)[:2]) == {0, 9}
    assert np.isinf(v[2:]).all()        # no finite duplicates past the cache


def _rank_inputs(n, seed):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.uniform(ks[0], (n,), minval=1e-3, maxval=50.0),
            jax.random.uniform(ks[1], (n,), minval=1e-3, maxval=2.0),
            jax.random.uniform(ks[2], (n,), minval=1e-3, maxval=10.0),
            jax.random.uniform(ks[3], (n,), minval=1.0, maxval=100.0),
            jax.random.bernoulli(ks[4], 0.5, (n,)))


def _rank_both(n, block):
    """``ranking_scores`` and ``ranking_victim_order(top=8)`` over one
    table, interpreted, as numpy arrays."""
    from repro.kernels.ranking_score import ranking_victim_order
    args = _rank_inputs(n, 11)
    f, idx, val = ranking_scores(*args, omega=1.0, block=block,
                                 interpret=True)
    g, order, vals = ranking_victim_order(*args, omega=1.0, top=8,
                                          block=block, interpret=True)
    return [np.asarray(x) for x in (f, idx, val, g, order, vals)]


@pytest.mark.parametrize("block", [1024, 4096, 8192, 16384, None])
@pytest.mark.parametrize("n", [100, 5000, 40_000])
def test_rank_select_is_block_invariant(n, block):
    """Scores, the victim and the top-8 order are bitwise the same at any
    block, the table-sized default included: scores are elementwise and
    the merge keeps the first minimum across blocks."""
    for got, want in zip(_rank_both(n, block), _rank_both(n, 1024)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [1, 100, 1024, 1025, 5000, 40_000, 131_072,
                               200_000])
def test_auto_block_sizes_the_grid_from_the_table(n):
    """One block up to 1024 slots; above, a multiple of 1024 up to the
    16384-slot cap and within the padded table; 8 steps at 131 072."""
    from repro.kernels.ranking_score import _auto_block, _rank_select
    block = _auto_block(n)
    spec = jax.ShapeDtypeStruct((n,), jnp.float32)
    cand = jax.eval_shape(
        lambda *a: _rank_select(*a, 1.0, 1, None, True)[1],
        spec, spec, spec, spec, jax.ShapeDtypeStruct((n,), jnp.bool_))
    steps = cand.shape[0]
    if n <= 1024:
        assert steps == 1
    else:
        assert block % 1024 == 0 and block <= 16384
        assert block <= -(-n // 1024) * 1024
        assert steps == -(-n // block)
    if n == 131_072:
        assert steps == 8


def test_victim_order_ref_is_argmin_remove_sequence():
    """victim_order_ref == iterative masked argmin-and-remove, ties and
    non-cached +inf sentinels included (the eviction-loop contract)."""
    scores = jnp.asarray([3.0, 1.0, 2.0, 1.0, 5.0, 1.0], jnp.float32)
    cached = jnp.asarray([True, True, False, True, True, True])
    idx, vals = ref.victim_order_ref(scores, cached, 6)
    m = np.where(np.asarray(cached), np.asarray(scores), np.inf)
    want = []
    for _ in range(6):
        v = int(np.argmin(m))
        want.append((v, m[v]))
        m[v] = np.inf
    # positions holding +inf may differ in index (argmin returns the first
    # remaining slot) — values must match; indices must match while finite
    np.testing.assert_array_equal(np.asarray(vals), [w[1] for w in want])
    for k, (wi, wv) in enumerate(want):
        if np.isfinite(wv):
            assert int(idx[k]) == wi


def test_ranking_scores_agrees_with_core_ranking():
    """Kernel scores == core/ranking.py eq.16 (the simulator's rank_fn)."""
    from repro.core.ranking import PolicyParams, rank_stochastic_vacdh
    from repro.core.state import ObjStats
    n = 256
    ks = jax.random.split(jax.random.key(7), 4)
    lam = jax.random.uniform(ks[0], (n,), minval=0.1, maxval=20.0)
    z = jax.random.uniform(ks[1], (n,), minval=0.01, maxval=1.0)
    t = 100.0
    last = t - jax.random.uniform(ks[2], (n,), minval=0.1, maxval=10.0)
    sizes = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=50.0)
    # the kernel takes R as an input; core's default estimator is R = 1/lam
    f_k, _, _ = ranking_scores(lam, z, 1.0 / lam, sizes,
                               jnp.ones(n, bool), omega=1.0, interpret=True)
    o = ObjStats(
        cached=jnp.ones(n, bool), in_flight=jnp.zeros(n, bool),
        complete_t=jnp.zeros(n), issue_t=jnp.zeros(n),
        last_access=last, first_access=last,
        gap_mean=1.0 / lam, count=jnp.full(n, 5.0), z_est=z,
        agg_sum=jnp.zeros(n), agg_sq_sum=jnp.zeros(n),
        agg_cnt=jnp.zeros(n), episode_delay=jnp.zeros(n),
        gd_h=jnp.zeros(n))
    f_core = rank_stochastic_vacdh(o, sizes, jnp.float32(t),
                                   PolicyParams(resid="rate"))
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_core),
                               rtol=2e-4)


def test_slstm_shapes_and_state_continuity():
    """sLSTM: finite outputs + split-sequence state continuity."""
    from repro.models.ssm import init_slstm, slstm_apply
    key = jax.random.key(0)
    b, s, d, h = 2, 24, 32, 4
    p = init_slstm(key, d, h)
    x = jax.random.normal(jax.random.key(1), (b, s, d), jnp.float32) * 0.5
    y_full, st_full = slstm_apply(p, x, n_heads=h)
    assert y_full.shape == (b, s, d)
    assert bool(jnp.all(jnp.isfinite(y_full)))
    y1, st1 = slstm_apply(p, x[:, :12], n_heads=h)
    y2, st2 = slstm_apply(p, x[:, 12:], n_heads=h, state=st1)
    np.testing.assert_allclose(np.asarray(y_full[:, 12:]), np.asarray(y2),
                               atol=1e-4, rtol=1e-3)
    for a, bb in zip(st_full, st2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=1e-4, rtol=1e-3)


def test_slstm_gradients_finite():
    from repro.models.ssm import init_slstm, slstm_apply
    p = init_slstm(jax.random.key(2), 16, 2)
    x = jax.random.normal(jax.random.key(3), (1, 10, 16), jnp.float32)

    def loss(p):
        y, _ = slstm_apply(p, x, n_heads=2)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g = jax.grad(loss)(p)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))


# ---------------------------------------------------------------------------
# lane scatter: batched point updates with lane-varying indices (the state
# update seam's batched lowering — DESIGN.md §11)
# ---------------------------------------------------------------------------
def _lane_case(lanes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    n = 53
    if dtype == jnp.bool_:
        x = rng.standard_normal((lanes, n)) > 0
        val = rng.standard_normal(lanes) > 0
    else:
        x = rng.standard_normal((lanes, n)).astype(np.float32)
        val = rng.standard_normal(lanes).astype(np.float32)
    idx = rng.integers(0, n, lanes).astype(np.int32)
    # duplicate-column case: two lanes addressing the same column must not
    # interfere (each lane owns its row)
    if lanes > 1:
        idx[-1] = idx[0]
    return jnp.asarray(x), jnp.asarray(idx), jnp.asarray(val), n


def _onehot_oracle(x, idx, val, n, add):
    def one(r, j, v):
        hot = jnp.arange(n) == j
        if add:
            new = (r | v) if r.dtype == jnp.bool_ else r + v
            return jnp.where(hot, new, r)
        return jnp.where(hot, v, r)

    return jax.vmap(one)(x, idx, val)


@pytest.mark.parametrize("lanes", [1, 7, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bool_])
@pytest.mark.parametrize("add", [False, True])
def test_lane_scatter_bitwise_matches_onehot(lanes, dtype, add):
    """Kernel (interpret), jnp ref, and the one-hot oracle must agree
    bit-for-bit across lane counts and both state dtypes."""
    from repro.kernels.lane_scatter import lane_scatter_add, lane_scatter_set
    x, idx, val, n = _lane_case(lanes, dtype)
    want = np.asarray(_onehot_oracle(x, idx, val, n, add))
    if add:
        got_ref = ref.lane_scatter_add_ref(x, idx, val)
        got_kern = lane_scatter_add(x, idx, val, interpret=True)
    else:
        got_ref = ref.lane_scatter_set_ref(x, idx, val)
        got_kern = lane_scatter_set(x, idx, val, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_ref), want)
    np.testing.assert_array_equal(np.asarray(got_kern), want)


def test_lane_seam_unbatched_and_batched_forms_agree():
    """state.lane_set/lane_add: the custom_vmap unbatched form (a point
    scatter) and the vmapped form (the diagonal scatter) must write the
    same bits — including a shared scalar index (the hierarchy's broadcast
    request id), which lowers as a column update."""
    from repro.core.state import lane_add, lane_set
    x, idx, val, n = _lane_case(7, jnp.float32, seed=3)
    want_set = _onehot_oracle(x, idx, val, n, add=False)
    want_add = _onehot_oracle(x, idx, val, n, add=True)
    got_set = jax.vmap(lane_set)(x, idx, val)
    got_add = jax.vmap(lane_add)(x, idx, val)
    np.testing.assert_array_equal(np.asarray(got_set), np.asarray(want_set))
    np.testing.assert_array_equal(np.asarray(got_add), np.asarray(want_add))
    # unbatched == row-wise python loop
    for l in range(7):
        np.testing.assert_array_equal(
            np.asarray(lane_set(x[l], idx[l], val[l])),
            np.asarray(want_set[l]))
    # shared scalar index under vmap (in_batched=False for j)
    j = jnp.int32(11)
    got = jax.vmap(lambda r, v: lane_set(r, j, v))(x, val)
    want = jax.vmap(lambda r, v: jnp.where(jnp.arange(n) == j, v, r))(x, val)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

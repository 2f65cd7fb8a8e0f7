"""Pallas kernels: shape/dtype sweeps + hypothesis, allclose vs ref.py
oracles. interpret=True on CPU per the deliverable contract."""
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import givens
from repro.kernels import ops, ref


@pytest.mark.parametrize("m,n", [(8, 8), (64, 32), (100, 64), (257, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_givens_rotate_sweep(m, n, dtype):
    key = jax.random.PRNGKey(m * 1000 + n)
    X = jax.random.normal(key, (m, n)).astype(dtype)
    perm = np.random.RandomState(0).permutation(n)
    pi = jnp.asarray(perm[: n // 2])
    pj = jnp.asarray(perm[n // 2: 2 * (n // 2)])
    theta = jax.random.normal(jax.random.fold_in(key, 1), (n // 2,))
    got = ops.apply_pair_rotations(X, pi, pj, theta)
    want = givens.apply_pair_rotations(X, pi, pj, theta)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("n", [32, 128, 384, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gcd_score_sweep(n, dtype):
    key = jax.random.PRNGKey(n)
    G = jax.random.normal(key, (n, n)).astype(dtype)
    R = jax.random.normal(jax.random.fold_in(key, 1), (n, n)).astype(dtype)
    got = np.asarray(ops.gcd_score(G, R))
    want = np.asarray(ref.gcd_score_ref(G.astype(jnp.float32),
                                        R.astype(jnp.float32)))
    tol = 1e-3 * n if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(got, want, atol=tol, rtol=1e-2)
    np.testing.assert_allclose(got, -got.T, atol=1e-5)  # antisymmetric


@pytest.mark.parametrize("m,D,K,sub", [(17, 2, 4, 8), (300, 8, 16, 8),
                                       (1024, 4, 256, 16)])
def test_pq_assign_sweep(m, D, K, sub):
    key = jax.random.PRNGKey(m)
    X = jax.random.normal(key, (m, D * sub))
    cb = jax.random.normal(jax.random.fold_in(key, 1), (D, K, sub))
    got = np.asarray(ops.pq_assign(X, cb))
    want = np.asarray(ref.pq_assign_ref(X, cb))
    assert np.array_equal(got, want)


@given(N=st.integers(10, 600), D=st.sampled_from([2, 8]),
       K=st.sampled_from([4, 16]), b=st.integers(1, 5))
@settings(deadline=None, max_examples=12)
def test_adc_lookup_property(N, D, K, b):
    key = jax.random.PRNGKey(N)
    lut = jax.random.normal(key, (b, D, K))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (N, D), 0, K)
    got = np.asarray(ops.adc_lookup(lut, codes))
    want = np.asarray(ref.adc_lookup_ref(lut, codes))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@given(L=st.integers(1, 200), V=st.integers(10, 500),
       dim=st.sampled_from([8, 16]), B=st.integers(1, 20),
       weighted=st.booleans())
@settings(deadline=None, max_examples=12)
def test_embedding_bag_property(L, V, dim, B, weighted):
    rng = np.random.RandomState(L * 7 + V)
    table = jnp.asarray(rng.randn(V, dim).astype(np.float32))
    idx = jnp.asarray(rng.randint(-1, V, size=L).astype(np.int32))
    bags = jnp.asarray(np.sort(rng.randint(0, B, size=L)).astype(np.int32))
    w = jnp.asarray(rng.rand(L).astype(np.float32)) if weighted else None
    got = np.asarray(ops.embedding_bag(table, idx, bags, B, w))
    mask = np.asarray(idx) >= 0
    w_ref = np.where(mask, np.asarray(w) if w is not None else 1.0, 0.0)
    want = np.asarray(ref.embedding_bag_ref(
        table, jnp.maximum(idx, 0), bags, B, jnp.asarray(w_ref)))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_kernel_wrappers_jit_under_transforms():
    """Kernels must compose with jit+grad where gradients are defined."""
    X = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    pi = jnp.arange(8)
    pj = jnp.arange(8, 16)
    theta = 0.1 * jnp.ones((8,))

    # givens rotate is linear in X: grad = rotated cotangent
    def f(x):
        return jnp.sum(ops.apply_pair_rotations(x, pi, pj, theta) ** 2)

    g = jax.jit(jax.grad(f))(X)
    assert bool(jnp.all(jnp.isfinite(g)))


# ---------------------------------------------------------------------------
# PR 7: int8/uint8 LUT packs, rotation-fused LUT build, streaming merge
# ---------------------------------------------------------------------------


def _topk_ids(scores, k=10):
    return np.argsort(-np.asarray(scores), axis=-1)[..., :k]


@pytest.mark.parametrize("dtype", ["int8", "uint8"])
def test_quantize_luts_roundtrip_and_guard(dtype):
    lut = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
    qlut, scales = ops.quantize_luts(lut, dtype)
    assert qlut.dtype == jnp.dtype(dtype)
    assert scales.shape == (4, 8, 2)
    deq = ops.dequantize_luts(qlut, scales)
    # worst-case rounding error is half a quantization step per entry
    step = np.asarray(scales[..., 0])[..., None]
    assert np.all(np.abs(np.asarray(deq - lut)) <= 0.5001 * step + 1e-7)
    # a constant (zero-range) subspace must not divide by zero: the pack
    # dequantizes to the exact constant, not NaN
    const = lut.at[:, 3, :].set(0.0)
    qc, sc = ops.quantize_luts(const, dtype)
    deqc = np.asarray(ops.dequantize_luts(qc, sc))
    assert np.all(np.isfinite(deqc))
    np.testing.assert_allclose(deqc[:, 3, :], 0.0, atol=1e-7)


@pytest.mark.parametrize("dtype", ["int8", "uint8"])
@pytest.mark.parametrize("Dp", [4, 16])   # PQ-ish and RQ-2-ish code widths
def test_adc_lookup_int8_parity(dtype, Dp):
    """Quantized flat scan: kernel == ref on the same pack, and the top-k
    order stays monotone vs the f32 scores (same LUT, coarser steps)."""
    key = jax.random.PRNGKey(Dp)
    lut = jax.random.normal(key, (4, Dp, 16))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (256, Dp), 0, 16)
    qlut, scales = ops.quantize_luts(lut, dtype)
    got = np.asarray(ops.adc_lookup(qlut, codes, scales))
    want = np.asarray(ref.adc_lookup_ref(qlut, codes, scales))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    f32 = np.asarray(ops.adc_lookup(lut, codes))
    # quantization error bound: Dp columns × half-step each
    bound = Dp * 0.5001 * float(np.max(np.asarray(scales[..., 0]))) + 1e-5
    assert np.max(np.abs(got - f32)) <= bound
    # top-10 agreement within the error bound (monotone order preserved
    # wherever score gaps exceed the bound)
    agree = np.mean([len(set(a) & set(b)) / 10 for a, b in
                     zip(_topk_ids(got), _topk_ids(f32))])
    assert agree >= 0.8


def _hole_rows(block_idx, hole_block, bs):
    """(S, bs) mask of the rows of steps scheduled on ``hole_block``."""
    hole = np.asarray(block_idx) == hole_block
    return np.broadcast_to(hole[:, None], (hole.size, bs))


@pytest.mark.parametrize("holes", [False, True])
@pytest.mark.parametrize("dtype", ["int8", "uint8"])
def test_ivf_adc_int8_parity(dtype, holes):
    """Quantized probed scan: kernel == ref on the same pack; with
    ``holes`` every third step sits on the skipped all-hole block."""
    key = jax.random.PRNGKey(3)
    b, D, K, bs, nblocks = 3, 8, 16, 8, 12
    lut = jax.random.normal(key, (b, D, K))
    codes = jax.random.randint(jax.random.fold_in(key, 1),
                               (bs * nblocks, D), 0, K)
    block_idx = jnp.arange(nblocks, dtype=jnp.int32)[::-1]
    block_query = jnp.asarray(np.resize(np.arange(b), nblocks), jnp.int32)
    hole_block = nblocks - 1 if holes else None
    if holes:
        block_idx = jnp.where(jnp.arange(nblocks) % 3 == 1, hole_block,
                              block_idx)
    qlut, scales = ops.quantize_luts(lut, dtype)
    got = np.asarray(ops.ivf_adc(qlut, codes, block_idx, block_query,
                                 scales, block_size=bs,
                                 hole_block=hole_block))
    want = np.asarray(ref.ivf_adc_ref(qlut, codes, block_idx, block_query,
                                      block_size=bs, scales=scales,
                                      hole_block=hole_block))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    if holes:
        hole = _hole_rows(block_idx, hole_block, bs)
        assert np.all(np.isneginf(got[hole]))
        plain = np.asarray(ops.ivf_adc(qlut, codes, block_idx, block_query,
                                       scales, block_size=bs))
        np.testing.assert_array_equal(got[~hole], plain[~hole])


@pytest.mark.parametrize("dtype", ["int8", "uint8"])
def test_adc_batch_int8_parity(dtype):
    """Quantized grouped (KV-cache) scan: kernel == ref on the same pack."""
    key = jax.random.PRNGKey(5)
    g, r, Dp, K, S = 2, 3, 4, 16, 64
    lut = jax.random.normal(key, (g, r, Dp, K))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (g, S, Dp), 0, K)
    qlut, scales = ops.quantize_luts(lut, dtype)
    got = np.asarray(ops.adc_batch(qlut, codes, scales))
    want = np.asarray(ref.adc_batch_ref(qlut, codes, scales))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    f32 = np.asarray(ops.adc_batch(lut, codes))
    bound = Dp * 0.5001 * float(np.max(np.asarray(scales[..., 0]))) + 1e-5
    assert np.max(np.abs(got - f32)) <= bound


@pytest.mark.parametrize("b,n,D,K,sub", [(3, 16, 4, 8, 4),   # PQ identity
                                         (17, 32, 8, 16, 4)])
def test_fused_lut_pq_kernel_matches_ref(b, n, D, K, sub):
    key = jax.random.PRNGKey(b)
    Q = jax.random.normal(key, (b, n))
    qdelta = jax.random.normal(jax.random.fold_in(key, 1), (n, n))
    cb = jax.random.normal(jax.random.fold_in(key, 2), (D, K, sub))
    colmap = jnp.eye(D, dtype=jnp.float32)
    got = np.asarray(ops.fused_lut(Q, qdelta, cb, colmap))
    want = np.asarray(ref.fused_lut_ref(Q, qdelta, cb, colmap))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # and the ref itself equals the unfused two-step build
    QL = np.asarray(Q @ qdelta).reshape(b, D, sub)
    direct = np.einsum("bds,dks->bdk", QL, np.asarray(cb))
    np.testing.assert_allclose(want, direct, atol=1e-4, rtol=1e-4)


def test_fused_lut_rq_colmap():
    """Depth-2 RQ level-major columns: column l·D+d reads query subspace d
    through the one-hot colmap — both levels score the same subspace."""
    key = jax.random.PRNGKey(9)
    b, n, D, K, M = 5, 16, 4, 8, 2
    sub = n // D
    Q = jax.random.normal(key, (b, n))
    qdelta = jax.random.normal(jax.random.fold_in(key, 1), (n, n))
    cb = jax.random.normal(jax.random.fold_in(key, 2), (M * D, K, sub))
    cols = np.arange(M * D)
    colmap = jnp.asarray(np.eye(D, dtype=np.float32)[cols % D])
    got = np.asarray(ops.fused_lut(Q, qdelta, cb, colmap))
    want = np.asarray(ref.fused_lut_ref(Q, qdelta, cb, colmap))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    QL = np.asarray(Q @ qdelta).reshape(b, D, sub)
    for p in range(M * D):
        direct = np.einsum("bs,ks->bk", QL[:, p % D], np.asarray(cb[p]))
        np.testing.assert_allclose(want[:, p], direct, atol=1e-4, rtol=1e-4)


def test_topk_merge_deterministic_ties():
    """Equal scores rank by ascending id, so the merged top-k is a pure
    function of the candidate SET — identical under any permutation of the
    candidate axis (the serve batch-composition determinism contract)."""
    rng = np.random.RandomState(7)
    b, C, k = 4, 24, 8
    # heavy ties: scores drawn from 4 distinct values
    scores = jnp.asarray(
        rng.choice([3.0, 2.0, 1.0, -np.inf], size=(b, C)).astype(np.float32))
    ids = jnp.asarray(rng.permutation(C).astype(np.int32)[None, :]
                      .repeat(b, axis=0))
    ids = jnp.where(jnp.isfinite(scores), ids, -1)   # padding contract
    want_s, want_i = ops.topk_merge(scores, ids, k)
    # within every tied score run, ids must come out ascending
    ws, wi = np.asarray(want_s), np.asarray(want_i)
    for r in range(b):
        for v in (3.0, 2.0, 1.0):
            run = wi[r][ws[r] == v]
            assert list(run) == sorted(run), (r, v, run)
    # permutation invariance: merging the same candidates in any order
    # yields the bit-identical result
    for trial in range(5):
        perm = rng.permutation(C)
        got_s, got_i = ops.topk_merge(scores[:, perm], ids[:, perm], k)
        np.testing.assert_array_equal(np.asarray(got_i), wi)
        np.testing.assert_array_equal(np.asarray(got_s), ws)
    # −inf slots surface only when the pool runs dry, always with id −1
    empty_s, empty_i = ops.topk_merge(
        jnp.full((2, 3), -jnp.inf), jnp.full((2, 3), -1, jnp.int32), k)
    assert np.all(np.asarray(empty_s) == -np.inf)
    assert np.all(np.asarray(empty_i) == -1)


def test_streaming_topk_ref_tile_order_invariance():
    """The streamed merge is bit-identical to a one-shot top-k over the
    concatenated scores, whatever order the tiles arrive in."""
    rng = np.random.RandomState(0)
    b, T, t, k = 4, 6, 32, 10
    scores = jnp.asarray(rng.randn(T, b, t).astype(np.float32))
    ids = jnp.asarray(
        np.arange(T * t, dtype=np.int32).reshape(T, t))
    ids = ids.at[-1, -5:].set(-1)                 # padding rows in last tile
    want_s, want_i = ref.streaming_topk_ref(scores, ids, k)
    flat = np.concatenate([np.asarray(scores[i]) for i in range(T)], axis=1)
    flat_ids = np.concatenate([np.asarray(ids[i]) for i in range(T)])
    flat[:, flat_ids < 0] = -np.inf
    order = np.argsort(-flat, axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(want_i),
                                  flat_ids[order])
    np.testing.assert_array_equal(np.asarray(want_s),
                                  np.take_along_axis(flat, order, axis=1))
    # permute the tiles: same result set (ties broken by id order here
    # because all scores are distinct floats)
    perm = rng.permutation(T)
    got_s, got_i = ref.streaming_topk_ref(scores[perm], ids[perm], k)
    np.testing.assert_array_equal(np.sort(np.asarray(got_i)),
                                  np.sort(np.asarray(want_i)))
    np.testing.assert_allclose(np.sort(np.asarray(got_s)),
                               np.sort(np.asarray(want_s)))


# ---------------------------------------------------------------------------
# PR 8: in-kernel tombstone masks (repro.churn deletes)
# ---------------------------------------------------------------------------


@given(N=st.integers(10, 400), D=st.sampled_from([2, 8]),
       K=st.sampled_from([4, 16]), b=st.integers(1, 4),
       quantized=st.booleans())
@settings(deadline=None, max_examples=12)
def test_adc_lookup_mask_property(N, D, K, b, quantized):
    """Masked flat scan: kernel == ref, masked rows exactly −inf, live rows
    bit-equal to the unmasked scan (the mask must not perturb live scores)."""
    key = jax.random.PRNGKey(N * 31 + D)
    lut = jax.random.normal(key, (b, D, K))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (N, D), 0, K)
    ids = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(key, 2), 0.3, (N,)),
        -1, jnp.arange(N, dtype=jnp.int32))
    scales = None
    if quantized:
        lut, scales = ops.quantize_luts(lut, "int8")
    got = np.asarray(ops.adc_lookup(lut, codes, scales, ids))
    want = np.asarray(ref.adc_lookup_ref(lut, codes, scales, ids))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    dead = np.asarray(ids) < 0
    assert np.all(np.isneginf(got[:, dead]))
    plain = np.asarray(ops.adc_lookup(lut, codes, scales))
    np.testing.assert_array_equal(got[:, ~dead], plain[:, ~dead])


@pytest.mark.parametrize("holes", [False, True])
@given(nblocks=st.integers(2, 16), bs=st.sampled_from([8, 16]),
       b=st.integers(1, 4), quantized=st.booleans())
@settings(deadline=None, max_examples=12)
def test_ivf_adc_mask_property(holes, nblocks, bs, b, quantized):
    """Masked probed scan: the ids operand rides the same block_idx
    prefetch as the codes tile — kernel == ref, masked rows −inf, live
    rows bit-equal to the unmasked scan. With ``holes`` every other step
    sits on the skipped all-hole block, whose rows are −inf too."""
    D, K = 4, 16
    key = jax.random.PRNGKey(nblocks * 17 + bs)
    lut = jax.random.normal(key, (b, D, K))
    cap = bs * nblocks
    codes = jax.random.randint(jax.random.fold_in(key, 1), (cap, D), 0, K)
    ids = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(key, 2), 0.3, (cap,)),
        -1, jnp.arange(cap, dtype=jnp.int32))
    block_idx = jnp.asarray(
        np.random.RandomState(nblocks).permutation(nblocks), jnp.int32)
    block_query = jnp.asarray(np.resize(np.arange(b), nblocks), jnp.int32)
    hole_block = nblocks - 1 if holes else None
    if holes:
        block_idx = jnp.where(jnp.arange(nblocks) % 2 == 1, hole_block,
                              block_idx)
    scales = None
    if quantized:
        lut, scales = ops.quantize_luts(lut, "int8")
    got = np.asarray(ops.ivf_adc(lut, codes, block_idx, block_query,
                                 scales, ids, block_size=bs,
                                 hole_block=hole_block))
    want = np.asarray(ref.ivf_adc_ref(lut, codes, block_idx, block_query,
                                      block_size=bs, scales=scales, ids=ids,
                                      hole_block=hole_block))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    rows = (np.asarray(block_idx)[:, None] * bs + np.arange(bs))
    dead = np.asarray(ids)[rows] < 0
    if holes:
        dead = dead | _hole_rows(block_idx, hole_block, bs)
    assert np.all(np.isneginf(got[dead]))
    plain = np.asarray(ops.ivf_adc(lut, codes, block_idx, block_query,
                                   scales, block_size=bs))
    np.testing.assert_array_equal(got[~dead], plain[~dead])

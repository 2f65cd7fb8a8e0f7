"""IVF-PQ serving subsystem (repro.index + kernels/ivf_adc).

Coverage demanded by ISSUE 1:
  * search with nprobe = num_lists matches the flat ADC scan exactly;
  * the Pallas ivf_adc kernel (interpret mode) matches the jnp reference;
  * refresh_rotation matches a from-scratch re-encode (exact for
    within-subspace GCD steps, ≥99% for small full-matching steps);
plus CSR-layout invariants and add/remove maintenance.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import churn
from repro.core import givens, matching, pq
from repro.data import synthetic
from repro.index import ivf, maintain, search
from repro.kernels import ops, ref

DIM, D, K, L, BS = 16, 4, 16, 8, 8
N = 2000


@pytest.fixture(scope="module")
def index_and_data():
    X = synthetic.sift_like(jax.random.PRNGKey(0), N, DIM)
    R = givens.random_rotation(jax.random.PRNGKey(1), DIM)
    cfg = ivf.IVFPQConfig(num_lists=L, pq=pq.PQConfig(D, K), block_size=BS)
    index = ivf.build(jax.random.PRNGKey(2), X, R, cfg)
    Q = synthetic.sift_like(jax.random.PRNGKey(3), 16, DIM)
    return index, X, Q


def test_pack_csr_invariants(index_and_data):
    index, X, _ = index_and_data
    offsets = np.asarray(index.list_offsets)
    ids = np.asarray(index.ids)
    assert offsets[0] == 0
    assert np.all(offsets % BS == 0)
    assert np.all(np.diff(offsets) >= 0)
    assert index.capacity == offsets[-1] + BS  # sentinel hole block
    assert np.all(ids[offsets[-1]:] == -1)
    live = ids[ids >= 0]
    assert sorted(live.tolist()) == list(range(N))  # every item exactly once
    # every live row's code matches a fresh encode of its vector
    XR = X @ index.R
    list_ids, codes = ivf.encode(XR, index.coarse, index.quantizer)
    rows = np.nonzero(ids >= 0)[0]
    np.testing.assert_array_equal(
        np.asarray(index.codes)[rows].astype(np.int32),
        np.asarray(codes)[ids[rows]],
    )
    # rows live in the list their vector was assigned to
    row_list = np.searchsorted(offsets, rows, side="right") - 1
    np.testing.assert_array_equal(row_list, np.asarray(list_ids)[ids[rows]])


def test_search_nprobe_full_matches_flat(index_and_data):
    index, _, Q = index_and_data
    res = search.search(index, Q, nprobe=L, k=10, use_kernel=False)
    flat_scores, flat_ids = search.flat_adc_scores(index, Q)
    want_scores, pos = jax.lax.top_k(flat_scores, 10)
    want_ids = flat_ids[pos]
    np.testing.assert_allclose(
        np.asarray(res.scores), np.asarray(want_scores), rtol=1e-5, atol=1e-5
    )
    # ids agree except possibly on exact score ties
    agree = np.mean(np.asarray(res.ids) == np.asarray(want_ids))
    assert agree >= 0.95
    assert np.all(np.asarray(res.scanned) == index.capacity - BS)


def test_search_kernel_matches_ref(index_and_data):
    index, _, Q = index_and_data
    a = search.search(index, Q, nprobe=3, k=5, use_kernel=True)
    b = search.search(index, Q, nprobe=3, k=5, use_kernel=False)
    np.testing.assert_allclose(
        np.asarray(a.scores), np.asarray(b.scores), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def _assert_hole_steps(got, plain, bi, hole_block):
    """Steps on ``hole_block`` score exactly −inf; every other step is
    bit-equal to the scan without ``hole_block``."""
    hole = np.asarray(bi) == hole_block
    assert hole.any() and (~hole).any()
    assert np.all(np.isneginf(np.asarray(got)[hole]))
    np.testing.assert_array_equal(np.asarray(got)[~hole],
                                  np.asarray(plain)[~hole])


@pytest.mark.parametrize("holes", [False, True])
def test_ivf_adc_kernel_matches_ref(holes):
    """Kernel == ref; with ``holes`` about half the steps sit on the
    all-hole block and are skipped."""
    key = jax.random.PRNGKey(7)
    b, cap, bs, S = 5, 40 * 8, 8, 23
    lut = jax.random.normal(key, (b, D, K))
    codes = jax.random.randint(jax.random.PRNGKey(8), (cap, D), 0, K)
    bi = jax.random.randint(jax.random.PRNGKey(9), (S,), 0, cap // bs)
    bq = jax.random.randint(jax.random.PRNGKey(10), (S,), 0, b)
    hole_block = None
    if holes:
        hole_block = cap // bs - 1
        bi = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(15), 0.5,
                                            (S,)), hole_block, bi)
    got = ops.ivf_adc(lut, codes, bi, bq, block_size=bs,
                      hole_block=hole_block, use_kernel=True)
    want = ref.ivf_adc_ref(lut, codes, bi, bq, block_size=bs,
                           hole_block=hole_block)
    assert got.shape == (S, bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    if holes:
        plain = ops.ivf_adc(lut, codes, bi, bq, block_size=bs,
                            use_kernel=True)
        _assert_hole_steps(got, plain, bi, hole_block)


@pytest.mark.parametrize("holes", [False, True])
def test_ivf_adc_long_schedule_in_pieces(monkeypatch, holes):
    """A schedule longer than one call's SMEM share is scanned in pieces
    (the last one short) and gives the same scores as in one piece; with
    ``holes``, runs of hole steps straddle both piece boundaries."""
    from repro.kernels import ivf_adc as ivf_adc_mod

    monkeypatch.setattr(ivf_adc_mod, "SCHEDULE_STEPS", 8)
    b, cap, bs, S = 3, 24 * 8, 8, 29          # pieces of 8, 8, 8, 5
    lut = jax.random.normal(jax.random.PRNGKey(11), (b, D, K))
    codes = jax.random.randint(jax.random.PRNGKey(12), (cap, D), 0, K)
    bi = jax.random.randint(jax.random.PRNGKey(13), (S,), 0, cap // bs)
    bq = jax.random.randint(jax.random.PRNGKey(14), (S,), 0, b)
    hole_block = None
    if holes:
        hole_block = cap // bs - 1
        bi = bi.at[5:11].set(hole_block).at[14:18].set(hole_block)
    got = ops.ivf_adc(lut, codes, bi, bq, block_size=bs,
                      hole_block=hole_block, use_kernel=True)
    want = ref.ivf_adc_ref(lut, codes, bi, bq, block_size=bs,
                           hole_block=hole_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    if holes:
        plain = ops.ivf_adc(lut, codes, bi, bq, block_size=bs,
                            use_kernel=True)
        _assert_hole_steps(got, plain, bi, hole_block)


def test_search_skewed_lists_skips_hole_steps(index_and_data, monkeypatch):
    """Lists of very different lengths, so most scheduled tiles are the
    sentinel block: the kernel, which skips them, answers as the jnp
    reference does and as the kernel that scores them."""
    index, X, Q = index_and_data
    XR = X @ index.R
    list_ids, codes = ivf.encode(XR, index.coarse, index.quantizer)
    # three items in five crowd into list 0: it spans far more tiles than
    # the others, whose surplus window steps all go to the sentinel block
    crowd = np.random.RandomState(0).rand(N) < 0.6
    skewed = ivf.pack(index.R, index.coarse, index.quantizer, codes,
                      np.where(crowd, 0, np.asarray(list_ids)),
                      jnp.arange(N, dtype=jnp.int32), block_size=BS)
    max_blocks = skewed.max_list_blocks()
    Qs = Q[:4]
    # k past every probed live row: the answer is the whole candidate pool,
    # so a hole row that scored anything but −inf would show in it
    kw = dict(nprobe=4, k=2048, max_blocks=max_blocks)
    got = search.search_fixed(skewed, Qs, use_kernel=True, **kw)
    want = search.search_fixed(skewed, Qs, use_kernel=False, **kw)

    real = ops.ivf_adc

    def scores_hole_steps(*args, hole_block=None, **kwargs):
        return real(*args, **kwargs)

    # a fresh jit of search_fixed's body traces the scan without hole_block
    monkeypatch.setattr(search.kops, "ivf_adc", scores_hole_steps)
    unskipped = jax.jit(functools.partial(
        search.search_fixed.__wrapped__, use_kernel=True, **kw))(skewed, Qs)

    scheduled = Qs.shape[0] * kw["nprobe"] * max_blocks * BS
    assert int(jnp.sum(got.scanned)) < 0.4 * scheduled  # mostly hole steps
    assert np.all(np.asarray(got.scanned) < kw["k"])
    for name in ("scores", "ids"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(unskipped, name)))
    # the reference's sums round differently: near-ties may swap places
    np.testing.assert_array_equal(np.sort(np.asarray(got.ids), axis=1),
                                  np.sort(np.asarray(want.ids), axis=1))
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(want.scores), rtol=1e-5,
                               atol=1e-5)


def test_refresh_subspace_step_is_exact(index_and_data):
    index, X, _ = index_and_data
    G = jax.random.normal(jax.random.PRNGKey(11), (DIM, DIM))
    refreshed, (pi, pj, theta) = maintain.subspace_gcd_step(index, G, 2e-3)
    # delta really moved the rotation, and stayed in SO(n)
    assert float(jnp.max(jnp.abs(refreshed.R - index.R))) > 0
    assert float(givens.orthogonality_error(refreshed.R)) < 1e-4
    sub = DIM // D
    w = np.asarray(pi) // sub == np.asarray(pj) // sub
    np.testing.assert_allclose(np.where(w, 0.0, np.asarray(theta)), 0.0)
    # codes match a full re-encode (fp ties aside) — acceptance: ≥ 99%
    mismatch = float(maintain.refresh_mismatch(refreshed, X))
    assert mismatch <= 0.01


def test_refresh_small_full_step_matches_rebuild(index_and_data):
    index, X, _ = index_and_data

    def loss(Rm):
        return pq.distortion(X @ Rm, index.codebooks)

    G = jax.grad(loss)(index.R)
    A = givens.directional_derivs(G, index.R)
    pi, pj = matching.greedy_matching_fast(A)
    theta = -2e-4 * A[pi, pj] / givens.SQRT2
    refreshed = maintain.refresh_rotation(index, pi, pj, theta)
    assert float(givens.orthogonality_error(refreshed.R)) < 1e-4
    mismatch = float(maintain.refresh_mismatch(refreshed, X))
    assert mismatch <= 0.01  # ≥ 99% of items keep their rebuild codes


def test_refresh_preserves_flat_recall(index_and_data):
    index, X, Q = index_and_data
    G = jax.random.normal(jax.random.PRNGKey(12), (DIM, DIM))
    refreshed, _ = maintain.subspace_gcd_step(index, G, 1e-3)
    a = search.search(index, Q, nprobe=L, k=10, use_kernel=False)
    b = search.search(refreshed, Q, nprobe=L, k=10, use_kernel=False)
    # scores are rotation-invariant inner products — refresh must not move them
    np.testing.assert_allclose(
        np.asarray(a.scores), np.asarray(b.scores), rtol=1e-4, atol=1e-4
    )


def test_search_k_exceeding_candidate_pool_pads(index_and_data):
    index, _, Q = index_and_data
    res = search.search(index, Q, nprobe=1, k=10_000, use_kernel=False)
    assert res.ids.shape == (Q.shape[0], 10_000)
    ids = np.asarray(res.ids)
    scores = np.asarray(res.scores)
    assert np.all(np.isfinite(scores[ids >= 0]))
    assert np.all(np.isneginf(scores[ids < 0]))
    # nprobe beyond num_lists clamps instead of crashing
    res2 = search.search(index, Q, nprobe=10 * L, k=5, use_kernel=False)
    assert res2.ids.shape == (Q.shape[0], 5)


def test_remove_tombstones_and_masks(index_and_data):
    index, _, Q = index_and_data
    dead = jnp.arange(50, dtype=jnp.int32)
    idx2 = churn.tombstone_index(index, dead)
    assert int(index.num_items()) - int(idx2.num_items()) == 50
    res = search.search(idx2, Q, nprobe=L, k=10, use_kernel=False)
    assert not np.any(np.isin(np.asarray(res.ids), np.asarray(dead)))


def test_add_fills_holes_then_repacks(index_and_data):
    index, _, _ = index_and_data
    idx2 = churn.tombstone_index(index, jnp.arange(100, dtype=jnp.int32))
    Xn = synthetic.sift_like(jax.random.PRNGKey(13), 60, DIM)
    new_ids = jnp.arange(N, N + 60, dtype=jnp.int32)
    idx3 = churn.ingest_index(idx2, Xn, new_ids)
    assert int(idx3.num_items()) == N - 100 + 60
    # new items are findable and correctly encoded
    XR = Xn @ idx3.R
    list_ids, codes = ivf.encode(XR, idx3.coarse, idx3.quantizer)
    ids_np = np.asarray(idx3.ids)
    for i in (0, 17, 59):
        rows = np.nonzero(ids_np == N + i)[0]
        assert len(rows) == 1
        np.testing.assert_array_equal(
            np.asarray(idx3.codes)[rows[0]].astype(np.int32),
            np.asarray(codes)[i],
        )
    # force the overflow/repack path: add more than the holes can absorb
    Xbig = synthetic.sift_like(jax.random.PRNGKey(14), 500, DIM)
    idx4 = churn.ingest_index(idx3, Xbig,
                              jnp.arange(10_000, 10_500, dtype=jnp.int32))
    assert int(idx4.num_items()) == int(idx3.num_items()) + 500
    offsets = np.asarray(idx4.list_offsets)
    assert np.all(offsets % BS == 0)


def test_index_is_jit_traceable_pytree(index_and_data):
    index, _, Q = index_and_data
    leaves, treedef = jax.tree_util.tree_flatten(index)
    assert all(hasattr(leaf, "shape") for leaf in leaves)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.block_size == index.block_size

    @jax.jit
    def serve(ix, qb):
        return search.search_fixed(
            ix, qb, nprobe=2, k=5,
            max_blocks=index.max_list_blocks(), use_kernel=False
        ).scores

    out = serve(index, Q)
    assert out.shape == (Q.shape[0], 5)

"""repro.search — Searcher registry conformance + Engine serving tests.

Coverage demanded by ISSUE 4:
  * one shared conformance suite over every registered backend
    (build / search / refresh / stats);
  * backend parity: ``ivf`` at nprobe = num_lists returns the flat_adc
    top-k over the same codes, and ``exact`` beats both on recall@10;
  * the SearchResult padding contract when k exceeds the candidate pool
    (ids −1, scores −inf, recall_at_k ignores padding);
  * Engine: ragged batches match direct search, at most one compile per
    (bucket, k, nprobe), per-query LUT cache hits, live refresh between
    batches without recompiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rotations, search
from repro.data import synthetic
from repro.metrics import recall_at_k

DIM, SUB, K, L, BS = 16, 4, 16, 8, 8
N, B = 2000, 16
CFG = search.SearchConfig(num_lists=L, subspaces=SUB, codewords=K,
                          block_size=BS, nprobe=4, tile_rows=256)


@pytest.fixture(scope="module")
def data():
    X = synthetic.sift_like(jax.random.PRNGKey(0), N, DIM)
    R = rotations.random_rotation(jax.random.PRNGKey(1), DIM)
    Q = synthetic.sift_like(jax.random.PRNGKey(2), B, DIM)
    truth = np.argsort(-np.asarray(Q @ X.T), axis=1)[:, :10]
    return X, R, Q, truth


@pytest.fixture(scope="module")
def states(data):
    """One state per backend; flat_adc attached to the ivf build so both
    serve the identical codes. The sharded twins attach the same artifacts
    on the local data mesh (S = 1 in-process; the 8-fake-device parity runs
    live in tests/test_distributed.py)."""
    from repro.launch.mesh import make_data_mesh

    X, R, Q, _ = data
    mesh = make_data_mesh()
    ivf_state = search.make("ivf").build(jax.random.PRNGKey(3), X, R, CFG)
    return {
        "exact": search.make("exact").build(jax.random.PRNGKey(3), X, R, CFG),
        "exact_stream": search.make("exact_stream").build(
            jax.random.PRNGKey(3), X, R, CFG),
        "flat_adc": search.FlatADC.attach(ivf_state.index),
        "ivf": ivf_state,
        "exact_sharded": search.make("exact_sharded", mesh=mesh).build(
            jax.random.PRNGKey(3), X, R, CFG),
        "flat_sharded": search.FlatSharded.attach(ivf_state.index, mesh=mesh),
        "ivf_sharded": search.IVFSharded.attach(ivf_state.index, mesh=mesh,
                                                nprobe=CFG.nprobe),
    }


def _delta(R, key=0, lr=1e-3):
    """A genuine subspace-GCD RotationDelta (what a training step emits)."""
    G = jax.random.normal(jax.random.PRNGKey(100 + key), (DIM, DIM))
    learner = rotations.make("subspace_gcd", sub=DIM // SUB)
    _, delta = learner.update(learner.init_from(R), G, lr,
                              jax.random.PRNGKey(key))
    return delta


# ---------------------------------------------------------------------------
# Shared conformance suite
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", search.names())
def test_conformance_build_and_search(backend, data, states):
    _, _, Q, _ = data
    searcher = search.make(backend)
    res = searcher.search(states[backend], Q, k=10)
    assert res.scores.shape == (B, 10) and res.ids.shape == (B, 10)
    assert res.scanned.shape == (B,)
    scores = np.asarray(res.scores)
    ids = np.asarray(res.ids)
    assert np.all(np.diff(scores, axis=1) <= 1e-6)        # descending
    assert np.all((ids >= -1) & (ids < N))
    assert np.all(np.isfinite(scores[ids >= 0]))
    assert np.all(np.asarray(res.scanned) > 0)


@pytest.mark.parametrize("backend", search.names())
def test_conformance_refresh(backend, data, states):
    _, R, Q, _ = data
    searcher = search.make(backend)
    state = states[backend]
    before = searcher.search(state, Q, k=10)

    # identity delta: a no-op refresh must not move results
    ident = searcher.refresh(state, rotations.identity_delta())
    after = searcher.search(ident, Q, k=10)
    np.testing.assert_allclose(np.asarray(before.scores),
                               np.asarray(after.scores), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))

    # a genuine learner delta: state stays servable, rotation really moved,
    # and scores (rotation-invariant inner products) stay put
    moved = searcher.refresh(state, _delta(R))
    res = searcher.search(moved, Q, k=10)
    np.testing.assert_allclose(np.asarray(before.scores),
                               np.asarray(res.scores), rtol=1e-4, atol=1e-4)
    new_R = moved.R if hasattr(moved, "R") else moved.index.R
    old_R = state.R if hasattr(state, "R") else state.index.R
    assert float(jnp.max(jnp.abs(new_R - old_R))) > 0
    assert float(rotations.orthogonality_error(new_R)) < 1e-4


@pytest.mark.parametrize("backend", search.names())
def test_conformance_stats(backend, states):
    st = search.make(backend).stats(states[backend])
    assert st["backend"] == backend
    assert st["rows"] == N
    assert st["scan_rows_per_query"] > 0
    assert st["memory_bytes"] > 0
    assert st["compression"] >= 1.0


def test_registry_make_and_aliases():
    assert set(search.names()) == {"exact", "exact_stream", "flat_adc",
                                   "ivf", "exact_sharded", "flat_sharded",
                                   "ivf_sharded"}
    assert isinstance(search.make("flat"), search.FlatADC)
    assert isinstance(search.make("bruteforce"), search.Exact)
    assert isinstance(search.make("streaming"), search.ExactStreaming)
    assert isinstance(search.make("exact_streaming"), search.ExactStreaming)
    assert isinstance(search.make("sharded"), search.IVFSharded)
    assert isinstance(search.make("flat_adc_sharded"), search.FlatSharded)
    with pytest.raises(ValueError, match="unknown search backend"):
        search.make("faiss")


# ---------------------------------------------------------------------------
# Backend parity (ISSUE 4 regression)
# ---------------------------------------------------------------------------


def test_ivf_full_probe_matches_flat_adc(data, states):
    _, _, Q, _ = data
    a = search.make("ivf").search(states["ivf"], Q, k=10, nprobe=L)
    b = search.make("flat_adc").search(states["flat_adc"], Q, k=10)
    np.testing.assert_allclose(np.asarray(a.scores), np.asarray(b.scores),
                               rtol=1e-5, atol=1e-5)
    # ids agree except possibly on exact score ties
    assert np.mean(np.asarray(a.ids) == np.asarray(b.ids)) >= 0.95
    # and the flat backend scans strictly more rows
    assert np.all(np.asarray(b.scanned) >= np.asarray(a.scanned))


def test_sharded_twins_match_replicated_backends(data, states):
    """Each ``*_sharded`` backend serves the same artifacts as its
    replicated twin, so scores/ids must agree (S = 1 here; the 8-device
    parity including cross-shard merge lives in test_distributed.py)."""
    _, _, Q, _ = data
    for sharded, single in (("exact_sharded", "exact"),
                            ("flat_sharded", "flat_adc"),
                            ("ivf_sharded", "ivf")):
        a = search.make(sharded).search(states[sharded], Q, k=10)
        b = search.make(single).search(states[single], Q, k=10)
        np.testing.assert_allclose(np.asarray(a.scores),
                                   np.asarray(b.scores), rtol=1e-5,
                                   atol=1e-5)
        assert np.mean(np.asarray(a.ids) == np.asarray(b.ids)) >= 0.95, sharded


def test_exact_beats_quantized_on_recall(data, states):
    _, _, Q, truth = data
    recalls = {}
    for backend in search.names():
        res = search.make(backend).search(states[backend], Q, k=10)
        recalls[backend] = recall_at_k(np.asarray(res.ids), truth)
    assert recalls["exact"] >= 0.999          # brute force IS the truth
    assert recalls["exact"] >= recalls["flat_adc"]
    assert recalls["exact"] >= recalls["ivf"]
    # probing can only lose candidates the flat scan keeps (tolerance for
    # chance overlap with the ground truth on what both get wrong)
    assert recalls["flat_adc"] >= recalls["ivf"] - 0.05


# ---------------------------------------------------------------------------
# Padding contract: k > candidate pool
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", search.names())
def test_padding_when_k_exceeds_candidates(backend):
    n_small, k = 12, 32
    X = synthetic.sift_like(jax.random.PRNGKey(5), n_small, DIM)
    R = rotations.random_rotation(jax.random.PRNGKey(6), DIM)
    Q = synthetic.sift_like(jax.random.PRNGKey(7), 4, DIM)
    cfg = CFG._replace(num_lists=2, codewords=8, nprobe=1, tile_rows=8)
    searcher = search.make(backend)
    state = searcher.build(jax.random.PRNGKey(8), X, R, cfg)
    res = searcher.search(state, Q, k=k)
    ids = np.asarray(res.ids)
    scores = np.asarray(res.scores)
    assert ids.shape == (4, k)
    assert np.all(ids[:, n_small:] == -1)          # pool is at most n_small
    assert np.all(np.isneginf(scores[ids < 0]))    # padding scores −inf
    assert np.all(np.isfinite(scores[ids >= 0]))
    # downstream recall ignores the padding rows entirely
    truth = np.argsort(-np.asarray(Q @ X.T), axis=1)[:, :10]
    rec = recall_at_k(ids, truth)
    assert 0.0 <= rec <= 1.0
    if backend == "exact":
        assert rec == 1.0


@pytest.mark.parametrize("backend", search.names())
def test_padding_when_deletes_shrink_pool_below_k(backend, data, states):
    """Live deletes can shrink the pool below k on ANY backend: the result
    must pad with (−1, −inf) past the live count — exactly the k > pool
    contract — and never surface a tombstoned id."""
    from repro import churn

    _, _, Q, _ = data
    k, live = 10, 6                       # tombstone down to live < k
    dead = np.arange(N - live, dtype=np.int32)
    state = churn.tombstone(states[backend], dead)
    # full probe on the ivf pair so "every survivor served" is scan-
    # complete (narrow probes may legitimately miss survivors' lists)
    kw = {"nprobe": L} if backend.startswith("ivf") else {}
    res = search.make(backend).search(state, Q, k=k, **kw)
    ids = np.asarray(res.ids)
    scores = np.asarray(res.scores)
    assert ids.shape == (B, k)
    assert not np.any(np.isin(ids, dead))              # no tombstone leaks
    assert np.all((ids == -1) | (ids >= N - live))
    assert np.all((ids == -1) == np.isneginf(scores))  # pad pairs up
    assert np.all(np.isfinite(scores[ids >= 0]))
    assert np.all((ids >= 0).sum(axis=1) == live)      # all survivors served


def test_direct_adcstate_construction_searches_exactly(data, states):
    """ADCState(index=...) without attach must derive the probe window from
    the index, not silently truncate probed lists to one block."""
    _, _, Q, _ = data
    searcher = search.make("ivf")
    bare = search.ADCState(index=states["ivf"].index, nprobe=L)
    want = searcher.search(states["ivf"], Q, k=10, nprobe=L)
    got = searcher.search(bare, Q, k=10)
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(want.scores), rtol=1e-5, atol=1e-5)
    assert searcher.stats(bare)["max_blocks"] >= 1
    # and behind the Engine too: the state is normalized before it is ever
    # passed as a traced jit argument (regression: TracerArrayConversionError)
    engine = search.Engine(searcher, bare, k=10, nprobe=L, min_bucket=4)
    eres = engine.search(np.asarray(Q)[:8])
    np.testing.assert_allclose(np.asarray(eres.scores),
                               np.asarray(want.scores)[:8], rtol=1e-5,
                               atol=1e-5)


def test_shard_split_balances_sparse_ids(data, states):
    """shard_split partitions by id rank, so sparse/custom id spaces
    (build(ids=...), maintain.add) still split evenly instead of
    collapsing onto shard 0."""
    from repro.index import ivf as index_ivf

    X, R, _, _ = data
    sparse_ids = jnp.arange(N, dtype=jnp.int32) * 9973 + 5  # sparse, ragged
    index = index_ivf.build(jax.random.PRNGKey(3), X, R, CFG.ivf_config(),
                            ids=sparse_ids, train_size=512)
    parts = index_ivf.shard_split(index, 4)
    counts = [int(np.sum(np.asarray(p.ids) >= 0)) for p in parts]
    assert sum(counts) == N
    assert max(counts) - min(counts) <= 1, counts
    # and ids are preserved, not remapped
    got = np.sort(np.concatenate(
        [np.asarray(p.ids)[np.asarray(p.ids) >= 0] for p in parts]))
    np.testing.assert_array_equal(got, np.sort(np.asarray(sparse_ids)))


def test_direct_sharded_adcstate_prepared_path(data, states):
    """A directly-constructed ShardedADCState (max_blocks −1) must serve
    through search_prepared too, deriving the probe window like the
    replicated twin does."""
    _, _, Q, _ = data
    src = states["ivf_sharded"]
    bare = search.ShardedADCState(
        R=src.R, coarse=src.coarse, quantizer=src.quantizer,
        codes=src.codes, ids=src.ids, list_offsets=src.list_offsets,
        mesh=src.mesh, block_size=src.block_size, nprobe=L, axes=src.axes)
    assert bare.max_blocks == -1
    searcher = search.make("ivf_sharded")
    QR = searcher.rotate_queries(bare, Q)
    got = searcher.search_prepared(bare, QR, searcher.luts(bare, QR), k=10)
    want = searcher.search(states["ivf_sharded"], Q, k=10, nprobe=L)
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(want.scores), rtol=1e-5, atol=1e-5)


def test_flat_single_list_build(data):
    """num_lists=1 (the pure flat scan quickstart/gnn use) builds/serves."""
    X, R, Q, truth = data
    cfg = CFG._replace(num_lists=1)
    searcher = search.make("flat_adc")
    state = searcher.build(jax.random.PRNGKey(9), X, R, cfg)
    res = searcher.search(state, Q, k=10)
    assert recall_at_k(np.asarray(res.ids), truth) > 0.1


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def test_engine_matches_direct_search(data, states):
    _, _, Q, _ = data
    searcher = search.make("ivf")
    engine = search.Engine(searcher, states["ivf"], k=10, nprobe=4,
                           min_bucket=4)
    for b in (3, 7, 16):
        got = engine.search(np.asarray(Q)[:b])
        want = searcher.search(states["ivf"], Q[:b], k=10, nprobe=4)
        np.testing.assert_allclose(np.asarray(got.scores),
                                   np.asarray(want.scores), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got.ids),
                                      np.asarray(want.ids))


def test_engine_compiles_once_per_bucket_k_nprobe(data, states):
    _, _, Q, _ = data
    Qnp = np.asarray(Q)
    engine = search.Engine(search.make("ivf"), states["ivf"], k=10, nprobe=4,
                           min_bucket=4)
    for b in (3, 4, 7, 3):                 # buckets {4, 8}
        engine.search(Qnp[:b])
    assert engine.stats()["compiles"] == 2
    engine.search(Qnp[:3], k=5)            # new k -> one more
    engine.search(Qnp[:3], nprobe=L)       # new nprobe -> one more
    assert engine.stats()["compiles"] == 4
    for b in (3, 4, 7):                    # all warm now
        engine.search(Qnp[:b])
    engine.search(Qnp[:3], k=5)
    st = engine.stats()
    assert st["compiles"] == 4
    assert st["executables"] == 4
    assert st["requests"] == 10
    # oversized nprobe clamps to num_lists BEFORE keying the cache: both
    # requests share the nprobe=L executable compiled above
    engine.search(Qnp[:3], nprobe=10 * L)
    engine.search(Qnp[:3], nprobe=20 * L)
    st = engine.stats()
    assert st["compiles"] == 4
    assert engine.requests[-1]["nprobe"] == L   # records what was probed


def test_engine_lut_cache_hits_repeated_queries(data, states):
    _, _, Q, _ = data
    Qnp = np.asarray(Q)
    engine = search.Engine(search.make("flat_adc"), states["flat_adc"], k=10,
                           min_bucket=4)
    engine.search(Qnp[:8])
    st = engine.stats()
    assert st["lut_misses"] == 8 and st["lut_hits"] == 0
    engine.search(Qnp[:8])                 # same queries: all cached
    st = engine.stats()
    assert st["lut_hits"] == 8 and st["lut_misses"] == 8
    engine.search(Qnp[4:12])               # half cached
    st = engine.stats()
    assert st["lut_hits"] == 12 and st["lut_misses"] == 12
    # duplicate rows in one batch: counted per served row, computed once
    dup = np.stack([Qnp[14], Qnp[14], Qnp[14]])
    engine.search(dup)
    st = engine.stats()
    assert st["lut_hits"] == 12 and st["lut_misses"] == 15
    assert st["lut_cached_rows"] == 13      # one entry for the triplicate
    engine.search(dup)                     # now fully cached
    assert engine.stats()["lut_hits"] == 15


def test_engine_lut_eviction_under_pressure(data, states):
    """A full LRU must never evict rows the in-flight batch still needs:
    batches wider than the cache and steady-state hit/miss mixes both
    assemble (regression for read-after-evict KeyError)."""
    _, _, Q, _ = data
    Qnp = np.asarray(Q)
    engine = search.Engine(search.make("flat_adc"), states["flat_adc"], k=10,
                           min_bucket=4, lut_cache_rows=4)
    engine.search(Qnp[:8])                  # batch wider than the cache
    assert engine.stats()["lut_cached_rows"] == 4
    engine.search(Qnp[4:8])                 # hits on the survivors
    assert engine.stats()["lut_hits"] == 4
    engine.search(Qnp[2:7])                 # mixed: hits + evicting misses
    res = engine.search(Qnp)                # full batch, 4x the cache
    assert res.ids.shape == (B, 10)
    st = engine.stats()
    assert st["lut_cached_rows"] == 4
    assert st["lut_hits"] == 4 + 3 + 4      # 4,5,6 then 2,3,5,6 survivors


def test_engine_live_refresh_between_batches(data, states):
    _, R, Q, _ = data
    Qnp = np.asarray(Q)
    engine = search.Engine(search.make("ivf"), states["ivf"], k=10, nprobe=4,
                           min_bucket=4)
    before = engine.search(Qnp[:8])
    compiles = engine.stats()["compiles"]

    engine.refresh(_delta(R))
    after = engine.search(Qnp[:8])
    st = engine.stats()
    assert st["refreshes"] == 1
    assert st["compiles"] == compiles       # zero recompiles across refresh
    assert st["lut_misses"] == 16           # LUT cache invalidated (R moved)
    # scores are rotation-invariant; the refreshed engine still serves them
    np.testing.assert_allclose(np.asarray(before.scores),
                               np.asarray(after.scores), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nprobe", [None, 2, L])
def test_engine_counts_tile_and_scheduled_rows(data, states, nprobe):
    """``tile_rows``: the rows of the real list tiles each query's scan read
    (the probed lists' padded lengths); ``scheduled_rows``: every row the
    scan was scheduled to read (nprobe × max_blocks whole tiles a query,
    sentinel hole tiles included). No refresh record is kept as a span."""
    _, R, Q, _ = data
    state = states["ivf"]
    ix = state.index
    engine = search.Engine(search.make("ivf"), state, k=10, nprobe=4,
                           min_bucket=4)
    Qnp = np.asarray(Q)[:5]
    engine.search(Qnp, nprobe=nprobe)
    npb = 4 if nprobe is None else nprobe
    sizes = np.diff(np.asarray(ix.list_offsets))
    coarse = (Qnp @ np.asarray(ix.R)) @ np.asarray(ix.centroids).T
    probed = np.argsort(-coarse, axis=1)[:, :npb]
    st = engine.stats()
    assert st["tile_rows"] == int(sizes[probed].sum())
    assert st["scheduled_rows"] == 5 * npb * state.max_blocks * BS
    assert st["tile_rows"] <= st["scheduled_rows"]
    engine.refresh(_delta(R))
    assert not any(name.startswith("span.") for name in
                   engine.obs.snapshot()["distributions"])


def test_engine_counts_scheduled_rows_per_backend(data, states):
    """The sharded twin schedules its window on every shard; a backend
    without a tile schedule (exact) counts scanned rows only."""
    _, _, Q, _ = data
    Qnp = np.asarray(Q)[:6]
    st_sh = states["ivf_sharded"]
    sharded = search.Engine(search.make("ivf_sharded"), st_sh, k=10,
                            nprobe=4, min_bucket=4)
    res = sharded.search(Qnp)
    got = sharded.stats()
    assert got["tile_rows"] == int(np.sum(np.asarray(res.scanned)))
    assert got["scheduled_rows"] == (6 * 4 * st_sh.max_blocks
                                     * st_sh.block_size * st_sh.num_shards)
    assert got["tile_rows"] <= got["scheduled_rows"]
    exact = search.Engine(search.make("exact"), states["exact"], k=10,
                          min_bucket=4)
    res = exact.search(Qnp)
    got = exact.stats()
    assert got["tile_rows"] == int(np.sum(np.asarray(res.scanned)))
    assert got["scheduled_rows"] == 0


def test_engine_serves_sharded_backend(data, states):
    """The sharded family behind the Engine, unchanged: one compile per
    (bucket, k, nprobe), LUT cache live, refresh without recompiles."""
    _, R, Q, _ = data
    Qnp = np.asarray(Q)
    engine = search.Engine(search.make("ivf_sharded"), states["ivf_sharded"],
                           k=10, nprobe=4, min_bucket=4)
    for b in (3, 4, 7, 3):                 # buckets {4, 8}
        got = engine.search(Qnp[:b])
        want = search.make("ivf_sharded").search(
            states["ivf_sharded"], Q[:b], k=10, nprobe=4)
        np.testing.assert_allclose(np.asarray(got.scores),
                                   np.asarray(want.scores), rtol=1e-5,
                                   atol=1e-5)
    st = engine.stats()
    assert st["compiles"] == 2
    assert st["lut_misses"] > 0            # prepared path active
    compiles = st["compiles"]
    engine.refresh(_delta(R))
    after = engine.search(Qnp[:8])
    st = engine.stats()
    assert st["refreshes"] == 1
    assert st["compiles"] == compiles      # zero recompiles across refresh
    assert after.ids.shape == (8, 10)


def test_engine_plain_path_and_chunking(data, states):
    _, _, Q, _ = data
    engine = search.Engine(search.make("exact"), states["exact"], k=10,
                           min_bucket=4, max_bucket=8)
    res = engine.search(np.asarray(Q))      # B=16 > max_bucket: chunked
    assert res.ids.shape == (B, 10)
    st = engine.stats()
    assert st["requests"] == 2              # two max_bucket chunks
    assert st["lut_misses"] == 0            # exact has no LUT path
    assert st["searcher"]["backend"] == "exact"
    with pytest.raises(ValueError, match="empty query batch"):
        engine.search(np.zeros((0, DIM), np.float32))
    # nprobe on a backend that cannot honor it is an error, not a no-op
    with pytest.raises(ValueError, match="does not take nprobe"):
        engine.search(np.asarray(Q)[:4], nprobe=4)
    with pytest.raises(ValueError, match="does not take nprobe"):
        search.Engine(search.make("exact"), states["exact"], nprobe=4)


# ---------------------------------------------------------------------------
# PR 7: streaming exact scan, int8 LUTs, fused refresh (trace-counter checks)
# ---------------------------------------------------------------------------


def test_streaming_exact_matches_resident_exact(data, states):
    """The double-buffered host-streamed scan is the same oracle: scores
    bit-identical to the resident ``exact`` backend, through the Engine's
    eager (engine_jit=False) path included."""
    _, _, Q, _ = data
    want = search.make("exact").search(states["exact"], Q, k=10)
    got = search.make("exact_stream").search(states["exact_stream"], Q, k=10)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.scores),
                                  np.asarray(got.scores))
    engine = search.Engine(search.make("exact_stream"),
                           states["exact_stream"], k=10, min_bucket=4)
    eres = engine.search(np.asarray(Q))
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(eres.ids))
    # the host loop is never wrapped in an outer jit: zero Engine compiles
    assert engine.stats()["compiles"] == 0
    assert engine.stats()["searcher"]["streaming"] is True


def test_streaming_exact_fused_refresh_moves_no_tiles(data):
    """Fused mode: refresh touches only R — host tiles stay byte-identical
    and results stay exact (the delta cancels against the frozen corpus)."""
    X, R, Q, truth = data
    searcher = search.make("exact_stream")
    state = searcher.build(jax.random.PRNGKey(3), X, R,
                           CFG._replace(fused_refresh=True))
    tiles_before = [t.copy() for t in state.tiles]
    moved = searcher.refresh(state, _delta(R))
    for a, b in zip(tiles_before, moved.tiles):
        np.testing.assert_array_equal(a, b)      # zero corpus-side movement
    assert float(jnp.max(jnp.abs(moved.R - state.R))) > 0
    res = searcher.search(moved, Q, k=10)
    assert recall_at_k(np.asarray(res.ids), truth) >= 0.999
    assert searcher.stats(moved)["fused_refresh"] is True


@pytest.mark.parametrize("lut_dtype", ["int8", "uint8"])
def test_int8_luts_preserve_recall(data, states, lut_dtype):
    """Quantized ADC tables keep recall@10 within 0.01 of f32 on the same
    codes, for both the flat scan and the probed scan."""
    _, _, Q, truth = data
    index = states["ivf"].index
    for backend, attach_kw in (("flat_adc", {}), ("ivf", {"nprobe": L})):
        searcher = search.make(backend)
        f32 = searcher.attach(index, **attach_kw)
        q8 = searcher.attach(index, lut_dtype=lut_dtype, **attach_kw)
        r_f32 = searcher.search(f32, Q, k=10)
        r_q8 = searcher.search(q8, Q, k=10)
        rec_f32 = recall_at_k(np.asarray(r_f32.ids), truth)
        rec_q8 = recall_at_k(np.asarray(r_q8.ids), truth)
        assert rec_q8 >= rec_f32 - 0.01, (backend, lut_dtype)


def test_engine_lut_cache_keys_on_dtype(data, states):
    """Two Engines over the same index at different lut_dtypes must not
    alias cache entries: the key includes the dtype, so a dtype change is
    a miss, never a silently-wrong hit."""
    _, _, Q, _ = data
    Qnp = np.asarray(Q)
    searcher = search.make("flat_adc")
    state8 = searcher.attach(states["ivf"].index, lut_dtype="int8")
    engine = search.Engine(searcher, state8, k=10, min_bucket=4)
    engine.search(Qnp[:8])
    assert engine.stats()["lut_misses"] == 8
    engine.search(Qnp[:8])
    assert engine.stats()["lut_hits"] == 8
    # swap the state to f32 under the same Engine: same queries MISS
    engine.state = searcher.attach(states["ivf"].index)
    engine.search(Qnp[:8])
    st = engine.stats()
    assert st["lut_misses"] == 16 and st["lut_hits"] == 8
    key = engine._lut_key(Qnp[0])
    assert key[1] == "float32"                 # dtype is part of the key


def test_engine_fused_refresh_keeps_cache_and_executables(data, states):
    """The PR 7 acceptance trace: a fused within-subspace refresh costs the
    Engine zero recompiles AND zero LUT-cache invalidations — the epoch,
    the cached rows, and every executable survive; a cross-subspace delta
    still invalidates."""
    _, R, Q, _ = data
    Qnp = np.asarray(Q)
    searcher = search.make("flat_adc")
    state = searcher.attach(states["ivf"].index, lut_dtype="int8",
                            fused_refresh=True)
    engine = search.Engine(searcher, state, k=10, min_bucket=4)
    engine.search(Qnp[:8])
    compiles = engine.stats()["compiles"]
    assert engine.stats()["lut_invalidations"] == 0

    # subspace_gcd emits purely within-subspace pairs: LUTs provably valid
    engine.refresh(_delta(R))
    after = engine.search(Qnp[:8])
    st = engine.stats()
    assert st["refreshes"] == 1
    assert st["compiles"] == compiles          # zero recompiles
    assert st["lut_invalidations"] == 0        # zero cache rebuilds
    assert st["lut_hits"] == 8                 # the cached rows were REUSED
    assert st["lut_epoch"] == 0
    assert after.ids.shape == (8, 10)

    # a cross-subspace pair breaks the invariance proof: epoch advances
    cross = rotations.GivensDelta(pi=jnp.array([0]),
                                  pj=jnp.array([DIM - 1]),
                                  theta=jnp.array([1e-3]))
    engine.refresh(cross)
    engine.search(Qnp[:8])
    st = engine.stats()
    assert st["lut_invalidations"] == 1
    assert st["lut_epoch"] == 1
    assert st["lut_misses"] == 16
    assert st["compiles"] == compiles          # executables still survive


def test_fused_refresh_matches_eager_refresh(data):
    """Fused (query-side) and eager (corpus-side) refresh are the same
    math: after identical delta sequences the two states serve matching
    top-k on PQ and on depth-2 RQ."""
    X, R, Q, _ = data
    for depth in (1, 2):
        cfg = CFG._replace(depth=depth)
        searcher = search.make("flat_adc")
        eager = searcher.build(jax.random.PRNGKey(3), X, R, cfg)
        fused = searcher.build(jax.random.PRNGKey(3), X, R,
                               cfg._replace(fused_refresh=True))
        for i in range(3):
            d = _delta(R, key=i)
            eager = searcher.refresh(eager, d)
            fused = searcher.refresh(fused, d)
        r_e = searcher.search(eager, Q, k=10)
        r_f = searcher.search(fused, Q, k=10)
        np.testing.assert_allclose(np.asarray(r_e.scores),
                                   np.asarray(r_f.scores), rtol=1e-4,
                                   atol=1e-4)
        assert np.mean(np.asarray(r_e.ids) == np.asarray(r_f.ids)) >= 0.95


def test_sharded_fused_refresh_and_int8(data, states):
    """The sharded quantized twins inherit fused refresh + int8 LUTs: the
    frozen-index fused sharded state matches its REPLICATED fused twin
    after the same refresh (the shard merge only reorders candidates), and
    the invariance capability reports like the replicated one."""
    from repro.launch.mesh import make_data_mesh

    _, R, Q, _ = data
    mesh = make_data_mesh()
    index = states["ivf"].index
    searcher = search.make("flat_sharded")
    fused = searcher.attach(index, mesh=mesh, lut_dtype="int8",
                            fused_refresh=True)
    eager = searcher.attach(index, mesh=mesh)
    replicated = search.make("flat_adc").attach(index, lut_dtype="int8",
                                                fused_refresh=True)
    d = _delta(R)
    assert searcher.luts_refresh_invariant(fused, d) is True
    assert searcher.luts_refresh_invariant(eager, d) is False
    fused = searcher.refresh(fused, d)
    replicated = search.make("flat_adc").refresh(replicated, d)
    r_f = searcher.search(fused, Q, k=10)
    r_r = search.make("flat_adc").search(replicated, Q, k=10)
    np.testing.assert_allclose(np.asarray(r_r.scores),
                               np.asarray(r_f.scores), rtol=1e-5, atol=1e-5)
    assert np.mean(np.asarray(r_r.ids) == np.asarray(r_f.ids)) >= 0.95
    st = searcher.stats(fused)
    assert st["lut_dtype"] == "int8" and st["fused_refresh"] is True

"""repro.serve: continuous batching, SLO-adaptive nprobe, namespace
isolation, and the churn-maintenance glue — all on a VirtualClock so
queueing behavior is deterministic."""
import jax
import numpy as np
import pytest

from repro import rotations, search, serve
from repro.data import synthetic
from repro.serve.queue import BatchQueue, make_ticket

DIM, SUB, K, L, BS = 16, 4, 16, 8, 8
N = 1500
CFG = search.SearchConfig(num_lists=L, subspaces=SUB, codewords=K,
                          block_size=BS, nprobe=4, fused_refresh=True)


@pytest.fixture(scope="module")
def corpus():
    s = search.make("ivf")
    out = {}
    for i, name in enumerate(("alpha", "beta")):
        X = synthetic.sift_like(jax.random.PRNGKey(10 * i), N, DIM)
        R = rotations.random_rotation(jax.random.PRNGKey(10 * i + 1), DIM)
        state = s.build(jax.random.PRNGKey(10 * i + 2), X, R, CFG)
        Q = np.asarray(synthetic.sift_like(
            jax.random.PRNGKey(10 * i + 3), 16, DIM))
        out[name] = (state, Q)
    return s, out


def _frontend(corpus, **kw):
    s, states = corpus
    clk = serve.VirtualClock()
    fe = serve.Frontend(slo_ms=kw.pop("slo_ms", 200.0),
                        clock=clk.now, advance=clk.advance,
                        lut_budget_rows=kw.pop("lut_budget_rows", 256))
    for name, (state, Q) in states.items():
        fe.create_namespace(name, s, state, k=10, warmup_queries=Q[:2],
                            **kw)
    return clk, fe, states


# -- queue semantics --------------------------------------------------------
def test_queue_deadline_flush():
    clk = serve.VirtualClock()
    q = BatchQueue(admission_ms=5.0, max_admit=4, clock=clk.now)
    q.push(make_ticket("a", None, k=10, nprobe=None, slo_ms=50,
                       arrival=clk.now()))
    assert not q.due()                       # window still open
    assert q.take() == []
    clk.advance(0.004)
    q.push(make_ticket("a", None, k=10, nprobe=None, slo_ms=50,
                       arrival=clk.now()))
    assert not q.due()
    clk.advance(0.0015)                      # oldest passes 5 ms
    assert q.due()
    batch = q.take()
    assert len(batch) == 2                   # both ride the same bucket
    assert batch[0].waited_ms >= 5.0 > batch[1].waited_ms
    assert q.depth == 0 and not q.due()


def test_queue_full_bucket_flushes_immediately():
    clk = serve.VirtualClock()
    q = BatchQueue(admission_ms=1e6, max_admit=3, clock=clk.now)
    for _ in range(7):
        q.push(make_ticket("a", None, k=10, nprobe=None, slo_ms=50,
                           arrival=clk.now()))
    assert q.due()                           # full despite infinite window
    assert len(q.take()) == 3
    assert len(q.take()) == 3
    assert q.take() == []                    # 1 left, window open again
    assert q.depth == 1


def test_queue_deadline_zero_degenerates_to_immediate():
    clk = serve.VirtualClock()
    q = BatchQueue(admission_ms=0.0, max_admit=8, clock=clk.now)
    q.push(make_ticket("a", None, k=10, nprobe=None, slo_ms=50,
                       arrival=clk.now()))
    assert q.due()                           # no batching delay at all
    assert len(q.take()) == 1


def test_queue_empty_drain():
    q = BatchQueue(clock=serve.VirtualClock().now)
    assert list(q.drain()) == []
    assert q.take() == []
    assert q.next_deadline() is None


# -- SLO controller ---------------------------------------------------------
def test_slo_controller_sheds_and_recovers():
    c = serve.SLOController(ladder=(2, 8, 32), safety=1.0, ewma=0.5)
    for rung, ms in ((2, 1.0), (8, 4.0), (32, 16.0)):
        c.observe(8, rung, ms)
    assert c.choose(100.0, 8) == 32          # ample budget → top rung
    assert c.choose(10.0, 8) == 8            # mid fits, top doesn't
    assert c.choose(2.0, 8) == 2
    assert c.choose(0.5, 8) == 2             # nothing fits → floor
    # backlog feedforward: 2 waves of queued work halve the usable budget
    assert c.choose(20.0, 8, backlog=8) == 8
    assert c.choose(40.0, 8, backlog=8) == 32
    assert c.floors == 1 and c.sheds >= 3
    # EWMA folds new evidence: top rung speeding up re-enables it
    for _ in range(8):
        c.observe(8, 32, 2.0)
    assert c.choose(10.0, 8) == 32


def test_slo_unknown_cell_falls_to_floor():
    c = serve.SLOController(ladder=(2, 8))
    assert c.choose(1e9, 16) == 2            # no EWMA yet → serve at floor


# -- serving through the frontend ------------------------------------------
def test_ragged_k_nprobe_mix_one_bucket_matches_direct(corpus):
    """One flush holding mixed k and nprobe serves every request exactly
    as a direct Engine call with the same parameters would."""
    s, states = corpus
    clk, fe, _ = _frontend(corpus, admission_ms=2.0, max_admit=8)
    state, Q = states["alpha"]
    want_engine = search.Engine(s, state, k=10)
    mix = [dict(k=3, nprobe=2), dict(k=10, nprobe=2), dict(k=3, nprobe=8),
           dict(k=7, nprobe=None), dict(k=10, nprobe=None)]
    tickets = [fe.submit("alpha", Q[i], **m) for i, m in enumerate(mix)]
    clk.advance(0.003)
    fe.poll()
    assert all(t.done for t in tickets)
    for i, (t, m) in enumerate(zip(tickets, mix)):
        want = want_engine.search(Q[i:i + 1], k=m["k"], nprobe=m["nprobe"])
        np.testing.assert_array_equal(np.asarray(t.result.ids),
                                      np.asarray(want.ids)[0])
        np.testing.assert_allclose(np.asarray(t.result.scores),
                                   np.asarray(want.scores)[0], atol=1e-4)
        assert t.result.ids.shape == (m["k"],)


def test_batch_composition_invariance(corpus):
    """A request's results don't depend on which co-riders shared its
    bucket (deterministic topk_merge + row-independent ADC)."""
    s, states = corpus
    state, Q = states["alpha"]
    clk, fe, _ = _frontend(corpus, admission_ms=1.0, max_admit=8)
    solo = fe.submit("alpha", Q[0], nprobe=4)
    clk.advance(0.002)
    fe.poll()
    clk2, fe2, _ = _frontend(corpus, admission_ms=1.0, max_admit=8)
    crowd = [fe2.submit("alpha", Q[i], nprobe=4) for i in (3, 0, 5, 7)]
    clk2.advance(0.002)
    fe2.poll()
    np.testing.assert_array_equal(np.asarray(solo.result.ids),
                                  np.asarray(crowd[1].result.ids))


def test_adaptive_nprobe_stays_on_precompiled_ladder(corpus):
    """SLO adaptation only ever serves ladder rungs, and switching rungs
    never compiles a new executable after warmup."""
    clk, fe, states = _frontend(corpus, admission_ms=1.0, max_admit=4,
                                nprobe_ladder=(2, 4, 8), slo_ms=500.0)
    ns = fe.namespaces.get("alpha")
    warm = ns.engine.stats()["compiles"]
    _, Q = states["alpha"]
    served = []
    for i in range(12):
        t = fe.submit("alpha", Q[i % len(Q)],
                      slo_ms=500.0 if i % 3 else 1e-6)  # force floor sheds
        clk.advance(0.002)
        fe.poll()
        assert t.done
        served.append(t.nprobe_served)
    assert set(served) <= {2, 4, 8}
    assert 2 in served and 8 in served        # both ends exercised
    assert ns.engine.stats()["compiles"] == warm
    assert ns.slo.sheds >= 1


def test_default_warmup_synthesized(corpus):
    """create_namespace without warmup_queries still pre-compiles every
    (bucket, rung) cell and seeds the SLO model — synthetic Gaussian rows
    at the state's rotation width; warmup_queries=() opts out."""
    s, states = corpus
    state, Q = states["alpha"]
    clk = serve.VirtualClock()
    fe = serve.Frontend(clock=clk.now, advance=clk.advance,
                        lut_budget_rows=256, slo_ms=200.0)
    ns = fe.create_namespace("auto", s, state, k=10, nprobe_ladder=(2, 8),
                             admission_ms=1.0, max_admit=4)
    assert ns.warm_compiles > 0
    assert ns.slo.stats()["cells"]            # EWMA seeded per (bucket,rung)
    warm = ns.engine.stats()["compiles"]
    t = fe.submit("auto", Q[0], slo_ms=1e9)
    clk.advance(0.002)
    fe.poll()
    assert t.done and t.nprobe_served == 8    # budget allows the top rung
    assert ns.engine.stats()["compiles"] == warm

    cold = fe.create_namespace("cold", s, state, k=10,
                               warmup_queries=())
    assert cold.warm_compiles == 0


def test_namespace_isolation_refresh(corpus):
    """A cross-subspace refresh on alpha invalidates ONLY alpha's LUT
    cache; beta's cache, epoch, and executables are untouched."""
    s, states = corpus
    clk, fe, _ = _frontend(corpus, admission_ms=0.0, max_admit=4)
    Qa, Qb = states["alpha"][1], states["beta"][1]
    for i in range(4):
        fe.submit("alpha", Qa[i]); fe.submit("beta", Qb[i])
        fe.poll()
    ea = fe.namespaces.get("alpha").engine
    eb = fe.namespaces.get("beta").engine
    sb0 = eb.stats()
    assert sb0["lut_cached_rows"] > 0
    # cross-subspace delta: fused refresh cannot keep LUTs through it
    G = jax.random.normal(jax.random.PRNGKey(5), (DIM, DIM))
    learner = rotations.make("gcd")
    _, delta = learner.update(learner.init_from(ea.state.index.R), G, 1e-3,
                              jax.random.PRNGKey(6))
    ea.refresh(delta)
    sa, sb = ea.stats(), eb.stats()
    assert sa["lut_invalidations"] == 1 and sa["lut_epoch"] == 1
    assert sb["lut_invalidations"] == 0 and sb["lut_epoch"] == 0
    assert sb["lut_cached_rows"] == sb0["lut_cached_rows"]
    # beta still serves on warm caches: no new compiles, all LUT hits
    t = fe.submit("beta", Qb[0])
    fe.poll()
    assert t.done
    sb2 = eb.stats()
    assert sb2["compiles"] == sb0["compiles"]
    assert sb2["lut_misses"] == sb["lut_misses"]


def test_lut_budget_split_and_evictions(corpus):
    """The global LUT budget splits evenly per namespace; a hot tenant
    churning distinct queries evicts only its own rows."""
    s, states = corpus
    clk, fe, _ = _frontend(corpus, admission_ms=0.0, max_admit=4,
                           lut_budget_rows=8)
    ea = fe.namespaces.get("alpha").engine
    eb = fe.namespaces.get("beta").engine
    assert ea.lut_cache_rows == 4 and eb.lut_cache_rows == 4
    _, Qb = states["beta"]
    for i in range(3):
        fe.submit("beta", Qb[i]); fe.poll()
    rows_b = eb.stats()["lut_cached_rows"]
    rng = np.random.default_rng(0)
    for _ in range(10):                      # alpha hammers distinct queries
        fe.submit("alpha", rng.standard_normal(DIM).astype(np.float32))
        fe.poll()
    assert ea.stats()["lut_evictions"] > 0
    assert ea.stats()["lut_cached_rows"] <= 4
    assert eb.stats()["lut_cached_rows"] == rows_b     # beta untouched
    assert eb.stats()["lut_evictions"] == 0


def test_namespace_lifecycle_resplit(corpus):
    s, states = corpus
    clk = serve.VirtualClock()
    fe = serve.Frontend(clock=clk.now, advance=clk.advance,
                        lut_budget_rows=100)
    state, Q = states["alpha"]
    fe.create_namespace("a", s, state, k=10)
    assert fe.namespaces.get("a").engine.lut_cache_rows == 100
    fe.create_namespace("b", s, state, k=10)
    assert fe.namespaces.get("a").engine.lut_cache_rows == 50
    fe.drop_namespace("b")
    assert fe.namespaces.get("a").engine.lut_cache_rows == 100
    with pytest.raises(KeyError, match="unknown namespace"):
        fe.namespaces.get("b")
    with pytest.raises(ValueError, match="already exists"):
        fe.create_namespace("a", s, state, k=10)


def test_churn_ticks_in_idle_slots(corpus):
    """Idle polls run churn maintenance; staged rows flush through ticks
    without recompiling, and stay searchable."""
    s, states = corpus
    clk, fe, _ = _frontend(corpus, admission_ms=1.0, max_admit=4,
                           churn={"staging_rows": 64, "flush_at": 0.25})
    ns = fe.namespaces.get("alpha")
    _, Q = states["alpha"]
    t = fe.submit("alpha", Q[0])
    clk.advance(0.002)
    fe.poll()
    assert t.done
    compiles = ns.engine.stats()["compiles"]
    # in-distribution adds at double magnitude: distinctive PQ codes, so
    # each new row is its own query's strong match
    new = 2.0 * np.asarray(synthetic.sift_like(
        jax.random.PRNGKey(9), 32, DIM))
    new_ids = np.arange(10_000, 10_032, dtype=np.int32)
    ns.churn.add(new, new_ids)               # 32/64 staged > flush_at
    before = fe.stats()["maintenance_ticks"]
    fe.poll()                                # idle → maintenance tick
    assert fe.stats()["maintenance_ticks"] == before + 1
    assert ns.engine.obs.counter("churn.flushes").value >= 1
    assert ns.engine.stats()["compiles"] == compiles
    # flushed rows are searchable (probe every list so only ADC ranks)
    t2 = fe.submit("alpha", new[0], nprobe=L)
    clk.advance(0.002)
    fe.poll()
    assert 10_000 in np.asarray(t2.result.ids)


def test_poll_spans_reach_a_profiler_trace(corpus, tmp_path):
    """A flush under a profiler session: ``frontend.poll`` holds
    ``frontend.serve`` (its size and sequence number), which holds the
    Engine's submit and collect; a poll leaves no per-poll gauge."""
    import glob

    clk, fe, states = _frontend(corpus, admission_ms=2.0, max_admit=8)
    Q = states["alpha"][1]
    with jax.profiler.trace(str(tmp_path)):
        for q in Q[:3]:
            fe.submit("alpha", q)
        clk.advance(0.01)
        assert len(fe.poll()) == 3
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
            dict(ev.stats))
           for plane in jax.profiler.ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(("frontend.", "engine."))]
    by = {n: (s, e, a) for s, e, n, a in evs}
    assert set(by) >= {"frontend.poll", "frontend.serve", "engine.submit",
                       "engine.collect"}
    assert by["frontend.serve"][2] == {"size": 3,
                                       "flush": fe.stats()["flushes"]}
    assert by["engine.submit"][2]["batch"] == 3
    for inner, outer in (("frontend.serve", "frontend.poll"),
                         ("engine.submit", "frontend.serve"),
                         ("engine.collect", "frontend.serve")):
        assert by[outer][0] <= by[inner][0] <= by[inner][1] <= by[outer][1]
    assert not fe.obs.snapshot()["gauges"]


def test_drain_and_ticket_errors(corpus):
    clk, fe, states = _frontend(corpus, admission_ms=1e6, max_admit=64)
    _, Q = states["alpha"]
    tickets = [fe.submit("alpha", Q[i]) for i in range(3)]
    assert fe.poll() == []                   # window open for a long time
    assert not tickets[0].done
    with pytest.raises(ValueError, match="still in flight"):
        _ = tickets[0].latency_ms
    done = fe.drain()                        # shutdown flush ignores window
    assert len(done) == 3 and all(t.done for t in tickets)
    with pytest.raises(ValueError, match="query row"):
        fe.submit("alpha", Q[:2])
    with pytest.raises(KeyError, match="unknown namespace"):
        fe.submit("nope", Q[0])

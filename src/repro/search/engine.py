"""search.Engine — the batching, compile-cached serving front-end.

Production query traffic is ragged: request batches arrive at arbitrary
sizes, and naive ``jax.jit`` recompiles the whole search pipeline for every
new batch shape. The Engine sits between callers and a Searcher and makes
the hot path shape-stable:

  * **bucketizing** — a (b, n) batch is zero-padded up to the next
    power-of-two bucket (≥ ``min_bucket``), so the universe of compiled
    shapes is logarithmic in the max batch size; results are sliced back
    to b rows before returning. Batches beyond ``max_bucket`` are chunked.
  * **compile cache** — one executable per (bucket, k, nprobe) triple,
    built on first use and reused forever after: ``stats()["compiles"]``
    counts actual traces, and the cache-hit test in tests/test_search.py
    pins "at most one compile per shape". A ``refresh`` swaps the state
    *under* the cached executables (same pytree structure, same statics —
    guaranteed by the refresh contract), so a live rotation update costs
    zero recompiles.
  * **per-query ADC LUT cache** — for quantized backends the (code_width,
    K) LUT is the per-query setup cost; hot/repeated queries reuse their
    cached LUT pack (keyed by raw query bytes + ``lut_dtype`` + the
    invalidation epoch, LRU-evicted) and only cache misses pay the LUT
    build. A refresh normally invalidates the cache (LUTs depend on R),
    but a backend that proves its LUTs exactly invariant across the delta
    (``luts_refresh_invariant`` — fused refresh + within-subspace
    rotations) keeps the whole cache warm; ``stats()["lut_invalidations"]``
    counts the actual clears. Served through the backend's
    ``search_prepared`` capability; backends without it (``exact``) take
    the plain path, and host-loop backends (``exact_stream``,
    ``engine_jit = False``) run eagerly without an outer jit.
  * **buffer donation** — on accelerator backends the padded query/LUT
    buffers are donated to the executable, so serving steady-state holds
    one in-flight copy instead of two (donation is skipped on CPU, where
    XLA would warn and ignore it).
  * **submit/collect split** — ``submit`` runs everything up to and
    including launching the compiled executable and returns WITHOUT
    blocking (JAX dispatch is async); ``collect`` blocks on the result and
    records the request metrics. ``search`` is exactly
    ``collect(submit(...))`` plus chunking, so direct callers see no
    change — but a serving loop (``repro.serve``) can overlap host-side
    admission/batching for the next bucket with device execution of the
    current one.
  * **serving observability** — every request lands in a private, always-on
    ``repro.obs.Registry`` (latency distribution with p50/p95/p99, scanned
    rows against the rows the scan was scheduled to read, bucket pad
    waste, LUT hit rate, compile counts) aggregated by
    ``stats()``; an attached ``obs.RecallProbe`` replays a pinned query set
    through the serving path every N requests and gauges live recall@k.
  * **live refresh** — ``engine.refresh(delta)`` absorbs a rotation-learner
    step between batches: training and serving share the one
    ``RotationDelta`` path end to end. When the global ``repro.obs``
    registry is enabled the refresh also records health gauges (delta
    norm, post-refresh orthogonality drift — ``maintain.refresh_health``).

Typical loop::

    engine = search.Engine(search.make("ivf"), state, k=10, nprobe=16)
    for batch in requests:
        res = engine.search(batch)          # ragged sizes welcome
    engine.refresh(delta)                    # after a GCD training step
    print(engine.stats())
"""
from __future__ import annotations

import collections
import inspect
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, rotations
from repro.search.base import SearchResult, Searcher


def _lut_to_host(lut):
    """Host copy of a LUT pack (plain (b, Dp, K) array or (qlut, scales)
    tuple — see index/search.py ``split_lut_pack``). Always a copy: the
    device pack is donated to the scan right after, and a cached row must
    never alias a donated buffer (``np.asarray`` may return a view of it)."""
    if isinstance(lut, tuple):
        return tuple(np.array(p) for p in lut)
    return np.array(lut)


def _lut_row(lut_host, i: int):
    """Row ``i`` of a host LUT pack — the per-query cache value."""
    if isinstance(lut_host, tuple):
        return tuple(p[i] for p in lut_host)
    return lut_host[i]


def _stack_lut_rows(rows):
    """Reassemble cached per-query rows into a batch pack."""
    if isinstance(rows[0], tuple):
        return tuple(np.stack([r[j] for r in rows])
                     for j in range(len(rows[0])))
    return np.stack(rows)


def _pad_lut(lut, pad: int):
    """Zero-pad a LUT pack's query axis up to the bucket and land it on
    device (host rows assembled from the cache arrive as numpy)."""
    if isinstance(lut, tuple):
        return tuple(_pad_lut(p, pad) for p in lut)
    pads = ((0, pad),) + ((0, 0),) * (lut.ndim - 1)
    if isinstance(lut, np.ndarray):
        return jnp.asarray(np.pad(lut, pads))
    return jnp.pad(lut, pads)


class Pending(NamedTuple):
    """An in-flight ``Engine.submit`` — device work dispatched, not yet
    blocked on. Pass to ``Engine.collect`` exactly once; the request is
    only counted (latency, LUT hits, events) at collect time."""

    res: SearchResult          # sliced back to the request's b rows
    batch: int
    bucket: int
    k: int
    nprobe: int | None
    lut_hits: int
    lut_misses: int
    t0: float                  # perf_counter at submit — latency anchor
    compiled_before: int | float
    scheduled_rows: int        # rows the scan schedules for the b queries


class Engine:
    """Batching serving front-end over one Searcher + state (not thread-safe;
    one Engine per serving thread).

    ``lut_cache_rows`` bounds the LUT cache by *entries*, each a
    (code_width, K) f32 row on the host — 16 KiB at D=16/K=256 PQ, double
    at depth-2 RQ — so the default 8192 holds up to ~128–256 MiB per
    Engine at production configs. Size it to the host budget. The cache
    trades one synchronous device→host LUT copy per cold batch for free
    reuse on repeats; for purely streaming traffic with no repeated
    queries, set ``lut_cache_rows=0`` to disable it (and the prepared
    path) and serve fully on-device.

    ``probe`` (an ``obs.RecallProbe``) is replayed through ``search()``
    every ``probe.every`` requests; probe traffic takes the normal serving
    path and is counted in the request metrics like any other caller.
    """

    def __init__(self, searcher: Searcher, state: Any, *, k: int = 10,
                 nprobe: int | None = None, min_bucket: int = 8,
                 max_bucket: int = 4096, lut_cache_rows: int = 8192,
                 donate: bool | None = None, history: int = 512,
                 probe: obs.RecallProbe | None = None):
        self.searcher = searcher
        if hasattr(searcher, "prepare_state"):
            # bake derived statics now: inside the compiled executables the
            # state arrives as a traced pytree and cannot be host-synced
            state = searcher.prepare_state(state)
        self.state = state
        self.k = k
        self.nprobe = nprobe
        self.min_bucket = max(1, min_bucket)
        self.max_bucket = max(self.min_bucket, max_bucket)
        self.lut_cache_rows = lut_cache_rows
        self.history = history
        self.donate = (jax.default_backend() != "cpu"
                       if donate is None else donate)

        self._takes_nprobe = "nprobe" in inspect.signature(
            searcher.search).parameters
        if nprobe is not None and not self._takes_nprobe:
            raise ValueError(
                f"{type(searcher).__name__} does not take nprobe — an "
                "nprobe setting on this Engine would be silently ignored")
        # backends whose search is a host-side loop (exact_stream) opt out
        # of jit wrapping — their executables are the per-tile jits inside
        self._jit = bool(getattr(searcher, "engine_jit", True))
        self._prepared_ok = self._jit and lut_cache_rows > 0 and all(
            hasattr(searcher, m)
            for m in ("rotate_queries", "luts", "search_prepared"))
        self._compiled: dict[tuple, Any] = {}
        # per-query LUT rows (or (qlut, scales) row tuples), keyed by
        # (raw query bytes, lut_dtype, epoch) — the epoch advances whenever
        # a refresh actually invalidates LUTs, so stale entries can never
        # alias a fresh query even if a clear is ever skipped
        self._luts: collections.OrderedDict[tuple, Any] = \
            collections.OrderedDict()
        self._epoch = 0

        # private always-on registry: the source of truth behind ``stats()``
        # and the ``requests`` compat view (window = ``history`` requests)
        self.obs = obs.Registry(enabled=True, window=max(1, history))
        self._latency = self.obs.distribution("engine.latency_ms")
        self._scanned = self.obs.distribution("engine.scanned_rows")
        self._pad_waste = self.obs.distribution("engine.pad_waste")
        self._counters = {
            name: self.obs.counter(f"engine.{name}")
            for name in ("requests", "queries", "compiles", "refreshes",
                         "lut_hits", "lut_misses", "lut_invalidations",
                         "lut_evictions", "tile_rows", "scheduled_rows")}
        self.probe = probe
        self._in_probe = False

    # -- shape bucketing ---------------------------------------------------
    def _bucket(self, b: int) -> int:
        bucket = self.min_bucket
        while bucket < b:
            bucket *= 2
        # chunking guarantees b <= max_bucket, so the clamp still covers b
        # when max_bucket is not itself a power of two
        return min(bucket, self.max_bucket)

    # -- compile cache -----------------------------------------------------
    def _nprobe_key(self, nprobe: int | None) -> int | None:
        """The *effective* probe width: clamped by the backend where it can
        be (ivf caps at num_lists), so oversized requests share one
        executable and request records log what was actually probed."""
        if not self._takes_nprobe:
            if nprobe is not None:
                raise ValueError(
                    f"{type(self.searcher).__name__} does not take nprobe")
            return None
        npb = self.nprobe if nprobe is None else nprobe
        if npb is not None and npb < 1:
            raise ValueError(f"nprobe must be >= 1, got {npb}")
        if hasattr(self.searcher, "effective_nprobe"):
            npb = self.searcher.effective_nprobe(self.state, npb)
        return npb

    def _plain_fn(self, bucket: int, k: int, nprobe: int | None):
        key = ("plain", bucket, k, nprobe)
        if key not in self._compiled:
            searcher = self.searcher
            kw = {} if nprobe is None else {"nprobe": nprobe}
            if not self._jit:
                # eager backend (engine_jit=False): the host-side search
                # loop runs as-is — no outer trace, no donation, and no
                # compile tick (the backend jits its own inner steps)
                self._compiled[key] = \
                    lambda state, Q: searcher.search(state, Q, k=k, **kw)
                return self._compiled[key]
            compiles = self._counters["compiles"]

            def fn(state, Q):
                compiles.inc()  # traced once per key
                return searcher.search(state, Q, k=k, **kw)

            self._compiled[key] = jax.jit(
                fn, donate_argnums=(1,) if self.donate else ())
        return self._compiled[key]

    def _prepared_fn(self, bucket: int, k: int, nprobe: int | None):
        key = ("prepared", bucket, k, nprobe)
        if key not in self._compiled:
            searcher = self.searcher
            kw = {} if nprobe is None else {"nprobe": nprobe}
            compiles = self._counters["compiles"]

            def fn(state, QR, lut):
                compiles.inc()  # traced once per key
                return searcher.search_prepared(state, QR, lut, k=k, **kw)

            self._compiled[key] = jax.jit(
                fn, donate_argnums=(1, 2) if self.donate else ())
        return self._compiled[key]

    def _scheduled_rows(self, nprobe: int | None) -> int:
        """Rows the scan schedules per query at this probe width (the
        backend's ``scheduled_rows`` capability: whole tiles of every probed
        list's window, holes included), 0 for a backend without a tile
        schedule. Read from the state's statics: no host sync."""
        fn = getattr(self.searcher, "scheduled_rows", None)
        return 0 if fn is None else fn(self.state, nprobe)

    # -- per-query LUT cache -----------------------------------------------
    def _lut_key(self, row: np.ndarray) -> tuple:
        """Cache key for one query row: raw bytes + the LUT precision knob
        + the invalidation epoch. ``lut_dtype`` is in the key because the
        cached rows ARE dtype-specific (an int8 (qlut, scales) row is not a
        f32 row); the epoch is bumped by non-invariant refreshes."""
        return (row.tobytes(),
                getattr(self.state, "lut_dtype", "float32"),
                self._epoch)

    def _gather_luts(self, Qnp: np.ndarray,
                     QR: jax.Array) -> tuple[Any, int, int]:
        """LUT rows for every query, cached by raw query bytes (+ dtype,
        epoch). ``QR`` is the already-rotated batch (rows sliced for the
        misses, so the rotation runs once per request). Returns (lut pack
        (b, Dp, K) or ((b, Dp, K) qlut, (b, Dp, 2) scales), hits, misses)
        — both counted per served row; duplicate rows inside one batch pay
        the LUT build only once."""
        keys = [self._lut_key(row) for row in Qnp]
        hits = 0
        need, seen = [], set()
        for i, kb in enumerate(keys):
            if kb in self._luts:
                hits += 1
                self._luts.move_to_end(kb)  # MRU now: never evicted below
            elif kb not in seen:
                seen.add(kb)
                need.append(i)
        misses = len(keys) - hits
        if misses == len(keys) and len(need) == len(keys):
            # all-miss, all-distinct: serve the device LUTs directly (skip
            # the host round-trip); the host copy below only feeds the cache
            lut_dev = self.searcher.luts(self.state, QR)
            lut_host = _lut_to_host(lut_dev)
            for i, kb in enumerate(keys):
                self._luts[kb] = _lut_row(lut_host, i)
            self._evict()
            return lut_dev, hits, misses
        if need:
            lut_m = _lut_to_host(self.searcher.luts(
                self.state, QR[np.asarray(need)]))
            for j, i in enumerate(need):
                self._luts[keys[i]] = _lut_row(lut_m, j)
        # read every row BEFORE evicting: a batch wider than the cache (or
        # one whose misses push out nothing-but-this-batch entries) must
        # still assemble — eviction only trims for the NEXT request
        rows = _stack_lut_rows([self._luts[kb] for kb in keys])
        self._evict()
        return rows, hits, misses

    def _evict(self) -> None:
        """Trim to the capacity cap (LRU-first), counting every eviction —
        ``lut_evictions`` is how a multi-tenant front-end sees one hot
        namespace churning its budget (repro.serve sizes each tenant's cap
        so that churn can never spill into another tenant's cache)."""
        while len(self._luts) > self.lut_cache_rows:
            self._luts.popitem(last=False)
            self._counters["lut_evictions"].inc()

    # -- serving -----------------------------------------------------------
    def submit(self, Q: jax.Array, *, k: int | None = None,
               nprobe: int | None = None) -> Pending:
        """Dispatch one (b, n) batch (1 ≤ b ≤ ``max_bucket``) WITHOUT
        blocking: bucketize, resolve LUTs, launch the compiled executable,
        and return a ``Pending`` the caller hands to ``collect``. Device
        work proceeds asynchronously in the meantime, so a serving loop can
        keep admitting/batching the next bucket while this one runs."""
        b = Q.shape[0]
        if b == 0:
            raise ValueError("empty query batch")
        if b > self.max_bucket:
            raise ValueError(
                f"submit is bounded by max_bucket={self.max_bucket} "
                f"(got {b}); search() chunks oversized batches")
        k = self.k if k is None else k
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        npb = self._nprobe_key(nprobe)
        bucket = self._bucket(b)
        pad = bucket - b
        compiled_before = self._counters["compiles"].value
        t0 = time.perf_counter()

        lut_hits = lut_misses = 0
        shape = dict(batch=b, bucket=bucket)
        with obs.annotate("engine.submit", **shape):
            if self._prepared_ok:
                # the LUT cache keys on raw query bytes — the one place the
                # batch must visit the host (dtype preserved, matching the
                # plain path and direct searcher calls); rotation reads the
                # original array, so a device-resident Q is not re-uploaded
                Qnp = np.asarray(Q)
                with obs.annotate("engine.rotate", **shape):
                    QR = self.searcher.rotate_queries(self.state, Q)
                with obs.annotate("engine.luts", **shape):
                    lut, lut_hits, lut_misses = self._gather_luts(Qnp, QR)
                with obs.annotate("engine.dispatch", **shape):
                    QR = jnp.pad(QR, ((0, pad), (0, 0)))
                    # pack-aware: cached host rows and all-miss device
                    # packs both pad up to the bucket and land on device
                    lut = _pad_lut(lut, pad)
                    res = self._prepared_fn(bucket, k, npb)(self.state, QR,
                                                            lut)
            else:
                # plain path: never leaves the device
                with obs.annotate("engine.dispatch", **shape):
                    Qp = jnp.pad(jnp.asarray(Q), ((0, pad), (0, 0)))
                    res = self._plain_fn(bucket, k, npb)(self.state, Qp)

            res = SearchResult(scores=res.scores[:b], ids=res.ids[:b],
                               scanned=res.scanned[:b])
        return Pending(res=res, batch=b, bucket=bucket, k=k, nprobe=npb,
                       lut_hits=lut_hits, lut_misses=lut_misses, t0=t0,
                       compiled_before=compiled_before,
                       scheduled_rows=b * self._scheduled_rows(npb))

    def collect(self, pending: Pending) -> SearchResult:
        """Block on a ``submit``'s device work and account the request:
        latency covers submit → result-ready (exactly what the fused
        ``search`` span used to measure), LUT hits/misses and the request
        event land here. Call once per Pending."""
        res = pending.res
        with obs.annotate("engine.collect", batch=pending.batch,
                          bucket=pending.bucket):
            leaves = [x for x in jax.tree_util.tree_leaves(res)
                      if not isinstance(x, jax.core.Tracer)]
            if leaves:
                jax.block_until_ready(leaves)
            latency_ms = (time.perf_counter() - pending.t0) * 1e3

            scanned = np.asarray(res.scanned)
            scanned_rows = float(np.mean(scanned))
            self._counters["requests"].inc()
            self._counters["queries"].inc(pending.batch)
            self._counters["lut_hits"].inc(pending.lut_hits)
            self._counters["lut_misses"].inc(pending.lut_misses)
            self._counters["tile_rows"].inc(int(np.sum(scanned)))
            self._counters["scheduled_rows"].inc(pending.scheduled_rows)
            self._latency.observe(latency_ms)
            self._scanned.observe(scanned_rows)
            self._pad_waste.observe(
                (pending.bucket - pending.batch) / pending.bucket)
            self.obs.event(
                "request", batch=pending.batch, bucket=pending.bucket,
                k=pending.k, nprobe=pending.nprobe, latency_ms=latency_ms,
                scanned_rows=scanned_rows, lut_hits=pending.lut_hits,
                lut_misses=pending.lut_misses,
                compiled=(self._counters["compiles"].value
                          > pending.compiled_before))

        if self.probe is not None and not self._in_probe:
            self._in_probe = True
            try:
                self.probe.maybe_run(
                    lambda pq: self.search(pq, k=self.probe.k))
            finally:
                self._in_probe = False
        return res

    def search(self, Q: jax.Array, *, k: int | None = None,
               nprobe: int | None = None) -> SearchResult:
        """Serve one (b, n) query batch (any b ≥ 1) at top-``k`` —
        ``collect(submit(...))``, chunking batches beyond ``max_bucket``."""
        b = Q.shape[0]
        if b == 0:
            raise ValueError("empty query batch")
        if b > self.max_bucket:  # chunk oversized requests
            parts = [self.collect(self.submit(Q[i:i + self.max_bucket],
                                              k=k, nprobe=nprobe))
                     for i in range(0, b, self.max_bucket)]
            return SearchResult(
                scores=jnp.concatenate([p.scores for p in parts]),
                ids=jnp.concatenate([p.ids for p in parts]),
                scanned=jnp.concatenate([p.scanned for p in parts]))
        return self.collect(self.submit(Q, k=k, nprobe=nprobe))

    # -- live rotation refresh --------------------------------------------
    def refresh(self, delta: rotations.RotationDelta) -> None:
        """Absorb a rotation-learner step between batches. Cached LUTs are
        invalidated (they depend on R) — UNLESS the backend proves them
        exactly invariant across this delta (fused refresh + purely
        within-subspace rotations: ``luts_refresh_invariant``), in which
        case the whole cache and its epoch survive. Compiled executables
        survive either way (the state pytree's structure and statics are
        refresh-invariant)."""
        R = self._live_rot()
        if R is not None:
            n = int(R.shape[-1])
            pi = getattr(delta, "pi", None)
            if pi is not None and pi.size and int(
                    jnp.maximum(pi.max(), delta.pj.max())) >= n:
                # out-of-range pair indices would one-hot to zero rows and
                # silently corrupt R — a trainer/index dimension mismatch
                raise ValueError(
                    f"refresh: delta rotates pairs up to index "
                    f"{int(jnp.maximum(pi.max(), delta.pj.max()))} but the "
                    f"live rotation is {n}x{n} — the trainer's manifold "
                    f"leaf and this index have different dimensions")
            dR = getattr(delta, "dR", None)
            if dR is not None and dR.shape[-1] != n:
                raise ValueError(
                    f"refresh: dense delta is {dR.shape[-1]}x"
                    f"{dR.shape[-1]} but the live rotation is {n}x{n}")
        keep = (hasattr(self.searcher, "luts_refresh_invariant")
                and self.searcher.luts_refresh_invariant(self.state, delta))
        with obs.annotate("engine.refresh"):
            self.state = self.searcher.refresh(self.state, delta)
        if not keep:
            self._luts.clear()
            self._epoch += 1
            self._counters["lut_invalidations"].inc()
        self._counters["refreshes"].inc()
        if obs.enabled():
            # refresh health (delta norm + orthogonality drift) on the
            # global registry — a host sync on the (n, n) rotation, so only
            # when someone is watching
            from repro.index import maintain

            # the LIVE rotation lives at state.rot (fused quantized modes —
            # state.R / state.index.R are frozen at R₀ there), else state.R
            # (exact/flat/sharded) or state.index.R (the replicated ivf
            # backend wraps an IVFPQIndex)
            R = self._live_rot()
            if R is not None:
                maintain.refresh_health(R, delta)

    def _live_rot(self):
        """The live rotation the current backend scores through (see the
        per-backend comment in ``refresh``), or None."""
        R = getattr(self.state, "rot", None)
        if R is None:
            R = getattr(self.state, "R", None)
        if R is None:
            R = getattr(getattr(self.state, "index", None), "R", None)
        return R

    # -- observability -----------------------------------------------------
    @property
    def requests(self) -> list[dict]:
        """Compat view: the retained per-request records (newest last, at
        most ``history``), reconstructed from the registry's event window."""
        return [{k: v for k, v in rec.items() if k not in ("kind", "t")}
                for rec in self.obs.events("request")]

    def stats(self) -> dict:
        """Aggregate serving stats + the backend's static facts.

        Two scopes, in one place: **lifetime totals** — every counter key
        (``requests``, ``queries``, ``compiles``, ``executables``,
        ``refreshes``, ``lut_hits``, ``lut_misses``, and the
        ``lut_hit_rate`` derived from them; ``tile_rows``, the rows of the
        real list tiles the scan read, against ``scheduled_rows``, every
        row it was scheduled to read) counts since Engine
        construction and never resets. **Window-scoped** — every latency /
        scanned-rows / pad-waste aggregate (mean, p50, p95, p99, max)
        covers only the retained request window: the last
        ``window["size"]`` requests, bounded by ``window["capacity"]``
        (the ``history`` constructor arg). The ``window`` dict makes the
        scope machine-readable so dashboards don't have to guess."""
        lat = self._latency.summary()
        c = {name: m.value for name, m in self._counters.items()}
        looked = c["lut_hits"] + c["lut_misses"]
        out = dict(
            requests=c["requests"],
            queries=c["queries"],
            compiles=c["compiles"],
            executables=len(self._compiled),
            refreshes=c["refreshes"],
            lut_hits=c["lut_hits"],
            lut_misses=c["lut_misses"],
            lut_hit_rate=(c["lut_hits"] / looked if looked else 0.0),
            lut_cached_rows=len(self._luts),
            lut_evictions=c["lut_evictions"],
            lut_invalidations=c["lut_invalidations"],
            lut_epoch=self._epoch,
            tile_rows=c["tile_rows"],
            scheduled_rows=c["scheduled_rows"],
            window=dict(size=lat.get("window", 0),
                        capacity=self.history,
                        scope="latency/scanned/pad aggregates"),
            window_requests=lat.get("window", 0),
            latency_ms_mean=lat.get("mean", 0.0),
            latency_ms_p50=lat.get("p50", 0.0),
            latency_ms_p95=lat.get("p95", 0.0),
            latency_ms_p99=lat.get("p99", 0.0),
            latency_ms_max=(max(self._latency.window_values())
                            if lat.get("window") else 0.0),
            scanned_rows_mean=self._scanned.summary().get("mean", 0.0),
            pad_waste_mean=self._pad_waste.summary().get("mean", 0.0),
            searcher=self.searcher.stats(self.state),
        )
        if self.probe is not None:
            out["recall_probe"] = dict(k=self.probe.k,
                                       recall=self.probe.last,
                                       every=self.probe.every)
        out["churn"] = self._churn_stats()
        return out

    def _churn_stats(self) -> dict:
        """The live-churn block of ``stats()``: read off this Engine's own
        registry, where an attached ``churn.ChurnController`` records its
        counters/gauges/spans. Always present (all-zero without a
        controller) so dashboards have a stable schema; same two scopes as
        above — counters are lifetime, ``flush_ms`` aggregates cover the
        retained window described by the ``window`` dict."""
        flush_ms = self.obs.distribution("churn.flush_ms")
        summ = flush_ms.summary()
        return dict(
            staged_rows=self.obs.gauge("churn.staged_rows").value,
            tombstoned_rows=self.obs.gauge("churn.tombstoned_rows").value,
            staged=self.obs.counter("churn.staged").value,
            flushed=self.obs.counter("churn.flushed").value,
            tombstoned=self.obs.counter("churn.tombstoned").value,
            flushes=self.obs.counter("churn.flushes").value,
            compactions=self.obs.counter("churn.compactions").value,
            rebalances=self.obs.counter("churn.rebalances").value,
            grows=self.obs.counter("churn.grows").value,
            flush_ms_p95=flush_ms.percentile(95.0),
            # background compaction (BackgroundCompactor; zero without one)
            bg_submitted=self.obs.counter("churn.bg_submitted").value,
            bg_compactions=self.obs.counter("churn.bg_compactions").value,
            bg_discarded=self.obs.counter("churn.bg_discarded").value,
            flushes_deferred=self.obs.counter("churn.flushes_deferred").value,
            reencoded=self.obs.counter("churn.reencoded").value,
            compact_hidden_ms_total=self.obs.distribution(
                "churn.compact_hidden_ms").total,
            window=dict(size=summ.get("window", 0),
                        capacity=self.history,
                        scope="flush_ms aggregates"),
        )

"""The serving front-end: one loop over many tenants.

``Frontend`` owns the namespace table and runs the serving loop the rest
of the package supplies parts for: callers ``submit`` single queries
(each with its own latency SLO) and ``poll`` drives everything else —

  1. **flush due buckets**: every namespace whose admission deadline
     expired (or whose bucket filled) has its tickets popped, grouped by
     (k, nprobe) into Engine-shaped batches, and *submitted without
     blocking* — device work for one group overlaps host batching of the
     next. Groups are then collected in order and each ticket gets its
     own row of the batch result.
  2. **pick nprobe**: tickets without an explicit nprobe are served at
     the rung the namespace's ``SLOController`` picks from the remaining
     per-request budget and the current backlog. Rungs come from a fixed
     pre-compiled ladder, so adaptation never recompiles.
  3. **idle maintenance**: a poll that flushed nothing instead ticks ONE
     namespace's ``ChurnController`` (round-robin) — threshold-driven
     flush/compact/rebalance runs in the gaps between buckets, sharing
     the serving loop without a second thread and without recompiles
     (churn ops are shape-preserving once staging is installed).

Construction order matters and ``create_namespace`` enforces it: the
ChurnController is attached BEFORE warmup because installing the staging
buffer changes the state pytree's structure — the one structural change
allowed, and it must land before the first executable is compiled.
Warmup then compiles every (bucket ≤ max_admit, k, ladder rung) cell and
seeds the SLO latency model from a measured steady-state run of each, so
the controller starts with calibrated predictions and serving starts at
zero pending compiles.

Clocks: pass ``clock=time.monotonic`` (default) for wall-clock serving,
or a ``VirtualClock``'s ``now``/``advance`` pair to run deterministic
simulations where queueing dynamics unfold in virtual time while service
times are real measured compute (see benchmarks/serve_load.py).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import numpy as np

from repro import obs
from repro.search import registry as search_registry
from repro.search.base import SearchResult
from repro.search.engine import Engine
from repro.serve.namespaces import Namespace, NamespaceSet
from repro.serve.queue import BatchQueue, Ticket, make_ticket
from repro.serve.slo import SLOController

_ADAPTIVE = object()     # grouping key slot for "SLO picks the rung"


def _synth_warmup_queries(state: Any, rows: int = 8) -> np.ndarray:
    """Gaussian (rows, n) warmup queries at the state's rotation width.

    Warmup exists to compile cells and time them, and cell cost is
    query-content-independent, so synthetic rows are as good as real ones.
    Probes the serving rotation the same way Engine.refresh does
    (``state.rot`` for fused states, else ``state.R``, else
    ``state.index.R``); a state with none of these gets no default warmup.
    """
    R = getattr(state, "rot", None)
    if R is None:
        R = getattr(state, "R", None)
    if R is None:
        R = getattr(getattr(state, "index", None), "R", None)
    if R is None:
        return np.empty((0, 0), dtype=np.float32)
    n = int(np.asarray(R.shape)[-1])
    return np.random.default_rng(0).standard_normal((rows, n)).astype(
        np.float32)


class Frontend:
    """Multi-tenant continuous-batching serving loop (see module doc).

    ``lut_budget_rows`` is the global host LUT budget shared by all
    namespaces (split evenly — see ``serve.namespaces``). ``slo_ms`` is
    the default per-request latency budget; each submit may override it.
    """

    def __init__(self, *, lut_budget_rows: int = 8192, slo_ms: float = 50.0,
                 clock: Callable[[], float] = time.monotonic,
                 advance: Callable[[float], float] | None = None):
        self.namespaces = NamespaceSet(lut_budget_rows=lut_budget_rows)
        self.default_slo_ms = float(slo_ms)
        self.clock = clock
        self._advance = advance      # virtual-time hook; None = wall clock
        self._tick_order: list[str] = []   # round-robin churn cursor
        self.obs = obs.Registry(enabled=True, window=512)
        self._counters = {
            name: self.obs.counter(f"serve.{name}")
            for name in ("admitted", "flushes", "batches", "served",
                         "sheds", "maintenance_ticks")}

    # -- tenant lifecycle --------------------------------------------------
    def create_namespace(self, name: str, searcher, state: Any = None, *,
                         k: int = 10, nprobe_ladder: Sequence[int] | None = None,
                         slo_ms: float | None = None,
                         admission_ms: float = 2.0, max_admit: int = 64,
                         churn: dict | None = None,
                         warmup_queries: Any = None,
                         slo_safety: float = 1.3,
                         engine_kwargs: dict | None = None) -> Namespace:
        """Register a tenant. ``searcher`` is a registry spec string (state
        built by the caller and passed in) or an already-built Searcher.

        ``nprobe_ladder``: the fixed rung set SLO adaptation picks from
        (None → serve at the Engine's default nprobe, no adaptation —
        required for backends that don't take nprobe). ``churn``: kwargs
        for a ChurnController (e.g. ``{"staging_rows": 1024}``), attached
        before warmup; None → no churn hook. ``warmup_queries`` (m, n):
        rows tiled to pre-compile every (bucket, k, rung) cell and seed
        the SLO latency model; None synthesizes Gaussian rows at the
        state's rotation width (cell cost is query-content-independent —
        real rows only matter if you want warmup to also prime the LUT
        cache). Pass ``warmup_queries=()`` to skip warmup entirely
        (first requests then pay the compiles and the SLO controller
        floor-falls until it has observed each cell).
        """
        if isinstance(searcher, str):
            searcher = search_registry.make(searcher)
        kwargs = dict(engine_kwargs or {})
        kwargs.setdefault("max_bucket", max(max_admit, 1))
        engine = Engine(searcher, state, k=k, **kwargs)
        if nprobe_ladder is not None and not engine._takes_nprobe:
            raise ValueError(
                f"{type(searcher).__name__} does not take nprobe — "
                "nprobe_ladder requires an nprobe-capable backend")
        controller = None
        if churn is not None:
            # staging install mutates pytree STRUCTURE — must precede the
            # first compile, hence before warmup
            from repro.churn.controller import ChurnController
            controller = ChurnController(engine, **churn)
        ns = Namespace(
            name=name, engine=engine,
            queue=BatchQueue(admission_ms=admission_ms, max_admit=max_admit,
                             clock=self.clock),
            slo=SLOController(nprobe_ladder or (1,), safety=slo_safety),
            churn=controller)
        ns.slo_ms = self.default_slo_ms if slo_ms is None else float(slo_ms)
        ns.adaptive = nprobe_ladder is not None
        self.namespaces.add(ns)
        self._tick_order.append(name)
        if warmup_queries is None:
            warmup_queries = _synth_warmup_queries(state)
        Qw = np.asarray(warmup_queries)
        if Qw.size:
            self._warmup(ns, Qw)
        ns.warm_compiles = engine.stats()["compiles"]
        return ns

    def drop_namespace(self, name: str) -> None:
        self.namespaces.drop(name)
        self._tick_order.remove(name)

    def _warmup(self, ns: Namespace, Qw: np.ndarray) -> None:
        """Compile every (bucket, k, rung) cell the queue can produce and
        seed the SLO EWMA from a second, measured run of each (the first
        run pays the compile and must not poison the latency model)."""
        engine = ns.engine
        buckets, b = [], engine.min_bucket
        top = min(max(ns.queue.max_admit, 1), engine.max_bucket)
        while True:
            buckets.append(b)
            if b >= top:
                break
            b *= 2
        rungs = list(ns.slo.ladder) if ns.adaptive else [None]
        for bucket in buckets:
            reps = -(-bucket // Qw.shape[0])
            Qb = np.tile(Qw, (reps, 1))[:bucket]
            for rung in rungs:
                engine.collect(engine.submit(Qb, nprobe=rung))   # compile
                reps_ms = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    engine.collect(engine.submit(Qb, nprobe=rung))
                    reps_ms.append((time.perf_counter() - t0) * 1e3)
                if rung is not None:
                    # median of 3: one noisy sample must not skew the
                    # seed the controller (and rate calibration) trusts
                    ns.slo.observe(bucket, rung, sorted(reps_ms)[1])

    # -- request path ------------------------------------------------------
    def submit(self, namespace: str, query, *, k: int | None = None,
               nprobe: int | None = None, slo_ms: float | None = None,
               arrival: float | None = None) -> Ticket:
        """Admit one query row into its namespace's current bucket and
        return the Ticket to await (serving happens in ``poll``).

        ``arrival`` backdates the ticket to its true arrival time (open-
        loop load generators submit a burst of trace arrivals the moment
        the loop regains control — their queue wait must still count from
        when they *arrived*, not from when the loop got to them)."""
        ns = self.namespaces.get(namespace)
        row = np.asarray(query)
        if row.ndim != 1:
            raise ValueError(
                f"submit takes one (n,) query row, got shape {row.shape}")
        t = make_ticket(
            ns.name, row, k=ns.engine.k if k is None else int(k),
            nprobe=nprobe,
            slo_ms=ns.slo_ms if slo_ms is None else float(slo_ms),
            arrival=self.clock() if arrival is None else float(arrival))
        ns.queue.push(t)
        self._counters["admitted"].inc()
        return t

    def next_deadline(self) -> float | None:
        """Earliest bucket-flush deadline across all namespaces (None when
        every queue is empty) — what an event loop sleeps until."""
        deadlines = [d for ns in self.namespaces
                     if (d := ns.queue.next_deadline()) is not None]
        return min(deadlines) if deadlines else None

    def poll(self) -> list[Ticket]:
        """One turn of the serving loop: flush every due bucket (tickets
        come back completed); when nothing was due, run one idle-slot
        churn maintenance tick instead. Returns the completed tickets."""
        done: list[Ticket] = []
        with obs.annotate("frontend.poll"):
            for ns in self.namespaces:
                while (batch := ns.queue.take(self.clock())):
                    done.extend(self._serve(ns, batch))
            if not done:
                self._maintenance_tick()
        return done

    def drain(self) -> list[Ticket]:
        """Flush every namespace's remaining tickets regardless of
        deadlines (end of run / shutdown)."""
        done: list[Ticket] = []
        for ns in self.namespaces:
            for batch in ns.queue.drain():
                done.extend(self._serve(ns, batch))
        return done

    def _maintenance_tick(self) -> None:
        """Round-robin one namespace's churn step into this idle slot."""
        for _ in range(len(self._tick_order)):
            name = self._tick_order.pop(0)
            self._tick_order.append(name)
            if name in self.namespaces and \
                    self.namespaces.get(name).maintenance_tick():
                self._counters["maintenance_ticks"].inc()
                return

    # -- batch service -----------------------------------------------------
    def _serve(self, ns: Namespace, batch: list[Ticket]) -> list[Ticket]:
        """Serve one flushed bucket: group by (k, nprobe), pick rungs for
        the adaptive groups, submit all groups (device work overlaps),
        then collect in order and scatter rows back onto tickets. The
        ``frontend.serve`` annotation carries the flush's size and sequence
        number, which join the Engine's spans of one flush in a trace."""
        flushes = self._counters["flushes"]
        flushes.inc()
        with obs.annotate("frontend.serve", size=len(batch),
                          flush=flushes.value):
            now = self.clock()
            groups: dict[tuple, list[Ticket]] = {}
            for t in batch:
                key = (t.k, t.nprobe if t.nprobe is not None
                       else (_ADAPTIVE if ns.adaptive else None))
                groups.setdefault(key, []).append(t)

            inflight = []
            for (k, npkey), tickets in groups.items():
                rung = None
                if npkey is _ADAPTIVE:
                    budget = min(t.remaining_ms(now) for t in tickets)
                    bucket = ns.engine._bucket(len(tickets))
                    rung = ns.slo.choose(budget, bucket,
                                         backlog=ns.queue.depth)
                    if rung != ns.slo.ladder[-1]:
                        self._counters["sheds"].inc()
                    npb = rung
                else:
                    npb = npkey
                Q = np.stack([t.query for t in tickets])
                pending = ns.engine.submit(Q, k=k, nprobe=npb)
                inflight.append((tickets, pending, rung))
                self._counters["batches"].inc()

            done = []
            for tickets, pending, rung in inflight:
                res = ns.engine.collect(pending)
                service_ms = (time.perf_counter() - pending.t0) * 1e3
                if self._advance is not None:
                    # virtual time: queueing already elapsed on the virtual
                    # clock; fold the real measured service time in now
                    self._advance(service_ms * 1e-3)
                completed = self.clock()
                if rung is not None:
                    ns.slo.observe(pending.bucket, rung, service_ms)
                for i, t in enumerate(tickets):
                    t.result = SearchResult(scores=res.scores[i],
                                            ids=res.ids[i],
                                            scanned=res.scanned[i])
                    t.nprobe_served = pending.nprobe
                    t.completed = completed
                    done.append(t)
                self._counters["served"].inc(len(tickets))
            return done

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        """Front-end counters + per-namespace engine/queue/SLO views."""
        out = {name: c.value for name, c in self._counters.items()}
        out["namespaces"] = {ns.name: ns.stats() for ns in self.namespaces}
        return out

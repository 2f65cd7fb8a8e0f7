"""Traffic ``serve_open``: independent users, open-loop arrivals of single
distinct queries through ``serve.Frontend`` on the wall clock.

Set-up, from the seed: the configuration's corpus and IVF-PQ index (as
``serve_bulk``), a ``Frontend`` with one namespace (no nprobe ladder, no
churn; ``admission_ms`` and ``max_admit`` from the workload), whose own
warm-up compiles every bucket, and one distinct query per request.

Arrivals: ``rate`` × ``--seconds`` requests whose gaps are the quantiles of
the exponential distribution at that rate — the same set of gaps for every
seed, in an order the seed shuffles — so each run offers the same load.
Each request is submitted with ``arrival=`` its scheduled time, so its
latency runs from when it was due, and how late the generator submitted it
is recorded. The window ends when the last request due in it has been
answered (or ``grace_s`` later).

``serve_p50_ms`` and the tail ``serve_p90_ms`` are taken over all requests
due in the window; a request never answered counts in ``failed`` and at the
limit of the tail. After the window a sample of the
answers drawn from the seed is checked against the plain float32 search
over the same index.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, system


def arrival_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Scheduled send times (s from the window's start), ascending."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    np.random.default_rng(seed).shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def setup(run: harness.Run):
    from repro import serve

    wl, cfg = run.workload, run.config
    searcher, state, ckey = system.serving_index(run, wl, cfg)
    fe = serve.Frontend(clock=time.monotonic)
    ns = fe.create_namespace("bench", searcher, state, k=wl["k"],
                             nprobe_ladder=None,
                             admission_ms=wl["admission_ms"],
                             max_admit=wl["max_admit"], churn=None)
    # a flush serves any number of requests up to max_admit, and the
    # Engine's and Frontend's host paths run eager ops shaped by that
    # number: serve one flush of each size, of queries no LUT cache holds
    m = wl["max_admit"]
    Qw = system.query_pool(run, wl, cfg, ckey, m * (m + 1) // 2, 4)
    for b in range(1, m + 1):
        tickets = [fe.submit("bench", q)
                   for q in Qw[b * (b - 1) // 2:b * (b + 1) // 2]]
        while not all(t.done for t in tickets):
            fe.poll()
            time.sleep(wl["admission_ms"] * 1e-4)
    del ns
    return fe, ckey


def window(run: harness.Run, fe, queries: np.ndarray, times: np.ndarray,
           *, grace_s: float):
    """Drive one open-loop window; returns (tickets, lateness s, poll s)."""
    tickets, late = [], []
    poll_s = 0.0
    nxt, n = 0, len(times)
    done = 0
    t0 = time.monotonic()
    end = t0 + times[-1] + grace_s
    while done < n:
        now = time.monotonic()
        while nxt < n and t0 + times[nxt] <= now:
            due = t0 + times[nxt]
            with run.span("bench.submit"):
                tickets.append(fe.submit("bench", queries[nxt],
                                         arrival=due))
            late.append(time.monotonic() - due)
            nxt += 1
        p0 = time.perf_counter()
        with run.span("bench.poll"):
            done += len(fe.poll())
        poll_s += time.perf_counter() - p0
        if time.monotonic() > end:
            break
        wake = [t0 + times[nxt]] if nxt < n else []
        deadline = fe.next_deadline()
        if deadline is not None:
            wake.append(deadline)
        if wake:
            pause = min(wake) - time.monotonic()
            if pause > 0:
                time.sleep(pause)
    return tickets, np.asarray(late), poll_s


def run(run: harness.Run) -> harness.Result:
    wl, cfg = run.workload, run.config
    fe, ckey = setup(run)
    times = arrival_times(wl["rate"], run.seconds, run.seed)
    queries = system.query_pool(run, wl, cfg, ckey, len(times), 2)
    warm = system.query_pool(run, wl, cfg, ckey, wl["warm_requests"], 3)
    window(run, fe, warm, arrival_times(wl["rate"],
                                        wl["warm_requests"] / wl["rate"],
                                        run.seed), grace_s=10.0)

    run.open_window()
    tickets, late, poll_s = window(run, fe, queries, times,
                                   grace_s=wl["grace_s"])
    run.close_window()
    mem = harness.peak_bytes(run.devices)
    reduced = run.reduce_trace() if run.trace else None

    answered = [t for t in tickets if t.done]
    failed = len(times) - len(answered)
    lat = np.array([t.latency_ms for t in answered]
                   + [np.inf] * failed)
    pct = {q: float(min(np.percentile(lat, q), 1e9))
           for q in (50, 90, 95, 99)}

    rng = np.random.default_rng(run.seed)
    pick = rng.choice(len(answered), size=min(wl["check_queries"],
                                              len(answered)), replace=False)
    ns = fe.namespaces.get("bench")
    checks = system.served_check(
        ns.engine.state, np.stack([answered[i].query for i in pick]),
        np.stack([np.asarray(answered[i].result.scores) for i in pick]),
        np.stack([np.asarray(answered[i].result.ids) for i in pick]),
        nprobe=wl["nprobe"], k=wl["k"], limits=wl["limits"])

    run.values.update(
        window_s=run.window_s, requests=len(times), poll_s=poll_s,
        wait_ms=[t.waited_ms for t in answered],
        late_ms=1e3 * late)
    st = fe.stats()
    flushes: dict = {}
    for t in answered:
        flushes[t.completed] = flushes.get(t.completed, 0) + 1
    info = {"requests": len(times), "answered": len(answered),
            "largest_flush": max(flushes.values(), default=0),
            "window_s": run.window_s,
            "compiles_in_window": run.compile_in_window,
            "latency_ms_p95": pct[95], "latency_ms_p99": pct[99],
            "batches": st["batches"], "late_ms_p99":
                float(np.percentile(1e3 * late, 99)) if len(late) else 0.0,
            "late_ms_max": 1e3 * float(late.max()) if len(late) else 0.0,
            "setup_s": run.setup_s}
    return harness.Result(
        correct=failed == 0, attempted=len(times), failed=failed,
        end_to_end={"serve_p50_ms": pct[50], "serve_p90_ms": pct[90]},
        checks=checks, memory_peak_bytes=mem, reduced=reduced, info=info)

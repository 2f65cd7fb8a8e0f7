"""Traffic ``serve_bulk``: offline candidate generation, a closed loop of
distinct query batches through ``search.Engine``.

Set-up, from the seed: the configuration's corpus (``bench/corpus.py``),
an IVF-PQ index over it (``search.make("ivf")``, fused refresh), an Engine
with ``max_bucket`` = the batch, and a pool of distinct queries larger than
the Engine's LUT cache (so no query ever hits it). ``warm_batches`` batches
of other queries compile and warm the path. The window submits one batch,
collects it, and submits the next, for ``--seconds``.

``serve_qps`` is the queries whose results came back in the window over
the window. After it, a sample of the served queries drawn from the seed
is searched again by the plain float32 reference over the same index.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, system, work


def run(run: harness.Run) -> harness.Result:
    from repro import search

    wl, cfg = run.workload, run.config
    searcher, state, ckey = system.serving_index(run, wl, cfg)
    engine = search.Engine(searcher, state, k=wl["k"],
                           max_bucket=wl["batch"])
    b, P = wl["batch"], wl["pool_batches"]
    pool = system.query_pool(run, wl, cfg, ckey, b * P, 2).reshape(P, b, -1)
    warm = system.query_pool(run, wl, cfg, ckey, b * wl["warm_batches"],
                             3).reshape(wl["warm_batches"], b, -1)
    for Q in warm:
        engine.collect(engine.submit(Q))

    served = []
    run.open_window()
    while True:
        Q = pool[len(served) % P]
        with run.span("bench.submit"):
            pending = engine.submit(Q)
        with run.span("bench.collect"):
            res = engine.collect(pending)
        served.append(res)
        if time.perf_counter() - run.t_open >= run.seconds:
            break
    run.close_window()
    mem = harness.peak_bytes(run.devices)
    reduced = run.reduce_trace() if run.trace else None

    n_batches = len(served)
    rng = np.random.default_rng(run.seed)
    pick = rng.choice(n_batches * b, size=min(wl["check_queries"],
                                              n_batches * b), replace=False)
    bi, qi = pick // b, pick % b
    Q = pool[bi % P, qi]
    scores = np.stack([np.asarray(served[i].scores[j]) for i, j in
                       zip(bi, qi)])
    ids = np.stack([np.asarray(served[i].ids[j]) for i, j in zip(bi, qi)])
    checks = system.served_check(engine.state, Q, scores, ids,
                                 nprobe=wl["nprobe"], k=wl["k"],
                                 limits=wl["limits"])

    if run.trace:
        _work(run, engine.state, pool, n_batches, wl, cfg)
    st = engine.stats()
    run.values.update(batches=n_batches, window_s=run.window_s)
    info = {"batches": n_batches, "window_s": run.window_s,
            "compiles_in_window": run.compile_in_window,
            "lut_hit_rate": st["lut_hit_rate"],
            "max_blocks": st["searcher"]["max_blocks"],
            "setup_s": run.setup_s}
    return harness.Result(
        correct=n_batches > 0, attempted=n_batches * b, failed=0,
        end_to_end={"serve_qps": n_batches * b / run.window_s},
        checks=checks, memory_peak_bytes=mem, reduced=reduced, info=info)


def _work(run, state, pool, n_batches, wl, cfg) -> None:
    """Scan and whole-step work of every batch served in the window, from
    the lists the benchmark itself probes."""
    ix = state.index
    centroids = np.asarray(ix.coarse.centroids)
    R0 = np.asarray(ix.R)
    live = work.live_rows_per_list(np.asarray(ix.ids),
                                   np.asarray(ix.list_offsets))
    dp = int(ix.codes.shape[1])
    K = cfg["index"]["num_codewords"]
    P = len(pool)
    per_pool = {}
    for i in range(min(n_batches, P)):
        lists = work.probe_lists(pool[i] @ R0, centroids, wl["nprobe"])
        per_pool[i] = (
            work.scan(lists, live, code_width=dp, codewords=K, k=wl["k"]),
            work.search_batch(lists, live, dim=cfg["index"]["dim"],
                              num_lists=wl["lists"], code_width=dp,
                              codewords=K, k=wl["k"]))
    pk = run.peaks
    scan_s = step_s = 0.0
    for i in range(n_batches):
        (fs, bs), (fw, bw) = per_pool[i % P]
        scan_s += max(fs / pk.bf16_flops, bs / pk.hbm_bytes_per_s)
        step_s += max(fw / pk.bf16_flops, bw / pk.hbm_bytes_per_s)
    run.values.update(scan_roofline_s=scan_s, step_roofline_s=step_s)

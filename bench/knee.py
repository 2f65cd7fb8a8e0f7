#!/usr/bin/env python3
"""Sweep the open-loop arrival rate of a ``serve_open`` cell after one
set-up, to find the highest rate it sustains (the knee).

    python3 bench/knee.py --workload paper-serve-open --seed 7 \
        --seconds 8 --rates 100,200,400,800

For each rate: one open-loop window of ``--seconds`` (the cell's own
traffic at that rate, fresh queries), then p50 and p99 latency from the
scheduled send time, the requests still unanswered when the window's
arrivals ended (the backlog), and how late the generator ran. The cell's
fixed rate is set once from this, at about 0.8 of the knee, and written
into its workload file; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from bench import harness, system  # noqa: E402
from bench.traffic import serve_open  # noqa: E402


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = {w["name"]: w for w in harness.benchmark()["workloads"]}[
        args.workload]
    device, peaks = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    run = harness.Run(name=cell["name"],
                      workload=harness.workload(cell["name"]),
                      config=harness.config(cell["config"]), seed=args.seed,
                      seconds=args.seconds, trace=False,
                      t_start=time.perf_counter(), devices=[device],
                      peaks=peaks)
    fe, ckey = serve_open.setup(run)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        times = serve_open.arrival_times(rate, args.seconds, run.seed)
        queries = system.query_pool(run, run.workload, run.config, ckey,
                                    len(times), 100 + i)
        tickets, late, _ = serve_open.window(run, fe, queries, times,
                                             grace_s=30.0)
        end = tickets[0].arrival + times[-1]
        done = [t for t in tickets if t.done]
        lat = np.array([t.latency_ms for t in done])
        print(json.dumps({
            "rate": rate, "requests": len(times), "answered": len(done),
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "backlog_at_end": int(sum(1 for t in tickets
                                      if not t.done or t.completed > end)),
            "late_ms_p99": float(np.percentile(1e3 * late, 99))}),
            flush=True)


if __name__ == "__main__":
    main()

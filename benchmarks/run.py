"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure (+ kernel microbench + roofline
aggregation). Prints ``name,us_per_call,derived`` CSV while running, and
emits one merged ``BENCH_<fast|full>.json`` run record through the
``repro.obs`` trajectory writer — every section's results and claim checks
in one schema-valid file, appended to the destination trajectory so perf
history is pinned rather than scrolled away.

Destination resolution: ``--out DIR`` > ``$REPRO_BENCH_DIR`` > (for
``--fast`` only) the repo's ``benchmarks/`` directory — the committed
trajectory a fast run extends by default. A full run without an explicit
destination prints only. Use ``--only fig2a,fig4`` for a subset.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def resolve_bench_dir(cli_out: str | None,
                      fast_default: bool = False) -> str | None:
    """--out > $REPRO_BENCH_DIR > (--fast) the tracked benchmarks/ dir."""
    if cli_out:
        return cli_out
    env = os.environ.get("REPRO_BENCH_DIR")
    if env:
        return env
    return _BENCH_DIR if fast_default else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2a,fig2bc,table1,fig4,ivf,churn,"
                         "train_e2e,"
                         "serve,kernels,roofline")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--devices", type=int, default=1,
                    help="ivf/churn sections: run the sharded cells on N "
                         "devices (the first N chips on a TPU; forced host "
                         "devices in a subprocess on the CPU)")
    ap.add_argument("--out", default=None,
                    help="BENCH_*.json destination dir (default "
                         "$REPRO_BENCH_DIR; --fast falls back to the "
                         "tracked benchmarks/ trajectory)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch import compile_cache
    compile_cache.enable()

    def want(name: str) -> bool:
        return only is None or name in only

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = []
    sections: dict = {}
    checks_all: dict = {}

    def book(name: str, results, checks: dict | None = None) -> None:
        sections[name] = results
        for k, v in (checks or {}).items():
            key = f"{name}/{k}"
            checks_all[key] = bool(v)
            if not v:
                failures.append(key)

    if want("train_e2e"):
        # overlapped end-to-end training: async prefetch + live refresh +
        # background compaction with staleness re-encode — step overhead,
        # hidden-pause p99, and recall-vs-rebuild pinned. Runs FIRST: the
        # p99 pins compare an off-thread pack against an inline one, and a
        # heap pre-warmed by other sections skews the two arms differently
        # (standalone conditions are the calibrated ones).
        from benchmarks import train_e2e
        if args.fast:
            res, checks = train_e2e.run(
                n=32000, dim=32, queries=64, lists=32, subspaces=8,
                codewords=32, steps=54, batch=8192, nprobe=8,
                refresh_every=6, compact_every=3, reencode_rows=2048,
                staging_rows=512, churn_batch=32, churn_every=3,
                warmup=12, probe_every=6)
        else:
            res, checks = train_e2e.run()
        book("train_e2e", res, checks)

    if want("fig2a"):
        from benchmarks import fig2a_convergence
        res, checks = fig2a_convergence.run(
            num=2048 if args.fast else 4096,
            iters=15 if args.fast else 25)
        book("fig2a", res, checks)

    if want("fig2bc"):
        from benchmarks import fig2bc_stability
        out, stable = fig2bc_stability.run(
            num=2048 if args.fast else 4096,
            runs=3 if args.fast else 5,
            iters=12 if args.fast else 20)
        book("fig2bc", out, {"stability": stable})

    if want("table1"):
        from benchmarks import fig3_table1_e2e
        res, checks = fig3_table1_e2e.run(
            steps=60 if args.fast else 250,
            warmup=30 if args.fast else 40)
        book("table1", res, checks)

    if want("fig4"):
        from benchmarks import fig4_runtime
        out, checks = fig4_runtime.run(
            dims=(64, 128, 256) if args.fast else (64, 128, 256, 512))
        book("fig4", out, checks)

    if want("ivf"):
        # searcher-registry sweep: exact vs flat_adc vs ivf on one harness
        from benchmarks import ivf_recall_qps
        res, checks = ivf_recall_qps.run(
            n=20_000 if args.fast else 100_000,
            queries=64 if args.fast else 256,
            lists=64 if args.fast else 256,
            depths=(1, 2),
            devices=args.devices)
        book("ivf", res, checks)

    if want("churn"):
        # live mutations under query load: staged adds, in-kernel
        # tombstones, compaction — zero recompiles, recall pinned
        from benchmarks import churn as churn_bench
        if args.fast:
            res, checks = churn_bench.run(
                n=8000, dim=32, queries=64, lists=32, subspaces=8,
                codewords=32, steps=6, batch=64, nprobe=8,
                staging_rows=512, devices=args.devices)
        else:
            res, checks = churn_bench.run(devices=args.devices)
        book("churn", res, checks)

    if want("serve"):
        # multi-tenant serving under Poisson load: continuous batching +
        # SLO-adaptive nprobe vs fixed baselines, isolation pinned
        from benchmarks import serve_load
        if args.fast:
            res, checks = serve_load.run(
                n=8000, dim=32, lists=128, subspaces=16, codewords=64,
                ladder=(2, 4, 16), requests=600, max_admit=8,
                refresh_every=150)
        else:
            res, checks = serve_load.run()
        book("serve", res, checks)

    if want("kernels"):
        from benchmarks import kernels_micro
        results = kernels_micro.run()
        book("kernels", results,
             {k: v["ok"] for k, v in results.items()})

    if want("roofline"):
        from benchmarks import roofline_table
        res = roofline_table.run()
        book("roofline", res)

    elapsed = time.time() - t0
    print(f"# total {elapsed:.1f}s; claim-check failures: "
          f"{failures if failures else 'none'}")

    out_dir = resolve_bench_dir(args.out, fast_default=args.fast)
    if out_dir and sections:
        from repro import obs

        name = "fast" if args.fast else "full"
        path = obs.write_bench(
            out_dir, name, sections=sections, checks=checks_all,
            config=dict(only=sorted(only) if only else None,
                        fast=args.fast, devices=args.devices,
                        elapsed_s=elapsed))
        errs = obs.validate_bench(path)
        print(f"# BENCH written: {path} "
              f"({'schema-valid' if not errs else f'INVALID: {errs}'})")
        if errs:
            sys.exit(1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration and traffic;
``bench/workloads/<cell>.json`` holds its parameters,
``bench/configs/<config>.json`` its sizes, and ``bench/traffic/<traffic>.py``
the driver that sets the system up, drives the measured window and checks
what it served against the plain reference. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, ``breakdown`` (traced
runs) and ``checks`` (each number compared, beside its limit).

Refuses to run, with a non-zero exit and no result, on a device that is
not a TPU in ``bench/peaks.py`` or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = harness.benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"bench: no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    wl = harness.workload(cell["name"])
    cfg = harness.config(cell["config"])
    drv = harness.driver(cell["traffic"])

    devices, peaks = harness.check_device(cell["chips"])
    import jax

    devices = jax.devices()[:cell["chips"]]
    harness.enable_compile_cache()
    run = harness.Run(name=cell["name"], workload=wl, config=cfg,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START,
                      devices=devices, peaks=peaks)
    run.watch_compiles()
    result = drv.run(run)

    device = harness.device_info(run)
    device["memory_peak_bytes"] = result.memory_peak_bytes
    line = {"correct": bool(result.correct and harness.checks_pass(
                result.checks)),
            "attempted": int(result.attempted), "failed": int(result.failed)}
    if args.trace:
        reduced = result.reduced
        line["metrics"] = harness.per_layer(run, bench, reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        line["device"] = device
        line["breakdown"] = harness.breakdown(reduced)
        result.info["device_ops"] = reduced.top_ops(25)
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for name, value in result.end_to_end.items():
            metrics[name] = {"value": float(value), "unit": units[name]}
        line["metrics"] = metrics
        line["device"] = device
    clock = run.compile_clock
    line["info"] = dict(result.info, compile_s=clock.seconds,
                        cache_hits=clock.hits, cache_misses=clock.misses,
                        host=run.host)
    line["checks"] = result.checks
    harness.print_checks(result.checks)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

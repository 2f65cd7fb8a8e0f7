"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the
harness tests: the same drivers, references and checks, on the CPU with the
harness's look for a chip skipped. Widths here are toy sizes; nothing timed
at them is a device number."""
from __future__ import annotations

import time

from bench import harness, peaks

MODEL = {"embed_dim": 32, "tower_dims": [32, 32], "hist_len": 8,
         "scoring": "cosine", "hinge_margin": 0.1,
         "index": {"dim": 32, "num_subspaces": 4, "num_codewords": 16,
                   "distortion_weight": 1.0}}

TRAFFIC = {
    "train_live": {"batch": 64, "embed_chunk": 1000, "warm_steps": 4},
    "serve_bulk": {"batch": 32, "pool_batches": 20, "warm_batches": 1,
                   "check_queries": 40, "k": 10},
    "serve_open": {"rate": 400.0, "warm_requests": 20, "check_queries": 40,
                   "max_admit": 8},
}


def cell(name: str) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json entry, workload, configuration) of a cell, cut."""
    entry = {w["name"]: w for w in harness.benchmark()["workloads"]}[name]
    wl = harness.workload(name)
    cfg = harness.config(entry["config"])
    cfg.update(MODEL, name="tiny-" + cfg["name"], item_vocab=3000)
    wl.update(lists=16, nprobe=4, train_size=2048,
              **TRAFFIC[entry["traffic"]])
    return entry, wl, cfg


def run(name: str, *, seed: int = 2**33 + 5, seconds: float = 0.3,
        trace: bool = False):
    """Drive one tiny run of a cell on whatever devices JAX has; returns
    (run, result)."""
    import jax

    entry, wl, cfg = cell(name)
    r = harness.Run(name=name, workload=wl, config=cfg, seed=seed,
                    seconds=seconds, trace=trace,
                    t_start=time.perf_counter(), devices=jax.devices(),
                    peaks=peaks.TABLE["TPU v5 lite"])
    r.watch_compiles()
    return r, harness.driver(entry["traffic"]).run(r)


def correct(result) -> bool:
    """The verdict ``run.py`` prints as ``correct``."""
    return bool(result.correct and harness.checks_pass(result.checks))

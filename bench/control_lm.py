#!/usr/bin/env python3
"""Readings the ``train_lm`` cell's limits are set from, on the chip at the
cell's own size: the program's sound runs (lower readings), the planted
faults and the control (upper readings).

    python3 bench/control_lm.py --workload dsv2lite-train-8k --seeds 1,2

For each seed, in this one process: the cell's own timed path
(``program``); the same path with each fault of ``bench/lm_faults.py``
planted (``capacity_dispatch``, ``half_batch``, ``renormalised_gates``,
``unrotated_latent``), each judged by the run's own comparison against the
float32 reference; then ``control``: the reference computed a precision
below the configuration's bfloat16 (float8 operands) on the same batches,
against the float32 reference. The batches are pure functions of the seed,
so the float32 reference is computed once per seed. Prints one JSON line
per seed and reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def readings(name: str, wl: dict, cfg: dict, seed: int, seconds: float,
             devices, peaks, faults=None) -> list[dict]:
    """The readings of one seed (module docstring); ``faults`` maps a
    reading's name to a function of a pytest ``MonkeyPatch`` (default: all
    of ``lm_faults.PLANTED``)."""
    import jax
    import jax.numpy as jnp
    from pytest import MonkeyPatch

    from bench import lm_faults
    from bench.reference import deepseek_v2
    from bench.traffic import train_lm

    faults = lm_faults.PLANTED if faults is None else faults
    first_steps = deepseek_v2.first_steps
    memo: dict = {}

    def reference(seed_, cfg_, opt, batches, dtype=jnp.float32):
        same = "batches" in memo and all(
            np.array_equal(a, b) for x, y in zip(memo["batches"], batches)
            for a, b in zip(x, y))
        if not same:
            memo.update(batches=batches, ref=first_steps(seed_, cfg_, opt,
                                                         batches, dtype))
        return memo["ref"]

    out = []
    for reading, plant in [("program", None)] + sorted(faults.items()):
        jax.clear_caches()     # a planted fault is seen only by a new trace
        gc.collect()
        run = harness.Run(name=name, workload=wl, config=cfg, seed=seed,
                          seconds=seconds, trace=False,
                          t_start=time.perf_counter(), devices=devices,
                          peaks=peaks)
        run.watch_compiles()
        with MonkeyPatch.context() as mp:
            if plant is not None:
                plant(mp)
            mp.setattr(deepseek_v2, "first_steps", reference)
            res = harness.driver("train_lm").run(run)
        out.append(dict(
            reading=reading,
            correct=bool(res.correct and harness.checks_pass(res.checks)),
            train_step_ms=res.end_to_end["train_step_ms"],
            memory_peak_bytes=res.memory_peak_bytes,
            **{k: res.info[k] for k in ("steps", "loss_gap",
                                        "grad_gap_leaf", "change_gap_leaf",
                                        "local_tokens", "max_expert_load")},
            **{k: c["value"] for k, c in res.checks.items()}))
        del res
    jax.clear_caches()
    got = first_steps(run.seed, cfg, wl["optimizer"], memo["batches"],
                      dtype=jnp.float8_e4m3fn)
    out.append(dict(reading="control", **train_lm.gaps(got, memo["ref"])))
    return out


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="window of each run of the timed path")
    args = ap.parse_args()
    cell = {w["name"]: w for w in harness.benchmark()["workloads"]}[
        args.workload]
    device, peaks = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for r in readings(cell["name"], harness.workload(cell["name"]),
                          harness.config(cell["config"]), seed,
                          args.seconds, [device], peaks):
            print(json.dumps(dict(workload=cell["name"], seed=seed, **r)),
                  flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Readings a cell's limits are set from, on the chip at the cell's own
size: the control and the planted faults (upper readings) and, with
``--program``, the program's own sound runs (lower readings).

    python3 bench/control.py --workload <cell> --seeds 101,102,103
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --program 10

For each seed the cell's own set-up is made (the same index, batches and
queries a run would have) and the reference is put in the program's place:

* ``control``: the reference computed in bfloat16, the precision below the
  float32 the configuration states (``reference/*.py``, ``lowered`` /
  ``dtype=bfloat16``), compared with the float32 reference by the run's
  own comparison;
* ``half_batch`` (training cells): the reference fed the first half of
  each batch, its mean taken over that half, against the full batch;
* ``answer_altered`` (training cells): the reference's answers after the
  live refresh with each query's first id replaced by the next item's.

With ``--program <seconds>`` each seed instead drives the cell's own timed
path for that many seconds, one seed after another in this one process,
and prints the numbers it compared (``program``). For training cells the
refresh faults follow, planted in the reference put in the program's
place and read from the Givens deltas of that same run:
``refresh_unmasked`` (the within-subspace mask dropped: W = I),
``refresh_mask_inverted`` (W made of the cross-subspace pairs),
``refresh_unchanged`` (no delta applied) and ``refresh_bf16`` (the
reference's matrices rounded to bfloat16).

A state left unchanged reads 1 by the training comparison and needs no
run. Prints one JSON line per seed and reading. The benchmark's own runs
never run this.
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

from bench import harness, system  # noqa: E402
from bench.reference import ivfpq  # noqa: E402


def _search_readings(state, Q, wl, fault: bool) -> list[dict]:
    """The bfloat16 reference's answers in the program's place; for
    training cells also the float32 reference's answers with each query's
    first answer altered (the next item id, its score kept)."""
    index = ivfpq.Index.from_state(state)
    scores, ids = index.search(Q, nprobe=wl["nprobe"], k=wl["k"],
                               lowered=True)
    out = [dict(reading="control", **ivfpq.compare(
        index, Q, scores, ids, nprobe=wl["nprobe"], k=wl["k"]))]
    if fault:
        scores, ids = index.search(Q, nprobe=wl["nprobe"], k=wl["k"])
        ids = ids.copy()
        ids[:, 0] += 1
        out.append(dict(reading="answer_altered", **ivfpq.compare(
            index, Q, scores, ids, nprobe=wl["nprobe"], k=wl["k"])))
    return out


def train_readings(run: harness.Run) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from bench.reference import twotower
    from bench.traffic import train_live
    from repro import search
    from repro.data import pipeline as pipe_lib
    from repro.launch import train as train_lib

    wl, cfg = run.workload, run.config
    model = system.twotower_config(cfg)
    init, embed, _, _, queries = train_live._fns(model, wl["embed_chunk"])
    key = jax.random.PRNGKey(run.seed)
    params = init(key)
    corpus = embed(params)
    searcher = search.make("ivf")
    state = searcher.build(jax.random.fold_in(key, 7), corpus,
                           params["index"].R, system.search_config(cfg, wl))
    Q = np.asarray(queries(jax.random.fold_in(key, 11), corpus))
    del params, corpus
    out = _search_readings(state, Q, wl, fault=True)
    del state

    pipe = pipe_lib.Pipeline(train_lib.make_batch_fn(model, "recsys",
                                                     wl["batch"]),
                             seed=run.seed)
    batches = [tuple(np.asarray(x) for x in next(pipe))
               for _ in range(wl["check_steps"])]
    model_cfg = system.model_dict(cfg)
    ref = twotower.first_steps(run.seed, model_cfg, wl["optimizer"],
                               batches)
    half = [(h[:len(h) // 2], p[:len(p) // 2]) for h, p in batches]
    for name, kw in (("control", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"batches": half})):
        got = twotower.first_steps(
            run.seed, model_cfg, wl["optimizer"],
            kw.get("batches", batches), dtype=kw.get("dtype", jnp.float32))
        out.append(dict(reading=name, **train_live.gaps(got, ref)))
    return out


def refresh_faults(R0, deltas, sub: int) -> list[dict]:
    """``refresh_gap`` of live matrices made wrong in the ways a fused
    refresh can go wrong, against the reference's (module docstring)."""
    ref = ivfpq.live_transform(R0, deltas, sub)
    n = len(ref["delta"])
    eye = np.eye(n)
    inside = ivfpq.within(sub)
    cross = ivfpq.pair_rotations(n, deltas,
                                 keep=lambda pi, pj: ~inside(pi, pj))
    faults = {
        "refresh_unmasked": {"rot": ref["rot"], "wacc": eye,
                             "qdelta": ref["delta"]},
        "refresh_mask_inverted": {"rot": ref["rot"], "wacc": cross,
                                  "qdelta": ref["delta"] @ cross.T},
        "refresh_unchanged": {"rot": np.asarray(R0), "wacc": eye,
                              "qdelta": eye},
        "refresh_bf16": {k: ivfpq.bf16(ref[k])
                         for k in ("rot", "wacc", "qdelta")},
    }
    return [dict(reading=name, refresh_gap=ivfpq.refresh_gap(got, ref))
            for name, got in faults.items()]


def program_readings(run: harness.Run, traffic: str) -> list[dict]:
    """The numbers one run of the cell's own timed path compared, and for
    a training cell the refresh faults read from that run's deltas."""
    res = harness.driver(traffic).run(run)
    info = {k: res.info[k] for k in ("steps", "batches", "answered",
                                     "deltas", "delta_scale", "setup_s")
            if k in res.info}
    out = [dict(reading="program", correct=bool(
        res.correct and harness.checks_pass(res.checks)), **info,
        **{k: c["value"] for k, c in res.checks.items()})]
    if "deltas" in run.values:
        ix = run.config["index"]
        out += refresh_faults(run.values["R0"], run.values["deltas"],
                              ix["dim"] // ix["num_subspaces"])
    return out


def serve_readings(run: harness.Run) -> list[dict]:
    wl, cfg = run.workload, run.config
    searcher, state, ckey = system.serving_index(run, wl, cfg)
    Q = system.query_pool(run, wl, cfg, ckey, wl["check_queries"], 2)
    return _search_readings(state, Q, wl, fault=False)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=float, default=None,
                    help="seconds of the cell's own timed path per seed")
    args = ap.parse_args()
    cell = {w["name"]: w for w in harness.benchmark()["workloads"]}[
        args.workload]
    device, peaks = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.collect()
        run = harness.Run(name=cell["name"],
                          workload=harness.workload(cell["name"]),
                          config=harness.config(cell["config"]), seed=seed,
                          seconds=args.program or 0, trace=False,
                          t_start=time.perf_counter(), devices=[device],
                          peaks=peaks)
        run.compile_clock = clock
        if args.program:
            readings = program_readings(run, cell["traffic"])
        elif cell["traffic"] == "train_live":
            readings = train_readings(run)
        else:
            readings = serve_readings(run)
        for r in readings:
            print(json.dumps(dict(workload=cell["name"], seed=seed, **r)),
                  flush=True)


if __name__ == "__main__":
    main()

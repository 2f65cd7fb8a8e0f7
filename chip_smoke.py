#!/usr/bin/env python3
"""Smoke run of the system's main path on TPU, through its normal entry points.

One chip (the default), at the widths of ``configs/paper_twotower.py``:

  A  device check: JAX's default device must be a TPU.
  B  train: ``launch.train.train("paper-twotower", full=True)`` — the paper's
     two-tower model whose index layer learns R by GCD — for a few steps.
  C  build and serve: the item-tower output of every item, under the learned
     R, becomes a ``search.make("ivf")`` index (64×256 PQ, 2048 lists, the
     Pallas scan and LUT kernels); ragged request batches go through
     ``search.Engine`` and are checked against the same state on the jnp
     reference and against exact MIPS.
  D  live refresh: more trainer steps drive that Engine through
     ``pipeline.LiveIndexLoop``; serving again must not recompile.

``--chips 4`` runs only the sharded phase: ``ivf_sharded`` over a four-chip
mesh against its single-device twin on the same codes — results identical,
codes spread over all four chips.

Every check raises; the exit code is non-zero on any failure. The last line
of stdout is one JSON object naming the device.

    python3 chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "paper-twotower"
#: rows of the embedding table one v5e trains. The TPU compiler places the
#: train step (table, Adam moments, dense table gradient) at 17.67 GB for
#: the published 1,541,673 rows and at 16.05 GB for 1,400,000, against the
#: chip's 15.75 GB; 1,200,000 leaves about 2 GB for the live index and the
#: serving state beside it. Widths are never cut.
ONE_CHIP_VOCAB = 1_200_000
TRAIN_BATCH = 4096           # the config's 65,536 is a multi-chip batch
TRAIN_STEPS = 4
LIVE_STEPS = 4
REFRESH_EVERY = 2
K = 10
SERVE_BATCHES = (37, 64, 5, 100)      # ragged request sizes
ITEM_CHUNK = 65536                    # item-tower rows per device step


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (recorded by the jit machinery, not by this script)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses

    def since(self, mark) -> dict:
        s, h, m = mark
        return dict(compile_s=round(self.seconds - s, 2),
                    cache_hits=self.hits - h, cache_misses=self.misses - m)


def _memory_stat(key: str) -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get(key, -1))


def peak_bytes() -> int:
    return _memory_stat("peak_bytes_in_use")


def bytes_in_use() -> int:
    return _memory_stat("bytes_in_use")


def arch_id(full: bool) -> str:
    """The arch the trainer runs: the paper config with the one-chip
    embedding-table cut, registered beside it (``full=False``: the smoke
    config, for a CPU rehearsal)."""
    from repro import configs

    if not full:
        return ARCH
    spec = configs.get(ARCH)
    cut = ARCH + "-1chip"
    configs.REGISTRY[cut] = spec._replace(
        arch_id=cut,
        make_config=lambda: spec.make_config()._replace(
            item_vocab=ONE_CHIP_VOCAB))
    return cut


def phase_train(clock, *, seed: int, full: bool):
    """B: the trainer's own entry point; returns (params, model config)."""
    import jax
    from repro import configs, obs, rotations
    from repro.configs.base import RECSYS_SHAPES
    from repro.launch import train as train_lib

    aid = arch_id(full)
    spec = configs.get(aid)
    cfg = spec.make_config() if full else spec.make_smoke()
    if full:
        log("B", cut="item_vocab",
            published=configs.get(ARCH).make_config().item_vocab,
            run=cfg.item_vocab,
            batch=f"{TRAIN_BATCH} (published "
                  f"{RECSYS_SHAPES['train_batch'].params['batch']})")
    log("B", embed=cfg.embed_dim, towers=cfg.tower_dims, hist=cfg.hist_len,
        index=f"{cfg.index.num_subspaces}x{cfg.index.num_codewords}",
        item_vocab=cfg.item_vocab)
    mark, t0 = clock.mark(), time.perf_counter()
    steps_ms = obs.default_registry().distribution("train.step_ms")
    state, losses = train_lib.train(
        aid, TRAIN_STEPS, TRAIN_BATCH, None, full=full, seed=seed,
        rotation="gcd_greedy", prefetch=True, log_every=TRAIN_STEPS)
    ms = steps_ms.window_values()[-TRAIN_STEPS:]
    R = state.params["index"].R
    orth = float(rotations.orthogonality_error(R))
    # step times end in the trainer's host read of the loss; the first
    # includes its compile
    log("B", seconds=round(time.perf_counter() - t0, 2), **clock.since(mark),
        first_step_ms=round(ms[0], 1),
        steady_step_ms=round(float(np.median(ms[1:])), 2),
        loss=f"{losses[0]:.6f}->{losses[-1]:.6f}",
        orthogonality_error=f"{orth:.3e}", peak_bytes=peak_bytes())
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite training loss: {losses}")
    if not orth < 1e-4:
        raise AssertionError(f"learned R is not orthogonal: {orth:.3e}")
    return state.params, cfg


def _unit(x):
    import jax.numpy as jnp

    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def embed(params, cfg, *, seed: int, nq: int):
    """C, first half: the served queries (user tower over random histories),
    every item's tower output (cosine scoring: unit rows) under the trained
    params, and the exact top-K of each query, merged tile by tile as the
    corpus is produced — no second resident copy."""
    import jax
    import jax.numpy as jnp
    from repro.models import recsys

    hist = jax.random.randint(jax.random.PRNGKey(seed + 100),
                              (nq, cfg.hist_len), 0, cfg.item_vocab)
    Q = _unit(recsys.user_tower(params, hist, cfg))

    @jax.jit
    def items(p, ids):
        return _unit(recsys.item_tower(p, ids, cfg)[0])

    @jax.jit
    def merge(best_s, best_i, Q, v, ids):
        s = jnp.dot(Q, v.T, precision=jax.lax.Precision.HIGHEST)
        cs = jnp.concatenate([best_s, s], axis=1)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)], 1)
        top, pos = jax.lax.top_k(cs, K)
        return top, jnp.take_along_axis(ci, pos, axis=1)

    best_s = jnp.full((nq, K), -jnp.inf)
    best_i = jnp.full((nq, K), -1, jnp.int32)
    chunks = []
    for start in range(0, cfg.item_vocab, ITEM_CHUNK):
        ids = jnp.arange(start, min(start + ITEM_CHUNK, cfg.item_vocab),
                         dtype=jnp.int32)
        v = items(params, ids)
        best_s, best_i = merge(best_s, best_i, Q, v, ids)
        chunks.append(v)
    corpus = jnp.concatenate(chunks)
    del chunks
    return Q, corpus, np.asarray(best_i)


def search_config(cfg, *, full: bool):
    from repro import search

    return search.SearchConfig(
        subspaces=cfg.index.num_subspaces, codewords=cfg.index.num_codewords,
        num_lists=2048 if full else 32, nprobe=32 if full else 8,
        block_size=128, train_size=65536 if full else 2048,
        fused_refresh=True)


def agreement(res, ref) -> tuple[float, float]:
    """(share of queries whose top-K id sets agree, max |score| gap)."""
    a, b = np.asarray(res.ids), np.asarray(ref.ids)
    same = float(np.mean([set(x) == set(y) for x, y in zip(a, b)]))
    gap = float(np.max(np.abs(np.asarray(res.scores)
                              - np.asarray(ref.scores))))
    return same, gap


def serve(engine, ref_engine, Q, truth, phase: str) -> None:
    """Serve every ragged batch through both Engines; check agreement over
    all the queries served."""
    from repro.metrics import recall_at_k
    from repro.search import SearchResult

    got, want = [], []
    off = 0
    for b in SERVE_BATCHES:
        q = Q[off:off + b]
        off += b
        got.append(engine.search(q))
        want.append(ref_engine.search(q))
    res, ref = (SearchResult(*(np.concatenate([np.asarray(x) for x in col])
                               for col in zip(*rs)))
                for rs in (got, want))
    same, gap = agreement(res, ref)
    close = np.allclose(res.scores, ref.scores, rtol=1e-4, atol=1e-4)
    log(phase, batches=list(SERVE_BATCHES),
        kernel_vs_reference_top10_agree=round(same, 4),
        max_score_gap=f"{gap:.3e}",
        recall_at_10_vs_exact=round(float(recall_at_k(res.ids, truth)), 4),
        compiles=engine.stats()["compiles"])
    if same < 0.99:
        raise AssertionError(f"kernel and reference top-{K} agree on only "
                             f"{same:.3f} of queries")
    if not close:
        raise AssertionError(f"kernel and reference scores differ by {gap}")


def phase_build(Q, corpus, R, cfg, *, seed: int, full: bool, on_chip: bool):
    """C, second half: build the IVF index over the corpus and put the
    kernel and reference Engines on it. Returns (engine, reference
    engine)."""
    import jax
    from repro import churn, search

    t0 = time.perf_counter()
    searcher = search.make("ivf")
    state = searcher.build(jax.random.PRNGKey(seed + 1), corpus, R,
                           search_config(cfg, full=full))
    jax.block_until_ready(state.index.codes)
    st = searcher.stats(state)
    log("C", build_s=round(time.perf_counter() - t0, 2), rows=st["rows"],
        lists=st["num_lists"], max_blocks=st["max_blocks"],
        nprobe=st["nprobe"], use_kernel=st["use_kernel"],
        peak_bytes=peak_bytes())
    if on_chip and not st["use_kernel"]:
        raise AssertionError("the ivf state does not scan with the kernels")

    engine = search.Engine(searcher, state, k=K)
    # live-churn wiring (staging buffer + its flat-ADC side pass), installed
    # before the first search as benchmarks/train_e2e.py does
    churn.ChurnController(engine, staging_rows=1024)
    ref_engine = search.Engine(
        searcher, dataclasses.replace(engine.state, use_kernel=False), k=K,
        lut_cache_rows=0)
    if on_chip:
        QR = searcher.rotate_queries(engine.state, Q[:8])
        lut = searcher.luts(engine.state, QR)
        for name, fn, args in (
                ("lut_build", searcher.luts, (engine.state, QR)),
                ("scan", lambda s, q, t: searcher.search_prepared(
                    s, q, t, k=K), (engine.state, QR, lut))):
            hlo = jax.jit(fn).lower(*args).compile().as_text()
            if "tpu_custom_call" not in hlo:
                raise AssertionError(f"compiled {name} has no Pallas kernel")
        log("C", pallas_kernels_in="lut_build,scan,staging_side_pass")
    return engine, ref_engine


def phase_live(clock, engine, ref_engine, Q, truth, *, seed: int,
               full: bool) -> None:
    """D: trainer steps refresh the live Engine; serving again must not
    recompile. ``train`` starts again from its seed (it resumes only from a
    checkpoint, and none is used), so its Givens deltas land on top of the
    R the index was built with: what is checked is the refresh path, not
    training progress."""
    from repro.launch import train as train_lib
    from repro.pipeline import LiveIndexLoop

    mark, t0 = clock.mark(), time.perf_counter()
    compiles = engine.stats()["compiles"], ref_engine.stats()["compiles"]
    # the two-tower's rotation is the index layer's R
    loop = LiveIndexLoop(engine, refresh_every=REFRESH_EVERY,
                         delta_key="index/R")
    _, losses = train_lib.train(
        arch_id(full), LIVE_STEPS, TRAIN_BATCH, None, full=full, seed=seed,
        rotation="gcd_greedy", prefetch=True, live_loop=loop,
        log_every=LIVE_STEPS)
    ls = loop.stats()
    ref_engine.state = dataclasses.replace(engine.state, use_kernel=False)
    serve(engine, ref_engine, Q, truth, "D")
    after = engine.stats()["compiles"], ref_engine.stats()["compiles"]
    log("D", seconds=round(time.perf_counter() - t0, 2), **clock.since(mark),
        refresh_rounds=ls["refresh_rounds"],
        deltas_applied=ls["deltas_applied"],
        engine_refreshes=engine.stats()["refreshes"],
        engine_compiles=f"{compiles[0]}->{after[0]}",
        loss=f"{losses[-1]:.6f}", peak_bytes=peak_bytes())
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite training loss: {losses}")
    if ls["deltas_applied"] != LIVE_STEPS:
        raise AssertionError(f"{ls['deltas_applied']} deltas reached the "
                             f"index, expected {LIVE_STEPS}")
    if after != compiles:
        raise AssertionError(f"refresh recompiled: {compiles} -> {after}")


def run_one_chip(clock, *, seed: int, full: bool = True,
                 on_chip: bool = True) -> None:
    params, cfg = phase_train(clock, seed=seed, full=full)
    mark, t0 = clock.mark(), time.perf_counter()
    R = params["index"].R
    Q, corpus, truth = embed(params, cfg, seed=seed, nq=sum(SERVE_BATCHES))
    del params
    log("C", embed_s=round(time.perf_counter() - t0, 2),
        corpus=tuple(corpus.shape), queries=tuple(Q.shape))
    engine, ref_engine = phase_build(Q, corpus, R, cfg, seed=seed,
                                     full=full, on_chip=on_chip)
    del corpus                  # serving needs only the index
    serve(engine, ref_engine, Q, truth, "C")
    log("C", seconds=round(time.perf_counter() - t0, 2), **clock.since(mark),
        bytes_in_use=bytes_in_use(), peak_bytes=peak_bytes())
    phase_live(clock, engine, ref_engine, Q, truth, seed=seed, full=full)


def run_four_chips(clock, *, seed: int, n_items: int = 1_541_673,
                   dim: int = 512, nq: int = 256, full: bool = True) -> None:
    """The sharded phase: ``ivf_sharded`` on four chips vs its twin."""
    import jax
    import jax.numpy as jnp
    from repro import rotations, search
    from repro.index import ivf as index_ivf
    from repro.launch.mesh import make_data_mesh

    mark, t0 = clock.mark(), time.perf_counter()
    k_items, k_q, k_rot, k_build = jax.random.split(jax.random.PRNGKey(seed), 4)
    gen = jax.jit(lambda k, n: _unit(jax.random.normal(k, (n, dim))),
                  static_argnums=1)
    X = jnp.concatenate([
        gen(jax.random.fold_in(k_items, i), min(ITEM_CHUNK, n_items - start))
        for i, start in enumerate(range(0, n_items, ITEM_CHUNK))])
    Q = gen(k_q, nq)
    R = rotations.random_rotation(k_rot, dim)
    scfg = search.SearchConfig(
        subspaces=64 if full else 8, codewords=256 if full else 32,
        num_lists=2048 if full else 32, nprobe=32 if full else 8,
        block_size=128, train_size=65536 if full else 2048)
    index = index_ivf.build(k_build, X, R,
                            scfg.ivf_config(), train_size=scfg.train_size)
    del X
    log("S", items=n_items, dim=dim, build_s=round(time.perf_counter() - t0, 2))

    mesh = make_data_mesh(4)
    sharded = search.make("ivf_sharded", mesh=mesh)
    state = search.IVFSharded.attach(index, mesh=mesh, nprobe=scfg.nprobe)
    twin = search.IVF.attach(index, nprobe=scfg.nprobe)
    res = sharded.search(state, Q, k=K)
    ref = search.make("ivf").search(twin, Q, k=K)
    ids_equal = bool(np.array_equal(np.asarray(res.ids), np.asarray(ref.ids)))
    scores_equal = bool(np.array_equal(np.asarray(res.scores),
                                       np.asarray(ref.scores)))
    rows = {str(s.device): int(s.data.shape[1])
            for s in state.codes.addressable_shards}
    live = (np.asarray(state.ids) >= 0).sum(axis=1)
    in_use = {str(d): int((d.memory_stats() or {}).get("bytes_in_use", -1))
              for d in mesh.devices.flat}
    log("S", **clock.since(mark), use_kernel=state.use_kernel,
        ids_identical=ids_equal, scores_identical=scores_equal,
        code_rows_per_device=rows, live_rows_per_shard=live.tolist(),
        bytes_in_use=in_use)
    if not (ids_equal and scores_equal):
        same, gap = agreement(res, ref)
        raise AssertionError(f"sharded != single-device twin: ids agree on "
                             f"{same:.4f} of queries, max score gap {gap}")
    if len(rows) != 4:
        raise AssertionError(f"codes sit on {len(rows)} devices, not 4")
    total = int(live.sum())
    if not all(abs(int(r) - total / 4) <= 0.05 * total / 4 for r in live):
        raise AssertionError(f"codes are not spread evenly: {live.tolist()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX's default device is "
                 f"{dev.platform}")
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(jax.devices())} device(s)")
    from repro import obs
    from repro.launch import compile_cache

    cache = compile_cache.enable()
    clock = CompileClock()
    log("A", platform=dev.platform, kind=repr(dev.device_kind),
        devices=len(jax.devices()), jax=jax.__version__, compile_cache=cache)
    obs.enable()            # the trainer's step-time distribution
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(clock, seed=args.seed)
    else:
        run_one_chip(clock, seed=args.seed)
    log("total", seconds=round(time.perf_counter() - t0, 2),
        **clock.since((0.0, 0, 0)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

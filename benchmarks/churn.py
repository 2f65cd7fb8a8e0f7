"""Live churn under query load: staged adds, tombstone deletes, compaction.

The serving story ISSUE 8 adds to the paper's train-while-serving loop:
the corpus itself now moves while the rotation is being trained. A fused
``ivf`` Engine serves a steady query stream while a ``ChurnController``
interleaves, every step,

  * ``remove`` — tombstone a batch of ids (masked to −inf inside the very
    Pallas tile scans, never filtered post-hoc),
  * ``add`` — stage a batch of new rows into the fixed-capacity append
    buffer (served by the NEXT query via the flat-ADC side pass),
  * a ``subspace_gcd`` RotationDelta absorbed through ``Engine.refresh``
    (the training loop keeps running during churn),
  * controller-paced ``flush`` (staged rows folded into CSR holes) and
    ``compact`` (holes squeezed out, shapes preserved).

Acceptance (claim checks):
  * zero Engine recompiles across the whole churn run (trace-counter
    pinned: every mutation is shape-preserving by construction),
  * zero LUT-cache invalidations (fused refresh keeps cached tables),
  * zero capacity ``grows`` — balanced churn is steady-state,
  * no tombstoned id ever surfaces in any step's results,
  * end-state recall@10 within 0.01 of a from-scratch ``ivf.build`` on
    the live rows (and exactly matching a same-quantizer repack).

``--devices N`` appends a sharded cell (the first N chips in this process
on a TPU; forced host devices in a subprocess on the CPU):
the same controller loop over ``ivf_sharded``, with deletes concentrated
on the lowest id ranks so shard 0 drains and the controller's imbalance
trigger fires a ``shard_rebalance`` — recall must survive the migration.

Run:  PYTHONPATH=src python benchmarks/churn.py --fast [--devices 2]
      PYTHONPATH=src python -m benchmarks.run --only churn --fast
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import churn, rotations, search
from repro.data import synthetic
from repro.index import ivf as index_ivf
from repro.kernels.common import on_tpu
from repro.metrics import recall_at_k

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _exact_top10(Q: np.ndarray, vecs: dict) -> np.ndarray:
    """Brute-force MIPS oracle over the live id → vector map."""
    live_ids = np.asarray(sorted(vecs), dtype=np.int32)
    live_X = np.stack([vecs[int(i)] for i in live_ids])
    order = np.argsort(-(Q @ live_X.T), axis=1)[:, :10]
    return live_ids[order]


def _delta(R, dim, sub, key):
    G = jax.random.normal(jax.random.PRNGKey(1000 + key), (dim, dim))
    learner = rotations.make("subspace_gcd", sub=sub)
    _, delta = learner.update(learner.init_from(R), G, 1e-3,
                              jax.random.PRNGKey(key))
    return delta


def churn_loop(engine, ctl, Q, vecs, add_pool, *, steps, batch, dim, rng,
               refresh=True, low_end_removes=False):
    """Drive balanced add/remove churn + refresh under query load.

    ``add_pool`` is the in-distribution add stream — drawn from the SAME
    mixture as the corpus (one ``sift_like`` call split in two), the
    realistic churn model. Out-of-mixture adds are a quantizer-drift
    problem (retrain), not an index-mutation problem.

    Returns (per-step dicts, cumulative removed-id set). Asserts nothing —
    callers turn the records into claim checks.
    """
    sub = getattr(ctl.state, "index", ctl.state).quantizer.sub
    removed: set = set()
    next_id = max(vecs) + 1
    records = []
    for step in range(steps):
        live_sorted = sorted(vecs)
        if low_end_removes:
            dead = np.asarray(live_sorted[:batch], dtype=np.int32)
        else:
            dead = rng.choice(live_sorted, size=batch,
                              replace=False).astype(np.int32)
        add = add_pool[step * batch:(step + 1) * batch]
        add_ids = np.arange(next_id, next_id + batch, dtype=np.int32)
        next_id += batch

        t0 = time.time()
        ctl.step(add=add, add_ids=add_ids, remove_ids=dead)
        mut_ms = (time.time() - t0) * 1e3
        for i in dead:
            removed.add(int(i))
            vecs.pop(int(i))
        vecs.update({int(i): x for i, x in zip(add_ids, add)})

        if refresh:
            engine.refresh(_delta(ctl.state.index.R, dim, sub, step))
        res = engine.search(Q)
        ids = np.asarray(res.ids)
        records.append(dict(
            step=step, mutate_ms=mut_ms,
            surfaced_tombstone=bool(np.isin(ids[ids >= 0],
                                            list(removed)).any()),
            ids_live=bool(set(ids[ids >= 0].ravel().tolist())
                          <= set(vecs)),
        ))
    return records, removed


def run(n: int = 50_000, dim: int = 64, queries: int = 128, lists: int = 64,
        subspaces: int = 16, codewords: int = 64, steps: int = 20,
        batch: int = 128, nprobe: int = 16, staging_rows: int = 1024,
        verbose: bool = True, devices: int = 1):
    """The single-device churn benchmark; returns (results, checks)."""
    out = print if verbose else (lambda *a, **k: None)
    pool = np.asarray(synthetic.sift_like(
        jax.random.PRNGKey(0), n + steps * batch, dim))
    X, add_pool = pool[:n], pool[n:]
    Q = np.asarray(synthetic.sift_like(jax.random.PRNGKey(1), queries, dim))
    R = rotations.random_rotation(jax.random.PRNGKey(2), dim)
    cfg = search.SearchConfig(
        num_lists=lists, subspaces=subspaces, codewords=codewords,
        nprobe=nprobe, train_size=min(n, 16384), fused_refresh=True)

    ivf_s = search.make("ivf")
    t0 = time.time()
    state = ivf_s.build(jax.random.PRNGKey(3), jnp.asarray(X), R, cfg)
    out(f"# built fused ivf index: N={n} L={lists} D={subspaces} "
        f"K={codewords} ({time.time() - t0:.1f}s)")

    engine = search.Engine(ivf_s, state, k=10, nprobe=nprobe, min_bucket=32)
    ctl = churn.ChurnController(engine, staging_rows=staging_rows,
                                flush_at=0.5, compact_at=0.03)
    engine.search(Q)                      # compile once, WITH staging wired
    compiles0 = engine.stats()["compiles"]

    vecs = {i: X[i] for i in range(n)}
    rng = np.random.default_rng(0)
    t0 = time.time()
    records, removed = churn_loop(engine, ctl, Q, vecs, add_pool,
                                  steps=steps, batch=batch, dim=dim, rng=rng)
    churn_s = time.time() - t0

    es = engine.stats()
    ch = es["churn"]
    recompiles = es["compiles"] - compiles0
    tombstone_clean = not any(r["surfaced_tombstone"] for r in records)
    ids_live = all(r["ids_live"] for r in records)

    # --- end-state recall vs the two rebuild oracles -----------------------
    truth = _exact_top10(Q, vecs)
    final = engine.search(Q)
    recall_churn = float(recall_at_k(np.asarray(final.ids), truth))

    live_ids = np.asarray(sorted(vecs), dtype=np.int32)
    live_X = np.stack([vecs[int(i)] for i in live_ids])
    idx = ctl.state.index
    # (a) same-quantizer repack: the bit-parity oracle — compaction and
    # staging must cost exactly nothing relative to a clean CSR
    list_ids, codes = index_ivf.encode(
        np.asarray(live_X) @ np.asarray(idx.R), idx.coarse, idx.quantizer)
    repacked = index_ivf.pack(idx.R, idx.coarse, idx.quantizer, codes,
                              list_ids, live_ids,
                              block_size=cfg.block_size)
    res_repack = ivf_s.search(search.IVF.attach(repacked, nprobe=nprobe),
                              np.asarray(Q), k=10, nprobe=nprobe)
    recall_repack = float(recall_at_k(np.asarray(res_repack.ids), truth))
    # (b) from-scratch build: fresh k-means on the live rows under the
    # CURRENT (GCD-trained) rotation — the expensive path churn avoids
    rebuilt = ivf_s.build(jax.random.PRNGKey(3), np.asarray(live_X),
                          idx.R, cfg)
    rebuilt = search.IVF.attach(  # re-key ids: build numbers rows 0..m
        index_ivf.IVFPQIndex(
            R=rebuilt.index.R, coarse=rebuilt.index.coarse,
            quantizer=rebuilt.index.quantizer, codes=rebuilt.index.codes,
            ids=np.where(np.asarray(rebuilt.index.ids) >= 0,
                         live_ids[np.maximum(
                             np.asarray(rebuilt.index.ids), 0)],
                         -1).astype(np.int32),
            list_offsets=rebuilt.index.list_offsets,
            block_size=rebuilt.index.block_size),
        nprobe=nprobe)
    res_build = ivf_s.search(rebuilt, np.asarray(Q), k=10, nprobe=nprobe)
    recall_build = float(recall_at_k(np.asarray(res_build.ids), truth))

    results = dict(
        steps=steps, batch=batch, churn_qps=queries * steps / churn_s,
        mutate_ms_p50=float(np.median([r["mutate_ms"] for r in records])),
        latency_ms_p50=es["latency_ms_p50"],
        recompiles=recompiles, lut_invalidations=es["lut_invalidations"],
        recall_churn=recall_churn, recall_repack=recall_repack,
        recall_build=recall_build,
        staged=ch["staged"], flushed=ch["flushed"],
        tombstoned=ch["tombstoned"], flushes=ch["flushes"],
        compactions=ch["compactions"], grows=ch["grows"],
        flush_ms_p95=ch["flush_ms_p95"],
    )
    checks = dict(
        zero_recompiles=recompiles == 0,
        zero_lut_invalidations=es["lut_invalidations"] == 0,
        zero_grows=ch["grows"] == 0,
        no_tombstoned_id_surfaced=tombstone_clean and ids_live,
        all_mutations_exercised=(ch["flushes"] >= 1
                                 and ch["compactions"] >= 1
                                 and ch["staged"] == steps * batch
                                 and ch["tombstoned"] == steps * batch),
        recall_matches_repack=abs(recall_churn - recall_repack) <= 0.01,
        recall_within_rebuild=recall_churn >= recall_build - 0.01,
    )
    out(f"# [churn] {steps} steps x {batch} add/{batch} remove + refresh "
        f"under load: recompiles {recompiles}, lut_invalidations "
        f"{es['lut_invalidations']}, grows {ch['grows']}, flushes "
        f"{ch['flushes']}, compactions {ch['compactions']}, flush p95 "
        f"{ch['flush_ms_p95']:.1f} ms")
    out(f"# [churn] recall@10 vs live-set exact: churn={recall_churn:.3f} "
        f"repack={recall_repack:.3f} fresh-build={recall_build:.3f}")

    if devices > 1:
        cell = _run_sharded_cell(
            devices, n=n, dim=dim, queries=queries, lists=lists,
            subspaces=subspaces, codewords=codewords, steps=steps,
            batch=batch, nprobe=nprobe, staging_rows=staging_rows)
        results["sharded"] = cell
        out(f"# [churn --devices {devices}] recompiles "
            f"{cell['recompiles']}, rebalances {cell['rebalances']}, "
            f"shard rows {cell['shard_rows_before']} -> "
            f"{cell['shard_rows_after']}, recall {cell['recall']:.3f} "
            f"(repack {cell['recall_repack']:.3f})")
        checks["sharded_zero_recompiles"] = cell["recompiles"] == 0
        checks["sharded_rebalanced"] = cell["rebalances"] >= 1
        checks["sharded_no_tombstones"] = cell["tombstone_clean"]
        checks["sharded_recall_matches_repack"] = (
            abs(cell["recall"] - cell["recall_repack"]) <= 0.01)

    out(f"# ACCEPTANCE: {checks} -> "
        f"{'PASS' if all(checks.values()) else 'FAIL'}")
    return results, checks


def churn_sharded_cell(n: int, dim: int, queries: int, lists: int,
                       subspaces: int, codewords: int, steps: int,
                       batch: int, nprobe: int, staging_rows: int,
                       devices: int) -> dict:
    """The --devices cell: controller churn over ``ivf_sharded``, with
    low-end deletes draining shard 0 (the id-rank partition puts the lowest
    ids there) until the imbalance trigger rebalances. Runs inside the
    forced-host-device subprocess ``_run_sharded_cell`` spawns."""
    assert jax.device_count() >= devices
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh(devices)

    pool = np.asarray(synthetic.sift_like(
        jax.random.PRNGKey(0), n + steps * batch, dim))
    X, add_pool = pool[:n], pool[n:]
    Q = np.asarray(synthetic.sift_like(jax.random.PRNGKey(1), queries, dim))
    R = rotations.random_rotation(jax.random.PRNGKey(2), dim)
    cfg = search.SearchConfig(
        num_lists=lists, subspaces=subspaces, codewords=codewords,
        nprobe=nprobe, train_size=min(n, 16384))
    index = index_ivf.build(jax.random.PRNGKey(3), jnp.asarray(X), R,
                            cfg.ivf_config(), train_size=cfg.train_size)

    sh_s = search.make("ivf_sharded", mesh=mesh)
    state = search.IVFSharded.attach(index, mesh=mesh, nprobe=nprobe)
    engine = search.Engine(sh_s, state, k=10, nprobe=nprobe, min_bucket=32)
    # low-end removes drain shard 0 by ~batch rows/step; the tight
    # threshold makes the imbalance trigger fire within the short run
    ctl = churn.ChurnController(engine, staging_rows=staging_rows,
                                flush_at=0.5, compact_at=0.05,
                                imbalance_threshold=1.03)

    def shard_rows(st):
        ids = np.asarray(st.ids)
        return [int((ids[s] >= 0).sum()) for s in range(ids.shape[0])]

    rows_before = shard_rows(ctl.state)
    engine.search(Q)
    compiles0 = engine.stats()["compiles"]

    vecs = {i: X[i] for i in range(n)}
    records, removed = churn_loop(
        engine, ctl, Q, vecs, add_pool, steps=steps, batch=batch, dim=dim,
        rng=np.random.default_rng(0), refresh=False, low_end_removes=True)

    es = engine.stats()
    truth = _exact_top10(Q, vecs)
    final = engine.search(Q)
    recall = float(recall_at_k(np.asarray(final.ids), truth))

    # same-quantizer repack oracle, served through the same sharded backend
    live_ids = np.asarray(sorted(vecs), dtype=np.int32)
    live_X = np.stack([vecs[int(i)] for i in live_ids])
    idx0 = index
    list_ids, codes = index_ivf.encode(
        np.asarray(live_X) @ np.asarray(idx0.R), idx0.coarse, idx0.quantizer)
    repacked = index_ivf.pack(idx0.R, idx0.coarse, idx0.quantizer, codes,
                              list_ids, live_ids, block_size=cfg.block_size)
    res_repack = sh_s.search(
        search.IVFSharded.attach(repacked, mesh=mesh, nprobe=nprobe),
        np.asarray(Q), k=10, nprobe=nprobe)
    recall_repack = float(recall_at_k(np.asarray(res_repack.ids), truth))

    return dict(
        devices=devices,
        recompiles=int(es["compiles"] - compiles0),
        rebalances=int(es["churn"]["rebalances"]),
        grows=int(es["churn"]["grows"]),
        shard_rows_before=rows_before,
        shard_rows_after=shard_rows(ctl.state),
        tombstone_clean=not any(r["surfaced_tombstone"] for r in records)
        and all(r["ids_live"] for r in records),
        recall=recall, recall_repack=recall_repack,
    )


def _run_sharded_cell(devices: int, **kw) -> dict:
    """Run ``churn_sharded_cell`` over ``devices`` devices. On a TPU it runs
    in this process on the first ``devices`` chips (this process holds them;
    a child could not reach them). Elsewhere it spawns a child under a
    forced host-device count (the XLA flag must be set before jax
    initializes, hence the subprocess)."""
    if on_tpu():
        return churn_sharded_cell(devices=devices, **kw)
    code = (
        "import os, json\n"
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') + "
        f"' --xla_force_host_platform_device_count={devices}').strip()\n"
        "from benchmarks.churn import churn_sharded_cell\n"
        f"print('CELL=' + json.dumps(churn_sharded_cell(devices={devices}, "
        + ", ".join(f"{k}={v!r}" for k, v in kw.items()) + ")))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, os.path.join(_REPO, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"churn sharded cell failed:\n{out.stdout}\n{out.stderr}")
    line = [l for l in out.stdout.splitlines() if l.startswith("CELL=")][-1]
    return json.loads(line[len("CELL="):])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--lists", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--fast", action="store_true",
                    help="small corpus / few steps (CI churn-smoke scale)")
    ap.add_argument("--devices", type=int, default=1,
                    help="append the sharded churn cell on N devices (the "
                         "first N chips on a TPU; forced host devices in a "
                         "subprocess on the CPU)")
    ap.add_argument("--out", default=None,
                    help="BENCH_churn.json destination dir (default "
                         "$REPRO_BENCH_DIR; unset → print only)")
    args = ap.parse_args()
    kw = dict(n=args.n, dim=args.dim, queries=args.queries,
              lists=args.lists, steps=args.steps, batch=args.batch)
    if args.fast:
        kw = dict(n=8000, dim=32, queries=64, lists=32, subspaces=8,
                  codewords=32, steps=6, batch=64, nprobe=8,
                  staging_rows=512)
    res, checks = run(devices=args.devices, **kw)

    out_dir = args.out or os.environ.get("REPRO_BENCH_DIR")
    if out_dir:
        from repro import obs
        path = obs.write_bench(out_dir, "churn", sections={"churn": res},
                               checks=checks, config=vars(args))
        errs = obs.validate_bench(path)
        print(f"# BENCH written: {path} "
              f"({'schema-valid' if not errs else f'INVALID: {errs}'})")
        if errs:
            sys.exit(1)
    if not all(checks.values()):
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Recall@10 vs QPS across the repro.search registry: exact vs flat vs IVF.

One harness, every retrieval backend. Builds a GCD-rotated quantized index
per residual depth (PQ at depth 1, RQ above) and serves the same corpus,
queries, and rotation through each registered searcher:

  * exact     — tiled brute force; the recall oracle and the QPS floor
  * flat_adc  — full ADC scan over the very codes IVF probes (attached to
                the IVF build, so "recall vs flat" isolates probing loss)
  * ivf       — ``nprobe`` sweep: scan work vs recall, the serving knob
  * *_sharded — the row-sharded twins, attached to the same artifacts
                (parity rows in-process; the ``--devices N`` sweep runs on
                the first N chips in-process on a TPU, or in a forced-host-
                device subprocess on the CPU, and measures per-device scan
                work vs the replicated backend)

Metrics per row:
  * scan work   — CSR rows scored per query (the hardware-independent cost)
  * QPS         — measured wall-clock throughput of the jit'd search
  * recall@10   — (a) vs the flat ADC scan (isolates probing loss)
                  (b) vs exact MIPS through the registry (end-to-end)
  * compression — corpus f32 bytes / code payload bytes

The sweep ends with the serving pieces unique to this paper + subsystem:
a ``subspace_gcd`` RotationDelta absorbed via ``Searcher.refresh`` (codes
untouched, recall preserved) and a ``search.Engine`` ragged-batch pass
whose compile cache must stay at one executable per (bucket, k, nprobe).

Acceptance (ISSUE 1, carried forward): at ≥0.9 recall@10-vs-flat, PQ scan
work must drop ≥5× vs the flat path. ISSUE 2: RQ depth-2 end-to-end with
exact subspace refresh and better quantization than PQ. ISSUE 4: all
registry backends on one harness; Engine compile cache bounded. ISSUE 5
adds: sharded backends match their replicated twins, and per-device scan
work under ``--devices N`` shrinks ~linearly at unchanged recall@10.

Run:  PYTHONPATH=src python benchmarks/ivf_recall_qps.py [--n 100000]
      PYTHONPATH=src python -m benchmarks.run --only ivf [--fast]
      PYTHONPATH=src python -m benchmarks.run --only ivf --devices 4
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro import rotations, search
from repro.data import synthetic
from repro.index import maintain
from repro.kernels.common import on_tpu
from repro.metrics import recall_at_k

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def sharded_cell(n: int, dim: int, queries: int, lists: int, subspaces: int,
                 codewords: int, devices: int, nprobe: int = 8) -> dict:
    """The --devices measurement: single vs sharded IVF on a forced-host-
    device mesh (runs inside the worker subprocess ``run`` spawns — must be
    imported only after XLA_FLAGS pins the device count)."""
    assert jax.device_count() >= devices, (
        f"need {devices} devices, have {jax.device_count()}")
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh(devices)

    key = jax.random.PRNGKey(0)
    X = synthetic.sift_like(key, n, dim)
    Q = synthetic.sift_like(jax.random.PRNGKey(1), queries, dim)
    R = rotations.random_rotation(jax.random.PRNGKey(2), dim)
    cfg = search.SearchConfig(
        num_lists=lists, subspaces=subspaces, codewords=codewords,
        nprobe=nprobe, train_size=min(n, 16384))

    ivf_s = search.make("ivf")
    single = ivf_s.build(jax.random.PRNGKey(3), X, R, cfg)
    res_single = ivf_s.search(single, Q, k=10, nprobe=nprobe)
    scan_single = float(np.mean(np.asarray(res_single.scanned)))

    sh_s = search.make("ivf_sharded", mesh=mesh)
    state = search.IVFSharded.attach(single.index, mesh=mesh, nprobe=nprobe)
    res = sh_s.search(state, Q, k=10)
    # measured rows scanned: the sharded result's ``scanned`` psums every
    # shard's valid blocks, so /devices is the per-device share (comparable
    # to the single-device measurement, unlike the static window bound)
    per_dev = float(np.mean(np.asarray(res.scanned))) / devices

    truth = np.argsort(-np.asarray(Q @ X.T), axis=1)[:, :10]
    r_single = recall_at_k(np.asarray(res_single.ids), truth)
    r_sharded = recall_at_k(np.asarray(res.ids), truth)

    # Engine over the sharded backend: compile-cache + recompile-free refresh
    engine = search.Engine(sh_s, state, k=10, nprobe=nprobe, min_bucket=32)
    engine.search(np.asarray(Q))
    compiles = engine.stats()["compiles"]
    G = jax.random.normal(jax.random.PRNGKey(9), (dim, dim))
    learner = rotations.make("subspace_gcd", sub=single.index.quantizer.sub)
    _, delta = learner.update(learner.init_from(single.index.R), G, 2e-3,
                              jax.random.PRNGKey(0))
    engine.refresh(delta)
    post = engine.search(np.asarray(Q))
    return dict(
        devices=devices,
        scan_single=scan_single,
        scan_per_device=float(per_dev),
        reduction_per_device=scan_single / max(float(per_dev), 1.0),
        recall_single=float(r_single),
        recall_sharded=float(r_sharded),
        parity=bool(recall_at_k(np.asarray(res.ids),
                                np.asarray(res_single.ids)) >= 0.999),
        refresh_recompiles=int(engine.stats()["compiles"] - compiles),
        post_refresh_recall=float(
            recall_at_k(np.asarray(post.ids), truth)),
    )


def _run_sharded_cell(devices: int, **kw) -> dict:
    """Run ``sharded_cell`` over ``devices`` devices. On a TPU it runs in
    this process on the first ``devices`` chips (this process holds them; a
    child could not reach them). Elsewhere it spawns a child under
    ``--xla_force_host_platform_device_count`` (the flag must be set before
    jax initializes, hence the subprocess)."""
    if on_tpu():
        return sharded_cell(devices=devices, **kw)
    code = (
        "import os, json\n"
        # append rather than overwrite: inherited platform/memory flags must
        # survive (duplicated flags resolve last-wins in XLA)
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') + "
        f"' --xla_force_host_platform_device_count={devices}').strip()\n"
        "from benchmarks.ivf_recall_qps import sharded_cell\n"
        f"print('CELL=' + json.dumps(sharded_cell(devices={devices}, "
        + ", ".join(f"{k}={v!r}" for k, v in kw.items()) + ")))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, os.path.join(_REPO, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded cell failed:\n{out.stdout}\n{out.stderr}")
    line = [l for l in out.stdout.splitlines() if l.startswith("CELL=")][-1]
    return json.loads(line[len("CELL="):])


def run(n: int = 100_000, dim: int = 64, queries: int = 256, lists: int = 256,
        subspaces: int = 16, codewords: int = 256, depths=(1, 2),
        use_kernel: bool | None = None, verbose: bool = True,
        devices: int = 1):
    """Sweep the searcher registry × residual depths; returns
    (results dict, claim-check dict). ``devices > 1`` appends the sharded
    scan-work sweep (``_run_sharded_cell``). ``use_kernel=None`` scans with
    the Pallas kernels on a TPU and the jnp references elsewhere."""
    out = print if verbose else (lambda *a, **k: None)
    key = jax.random.PRNGKey(0)
    X = synthetic.sift_like(key, n, dim)
    Q = synthetic.sift_like(jax.random.PRNGKey(1), queries, dim)
    R = rotations.random_rotation(jax.random.PRNGKey(2), dim)

    results: dict = {}
    checks: dict = {}
    full_probe_recall: dict = {}
    swept = set()

    out("backend,scheme,nprobe,scan_rows,scan_reduction,qps,"
        "recall10_vs_flat,recall10_vs_exact")

    # --- exact backend: the oracle every quantized row is scored against
    exact_s = search.make("exact")
    exact_state = exact_s.build(key, X, R, search.SearchConfig(tile_rows=8192))
    exact_res = exact_s.search(exact_state, Q, k=10)
    exact_ids = np.asarray(exact_res.ids)
    exact_dt = _bench(lambda: exact_s.search(exact_state, Q, k=10).scores)
    out(f"exact,-,-,{n},1.0x,{queries/exact_dt:.0f},1.000,1.000")
    swept.add("exact")

    # --- streaming exact twin: the same oracle past HBM scale; its
    # double-buffered tile merge must be bit-identical to the resident scan
    stream_s = search.make("exact_stream")
    stream_state = stream_s.build(key, X, R,
                                  search.SearchConfig(tile_rows=8192))
    stream_res = stream_s.search(stream_state, Q, k=10)
    stream_dt = _bench(lambda: stream_s.search(stream_state, Q, k=10).scores)
    stream_exact = bool(np.array_equal(np.asarray(stream_res.ids), exact_ids))
    out(f"exact_stream,-,-,{n},1.0x,{queries/stream_dt:.0f},"
        f"{1.0 if stream_exact else 0.0:.3f},"
        f"{1.0 if stream_exact else 0.0:.3f}")
    swept.add("exact_stream")
    checks["streaming_matches_exact"] = stream_exact
    results["exact_stream"] = dict(qps=queries / stream_dt,
                                   bit_identical=stream_exact)

    ivf_s = search.make("ivf")
    flat_s = search.make("flat_adc")

    for depth in depths:
        name = "pq" if depth == 1 else f"rq{depth}"
        cfg = search.SearchConfig(
            num_lists=lists, subspaces=subspaces, codewords=codewords,
            depth=depth, block_size=128, nprobe=8,
            train_size=min(n, 16384), use_kernel=use_kernel,
        )
        t0 = time.time()
        ivf_state = ivf_s.build(jax.random.PRNGKey(3), X, R, cfg)
        flat_state = flat_s.attach(ivf_state.index, use_kernel=use_kernel)
        index = ivf_state.index
        st = flat_s.stats(flat_state)
        # residual distortion on a held sample — the strict quantization-
        # quality metric behind the recall frontier (recall can saturate)
        XRs = X[:4096] @ index.R
        res_s = XRs - index.coarse.centroids[index.coarse.assign(XRs)]
        sample_distortion = float(index.quantizer.distortion(res_s))
        out(f"# [{name}] built IVF index: N={n} L={lists} D={subspaces} "
            f"K={codewords} depth={depth} cap={st['capacity']} "
            f"code_bytes/item={st['code_bytes_per_row']} "
            f"({st['compression']:.0f}x compression) "
            f"residual_distortion={sample_distortion:.4f} "
            f"max_list_blocks={ivf_state.max_blocks} ({time.time()-t0:.1f}s)")

        # --- flat backend over the same codes the ivf backend probes
        flat_res = flat_s.search(flat_state, Q, k=10)
        flat_dt = _bench(lambda: flat_s.search(flat_state, Q, k=10).scores)
        flat_ids = np.asarray(flat_res.ids)
        flat_scan = st["capacity"]
        r_flat_exact = recall_at_k(flat_ids, exact_ids)
        out(f"flat_adc,{name},-,{flat_scan},1.0x,{queries/flat_dt:.0f},"
            f"1.000,{r_flat_exact:.3f}")
        swept.add("flat_adc")

        # --- int8 ADC LUT pack over the same index: the per-step LUT DMA
        # shrinks 4× and recall must stay within 0.01 of the f32 tables
        flat8_state = flat_s.attach(index, use_kernel=use_kernel,
                                    lut_dtype="int8")
        flat8_ids = np.asarray(flat_s.search(flat8_state, Q, k=10).ids)
        flat8_dt = _bench(lambda: flat_s.search(flat8_state, Q, k=10).scores)
        r_flat8 = recall_at_k(flat8_ids, exact_ids)
        out(f"flat_adc[int8],{name},-,{flat_scan},1.0x,"
            f"{queries/flat8_dt:.0f},-,{r_flat8:.3f}")
        checks[f"{name}_int8_recall_within_0.01"] = (
            r_flat8 >= r_flat_exact - 0.01)

        rows = []
        passed = False
        for nprobe in (1, 2, 4, 8, 16, 32, 64):
            if nprobe > lists:
                break
            res = ivf_s.search(ivf_state, Q, k=10, nprobe=nprobe)
            dt = _bench(lambda np_=nprobe: ivf_s.search(
                ivf_state, Q, k=10, nprobe=np_).scores)
            qps = queries / dt
            scan = float(np.mean(np.asarray(res.scanned)))
            reduction = flat_scan / max(scan, 1.0)
            ids_np = np.asarray(res.ids)
            r_flat = recall_at_k(ids_np, flat_ids)
            r_exact = recall_at_k(ids_np, exact_ids)
            rows.append(dict(nprobe=nprobe, scan=scan, reduction=reduction,
                             qps=qps, recall_flat=r_flat, recall_exact=r_exact))
            out(f"ivf,{name},{nprobe},{scan:.0f},{reduction:.1f}x,{qps:.0f},"
                f"{r_flat:.3f},{r_exact:.3f}")
            if r_flat >= 0.9 and reduction >= 5.0:
                passed = True
        swept.add("ivf")

        # --- rotation refresh through the protocol: the same RotationDelta
        # the trainer would emit, absorbed by Searcher.refresh
        def distortion_loss(Rm, index=index):
            return index.quantizer.distortion(X[:8192] @ Rm)

        G = jax.grad(distortion_loss)(index.R)
        learner = rotations.make("subspace_gcd", sub=index.quantizer.sub)
        _, delta = learner.update(learner.init_from(index.R), G, 2e-3,
                                  jax.random.PRNGKey(0))
        refreshed = ivf_s.refresh(ivf_state, delta)
        mismatch = float(maintain.refresh_mismatch(refreshed.index, X))
        post = ivf_s.search(refreshed, Q, k=10, nprobe=min(32, lists))
        post_recall = recall_at_k(np.asarray(post.ids), exact_ids)
        out(f"# [{name}] Searcher.refresh (subspace GCD delta): code mismatch "
            f"vs full rebuild = {mismatch*100:.2f}%, post-refresh "
            f"recall@10 vs exact = {post_recall:.3f}")

        results[name] = dict(rows=rows, flat_recall_exact=r_flat_exact,
                             int8_recall_exact=r_flat8,
                             compression=st["compression"],
                             refresh_mismatch=mismatch,
                             post_refresh_recall=post_recall,
                             residual_distortion=sample_distortion)
        full_probe_recall[name] = (r_flat_exact, sample_distortion)
        if depth == 1:
            checks["pq_scan_reduction_at_recall"] = passed

            # --- Engine: ragged batches, one compile per (bucket, k, nprobe)
            engine = search.Engine(ivf_s, ivf_state, k=10, nprobe=8,
                                   min_bucket=32)
            sizes = (31, 60, 17, 31, queries)
            for sz in sizes:
                engine.search(np.asarray(Q)[:sz])
            es = engine.stats()
            # expected bucket set through the Engine's own bucketing, so
            # the acceptance check cannot drift from the implementation
            buckets = {engine._bucket(sz) for sz in sizes}
            checks["engine_compile_cache"] = es["compiles"] <= len(buckets)
            results["engine"] = dict(
                compiles=es["compiles"], requests=es["requests"],
                lut_hit_rate=es["lut_hit_rate"],
                latency_ms_p50=es["latency_ms_p50"])
            out(f"# [engine] {es['requests']} ragged batches over buckets "
                f"{sorted(buckets)} -> {es['compiles']} compiles, LUT hit "
                f"rate {es['lut_hit_rate']:.2f}, p50 "
                f"{es['latency_ms_p50']:.1f} ms")

            # --- fused-refresh Engine: the live delta is absorbed on the
            # query side, so refresh costs zero recompiles and zero
            # LUT-cache invalidations (trace-counter verified), and the
            # post-refresh batch reuses every cached LUT row
            fstate = flat_s.attach(index, use_kernel=use_kernel,
                                   lut_dtype="int8", fused_refresh=True)
            feng = search.Engine(flat_s, fstate, k=10, min_bucket=32)
            feng.search(np.asarray(Q))
            fc0 = feng.stats()["compiles"]
            feng.refresh(delta)
            post_f = feng.search(np.asarray(Q))
            fs = feng.stats()
            fr = recall_at_k(np.asarray(post_f.ids), exact_ids)
            checks["fused_refresh_no_recompile"] = fs["compiles"] == fc0
            checks["fused_refresh_no_lut_invalidation"] = (
                fs["lut_invalidations"] == 0 and fs["lut_epoch"] == 0)
            results["fused_engine"] = dict(
                compiles=fs["compiles"],
                lut_invalidations=fs["lut_invalidations"],
                lut_hits=fs["lut_hits"], post_refresh_recall=fr)
            out(f"# [engine:fused int8] refresh -> recompiles "
                f"{fs['compiles'] - fc0}, lut_invalidations "
                f"{fs['lut_invalidations']}, lut_hits {fs['lut_hits']}, "
                f"post-refresh recall@10 vs exact = {fr:.3f}")

        else:
            # RQ end-to-end: built, searched, refreshed; refresh stays exact
            # (subspace matching) and recall survives the refresh.
            checks[f"{name}_end_to_end"] = (
                mismatch <= 0.01 and np.isfinite(post_recall)
                and post_recall > 0.0
            )

        if depth == depths[0]:
            # --- sharded twins on the local mesh (S = device_count; 1 in a
            # plain run — the --devices sweep below forces a real shard
            # count): same artifacts, so recall must match the replicated
            # backend row for row. First depth rather than depth 1, so a
            # --depths 2 run still sweeps (and ticks) every registry name.
            from repro.launch.mesh import make_data_mesh
            mesh = make_data_mesh()
            S = jax.device_count()
            sharded_ok = True
            ivf8_ids = np.asarray(
                ivf_s.search(ivf_state, Q, k=10, nprobe=8).ids)
            for sh_name, want_ids in (
                    ("exact_sharded", exact_ids),
                    ("flat_sharded", flat_ids),
                    ("ivf_sharded", ivf8_ids)):
                sh_s = search.make(sh_name, mesh=mesh)
                if sh_name == "exact_sharded":
                    sh_state = sh_s.build(
                        key, X, R, search.SearchConfig(tile_rows=8192))
                else:
                    sh_state = type(sh_s).attach(index, mesh=mesh, nprobe=8)
                kw = {"nprobe": 8} if sh_name == "ivf_sharded" else {}
                res = sh_s.search(sh_state, Q, k=10, **kw)
                dt = _bench(lambda s_=sh_s, st_=sh_state, kw_=kw: s_.search(
                    st_, Q, k=10, **kw_).scores)
                ids_np = np.asarray(res.ids)
                r_exact = recall_at_k(ids_np, exact_ids)
                sharded_ok &= recall_at_k(ids_np, want_ids) >= 0.999
                per_dev = float(np.mean(np.asarray(res.scanned))) / S
                out(f"{sh_name},{name},{'8' if kw else '-'},{per_dev:.0f}"
                    f"/dev×{S},-,{queries/dt:.0f},-,{r_exact:.3f}")
                swept.add(sh_name)
            checks["sharded_parity"] = sharded_ok

    if 1 in depths and len(full_probe_recall) > 1:
        pq_r, pq_d = full_probe_recall["pq"]
        best_rq = max(v[0] for k, v in full_probe_recall.items() if k != "pq")
        best_rq_d = min(v[1] for k, v in full_probe_recall.items()
                        if k != "pq")
        # more code bits per item must buy strictly lower residual
        # distortion (recall can saturate and tie on easy corpora — the
        # distortion metric cannot) without losing end-to-end recall
        checks["rq_beats_pq_quantization"] = (
            best_rq_d < pq_d and best_rq >= pq_r - 1e-6
        )
        out(f"# frontier: flat recall@10 vs exact — pq={pq_r:.3f}, "
            f"best rq={best_rq:.3f}; residual distortion — pq={pq_d:.4f}, "
            f"best rq={best_rq_d:.4f}")

    if devices > 1:
        cell = _run_sharded_cell(
            devices, n=n, dim=dim, queries=queries, lists=lists,
            subspaces=subspaces, codewords=codewords)
        results["sharded"] = cell
        out(f"# [sharded --devices {devices}] scan/query: "
            f"{cell['scan_single']:.0f} (1 dev) -> "
            f"{cell['scan_per_device']:.0f}/dev "
            f"({cell['reduction_per_device']:.1f}x per-device reduction), "
            f"recall@10 {cell['recall_single']:.3f} -> "
            f"{cell['recall_sharded']:.3f}, refresh recompiles "
            f"{cell['refresh_recompiles']}")
        # near-linear: per-device scan work within 2x of the ideal 1/S slice
        # (block-padding rounds short per-shard lists up to whole tiles)
        checks["sharded_scan_linear"] = (
            cell["reduction_per_device"] >= devices / 2.0)
        checks["sharded_recall_unchanged"] = (
            cell["recall_sharded"] >= cell["recall_single"] - 1e-6
            and cell["parity"])
        checks["sharded_refresh_no_recompile"] = (
            cell["refresh_recompiles"] == 0)

    checks["registry_swept"] = swept == set(search.names())
    out(f"# ACCEPTANCE: {checks} -> "
        f"{'PASS' if all(checks.values()) else 'FAIL'}")
    return results, checks


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--lists", type=int, default=256)
    ap.add_argument("--subspaces", type=int, default=16)
    ap.add_argument("--codewords", type=int, default=256)
    ap.add_argument("--depths", default="1,2",
                    help="comma list of residual depths (1=PQ, 2=RQ-2, ...)")
    ap.add_argument("--use-kernel", action="store_const", const=True,
                    help="force the Pallas kernels (default: on a TPU "
                         "only; interpret mode is too slow on the CPU)")
    ap.add_argument("--devices", type=int, default=1,
                    help="run the sharded sweep on N devices (the first N "
                         "chips on a TPU; forced host devices in a "
                         "subprocess on the CPU)")
    ap.add_argument("--out", default=None,
                    help="BENCH_ivf_recall_qps.json destination dir "
                         "(default $REPRO_BENCH_DIR; unset → print only)")
    args = ap.parse_args()
    depths = tuple(int(d) for d in args.depths.split(","))
    res, checks = run(
        n=args.n, dim=args.dim, queries=args.queries, lists=args.lists,
        subspaces=args.subspaces, codewords=args.codewords, depths=depths,
        use_kernel=args.use_kernel, devices=args.devices)
    from repro import obs

    # --out > $REPRO_BENCH_DIR (no benchmarks.run import: this file also
    # runs script-style as `python benchmarks/ivf_recall_qps.py`)
    out_dir = args.out or os.environ.get("REPRO_BENCH_DIR")
    if out_dir:
        path = obs.write_bench(out_dir, "ivf_recall_qps",
                               sections={"ivf": res}, checks=checks,
                               config=vars(args))
        print(f"# BENCH written: {path}")


if __name__ == "__main__":
    main()

"""Fault-tolerant training launcher.

Wires together: config registry → synthetic pipeline → jit'd train step
(AdamW + GCD manifold updates) → async checkpointing → auto-resume.

Fault-tolerance contract (DESIGN.md §6):
  * checkpoints are atomic + manifest-gated; a crash mid-save is ignorable;
  * ``--resume`` (default) restores the newest complete checkpoint AND the
    data-pipeline cursor, so a restarted job replays no batch twice;
  * checkpoints are saved mesh-agnostic (host numpy) — a resume may use a
    different device count (elastic re-mesh: params are re-device_put with
    the new mesh's shardings);
  * a step watchdog flags stragglers: any step exceeding
    ``--watchdog-factor`` × median step time is logged with its step index
    (on a real fleet this signal feeds the pod-restart policy).

On this CPU container the launcher runs the smoke configs end-to-end; on a
TPU fleet the same entry point takes the full configs (--full).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch two-tower-retrieval \
      --steps 200 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs, rotations
from repro.data import pipeline as pipe_lib
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models import gnn, layers, recsys
from repro.models import transformer as tfm
from repro.training import checkpoint as ckpt
from repro.training import optimizer as opt_lib
from repro.training import train_state as ts


def _host_seed(key: jax.Array) -> int:
    """The batch's host RNG seed, drawn from its key: one device read, which
    waits for whatever the device is running."""
    with obs.annotate("pipeline.seed"):
        return int(jax.random.randint(key, (), 0, 1 << 30))


def make_batch_fn(cfg, family: str, batch: int):
    """Family-specific synthetic batch maker: key -> tuple of arrays."""
    if family == "lm":
        def f(key):
            return synthetic.lm_batch(key, batch, cfg.train_seq_len,
                                      cfg.vocab_size)
        return f
    if family == "gnn":
        from repro.data import graph as graph_lib
        g = graph_lib.synthetic_graph(0, 2000, 8, cfg.d_in,
                                      num_classes=cfg.num_classes)

        def f(key):
            seed = _host_seed(key)
            rng = np.random.RandomState(seed)
            seeds = rng.randint(0, g.num_nodes, size=batch)
            feats, labels = graph_lib.sample_blocks(
                g, seeds, cfg.sample_sizes, seed)
            return (*feats, labels)
        return f
    # recsys
    if isinstance(cfg, recsys.WideDeepConfig):
        def f(key):
            return synthetic.ctr_batch(key, batch, cfg.n_sparse,
                                       cfg.vocab_per_field)
        return f
    if isinstance(cfg, (recsys.TwoTowerConfig, recsys.MINDConfig)):
        log = synthetic.ClickLog(0, cfg.item_vocab, dim=32)

        def f(key):
            return log.batch(_host_seed(key), batch, cfg.hist_len)
        return f
    if isinstance(cfg, recsys.DINConfig):
        def f(key):
            return synthetic.din_batch(key, batch, cfg.hist_len,
                                       cfg.item_vocab)
        return f
    raise TypeError(type(cfg))


def make_loss_fn(cfg, family: str):
    if family == "lm":
        return lambda p, tok, lab: tfm.forward_train_stats(p, tok, lab, cfg)
    if family == "gnn":
        return lambda p, h0, h1, h2, lab: gnn.loss_minibatch(
            p, [h0, h1, h2], lab, cfg)
    if isinstance(cfg, recsys.WideDeepConfig):
        return lambda p, ids, lab: recsys.widedeep_loss(p, ids, lab, cfg)
    if isinstance(cfg, recsys.TwoTowerConfig):
        return lambda p, h, pos: recsys.twotower_loss(p, h, pos, cfg)
    if isinstance(cfg, recsys.MINDConfig):
        return lambda p, h, pos: recsys.mind_loss(p, h, pos, cfg)
    if isinstance(cfg, recsys.DINConfig):
        return lambda p, h, t, lab: recsys.din_loss(p, h, t, lab, cfg)
    raise TypeError(type(cfg))


def init_model(key, cfg, family):
    if family == "lm":
        return tfm.init_params(key, cfg)
    if family == "gnn":
        return gnn.init_params(key, cfg)
    if isinstance(cfg, recsys.WideDeepConfig):
        return recsys.widedeep_init(key, cfg)
    if isinstance(cfg, recsys.TwoTowerConfig):
        return recsys.twotower_init(key, cfg)
    if isinstance(cfg, recsys.MINDConfig):
        return recsys.mind_init(key, cfg)
    if isinstance(cfg, recsys.DINConfig):
        return recsys.din_init(key, cfg)
    raise TypeError(type(cfg))


def build_step(cfg, family: str, steps: int, rotation: str, *,
               emit_deltas: bool):
    """The trainer's jitted step (state donated) and its optimizer config,
    for a schedule of ``steps`` steps with the ``rotation`` learner."""
    ocfg = opt_lib.OptimizerConfig(
        lr=1e-3, total_steps=steps, warmup_steps=min(50, steps // 10 + 1),
        rotation=rotations.RotationConfig.from_spec(rotation),
    )
    step_fn = jax.jit(
        ts.make_train_step(make_loss_fn(cfg, family), ocfg,
                           emit_deltas=emit_deltas,
                           has_aux=family == "lm"),
        donate_argnums=(0,))
    return step_fn, ocfg


def _rotation_health(params) -> float | None:
    """Max orthogonality error over the manifold (SO(n)) leaves — the
    trainer-side twin of ``maintain.refresh_health``'s drift gauge. One
    host sync per call; callers gate on ``obs.enabled()``."""
    errs = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = str(getattr(path[-1], "key", getattr(path[-1], "name", "")))
        if name in opt_lib.MANIFOLD_LEAVES and leaf.ndim >= 2 \
                and leaf.shape[-1] == leaf.shape[-2]:
            R = leaf.reshape(-1, leaf.shape[-1], leaf.shape[-1])
            errs.extend(float(rotations.orthogonality_error(r)) for r in R)
    return max(errs) if errs else None


def train(arch_id: str, steps: int, batch: int, ckpt_dir: str | None,
          resume: bool = True, full: bool = False, seed: int = 0,
          ckpt_every: int = 50, watchdog_factor: float = 5.0,
          rotation: str = "gcd_greedy", log_every: int = 10,
          stop_after: int | None = None, obs_log: str | None = None,
          prefetch: bool = False, live_loop=None):
    """``stop_after``: checkpoint and exit after that many steps — simulates
    a crash for the resume tests (the schedule still targets ``steps``, so a
    resumed run is bit-identical to an uninterrupted one).

    ``obs_log``: enable the global ``repro.obs`` registry with a JSONL
    event log at that path — per-step spans/metrics (time, loss, grad
    norm, rotation health every ``log_every``) stream there; the loop
    stays metric-free when observability is off.

    ``prefetch``: double-buffer the host pipeline — batch k+1 is generated
    on a worker thread while step k runs. Bit-identical stream (batches
    are pure functions of (seed, step)); checkpoints carry the cursor
    either way, so resume works mid-prefetch.

    ``live_loop``: a ``repro.pipeline.LiveIndexLoop`` to drive from this
    trainer — the step function is built with ``emit_deltas=True`` and the
    loop's ``on_step`` runs after each step (live-index refresh + the
    background compactor's poll stay off the device's critical path)."""
    if obs_log:
        obs.enable(jsonl=obs_log)
    reg = obs.default_registry()
    arch = configs.get(arch_id)
    cfg = arch.make_config() if full else arch.make_smoke()
    batch_fn = make_batch_fn(cfg, arch.family, batch)
    step_fn, ocfg = build_step(cfg, arch.family, steps, rotation,
                               emit_deltas=live_loop is not None)
    key = jax.random.PRNGKey(seed)
    params = init_model(key, cfg, arch.family)
    state = ts.init_state(jax.random.fold_in(key, 1), params, ocfg)
    pipe = pipe_lib.Pipeline(batch_fn, seed=seed, prefetch=prefetch,
                             registry=reg)

    # ---- auto-resume (elastic: arrays re-device_put on the current mesh) ----
    start_step = 0
    if ckpt_dir and resume:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            (restored, pipe_state), manifest = ckpt.restore(
                ckpt_dir, latest, (state, pipe.state()))
            state = jax.device_put(restored)
            pipe.restore(pipe_state)
            start_step = latest
            print(f"[train] resumed from step {latest}")

    if obs.enabled() and arch.family == "lm":
        # the attention kernel's causal skip, from its static grid
        for name, value in layers.attention_tiles(cfg.train_seq_len).items():
            reg.gauge(name).set(value)

    times: list[float] = []
    metrics_hist = []
    for i in range(start_step, steps):
        t0 = time.time()
        with reg.span("train.step"):
            with obs.annotate("train.next_batch", step=i):
                batch_data = next(pipe)
            with obs.annotate("train.dispatch", step=i):
                state, metrics = step_fn(state, *batch_data)
            with obs.annotate("train.loss_read", step=i):
                loss = float(metrics["loss"])   # blocks until the step ends
        if live_loop is not None:
            live_loop.on_step(metrics)
        dt = time.time() - t0
        times.append(dt)
        metrics_hist.append(loss)
        if obs.enabled():
            reg.distribution("train.step_ms").observe(dt * 1e3)
            reg.gauge("train.loss").set(loss)   # eq1 term included for
            reg.gauge("train.grad_norm").set(   # quantization-aware archs
                float(metrics["grad_norm"]))
            for name in ("moe.local_tokens", "moe.max_expert_load",
                         "moe.dropped"):            # expert-layer counters
                if name in metrics:
                    reg.gauge(name).set(float(metrics[name]))
        if len(times) > 8:
            med = statistics.median(times[-64:])
            if dt > watchdog_factor * med:
                print(f"[watchdog] step {i} straggled: {dt:.2f}s vs median "
                      f"{med:.2f}s — would trigger pod health-check")
                reg.counter("train.straggler_steps").inc()
        if i % log_every == 0:
            print(f"step {i:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            if obs.enabled():
                health = _rotation_health(state.params)
                if health is not None:
                    reg.gauge("train.rotation_orthogonality").set(health)
                reg.event("train_step", step=i, loss=loss,
                          grad_norm=float(metrics["grad_norm"]),
                          step_ms=dt * 1e3, rotation_orthogonality=health)
        if ckpt_dir and (i + 1) % ckpt_every == 0:
            ckpt.save_async(ckpt_dir, i + 1, (state, pipe.state()),
                            metadata={"arch": arch_id, "loss": loss})
        if stop_after is not None and (i + 1) >= stop_after:
            if ckpt_dir:
                ckpt.save(ckpt_dir, i + 1,
                          (jax.tree.map(np.asarray, state), pipe.state()),
                          metadata={"arch": arch_id, "crashed": True})
            print(f"[train] simulated crash after step {i + 1}")
            pipe.close()
            return state, metrics_hist
    if live_loop is not None:
        live_loop.drain()
    pipe.close()
    if ckpt_dir:
        ckpt.save(ckpt_dir, steps, (jax.tree.map(np.asarray, state),
                                    pipe.state()),
                  metadata={"arch": arch_id, "final": True})
        ckpt.wait_pending()
    return state, metrics_hist


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (TPU fleets only)")
    ap.add_argument("--rotation", default="gcd_greedy",
                    choices=[n for n in rotations.names()
                             if n != "subspace_gcd"])
    ap.add_argument("--obs-log", default=None,
                    help="enable repro.obs and stream step events to this "
                         "JSONL file; a metrics report prints at exit")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffer host batch synthesis + device_put "
                         "on a worker thread (bit-identical stream)")
    args = ap.parse_args()
    compile_cache.enable()
    _, hist = train(args.arch, args.steps, args.batch, args.ckpt_dir,
                    resume=not args.no_resume, full=args.full,
                    rotation=args.rotation, obs_log=args.obs_log,
                    prefetch=args.prefetch)
    print(f"final loss: {hist[-1]:.4f} (start {hist[0]:.4f})")
    if args.obs_log:
        print(obs.report())


if __name__ == "__main__":
    main()

"""Optimizer + train-state + checkpoint + grad-compression tests."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rotations
from repro.core import givens
from repro.data import synthetic
from repro.models import transformer as tfm
from repro.training import checkpoint as ckpt
from repro.training import grad_compress as gc
from repro.training import optimizer as opt
from repro.training import train_state as ts


def _tiny_cfg(**kw):
    return tfm.TransformerConfig(
        name="t", num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
        head_dim=8, d_ff=64, vocab_size=97, dtype=jnp.float32,
        param_dtype=jnp.float32, q_chunk=8, xent_chunk=16, **kw)


def test_adam_matches_reference_on_quadratic():
    cfg = opt.OptimizerConfig(lr=0.1, beta1=0.9, beta2=0.999, grad_clip=0.0,
                              warmup_steps=0, schedule="constant")
    params = {"w": jnp.ones((4,)) * 2.0}
    state = opt.init(params, cfg)
    # reference adam in numpy
    w = np.ones(4) * 2.0
    m = np.zeros(4)
    v = np.zeros(4)
    for t in range(1, 6):
        g = 2 * (w - 1.0)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g**2
        w = w - 0.1 * (m / (1 - 0.9**t)) / (np.sqrt(v / (1 - 0.999**t)) + 1e-8)
        grads = {"w": jnp.asarray(2 * (np.asarray(params["w"]) - 1.0))}
        params, state = opt.update(grads, state, params, cfg, jax.random.PRNGKey(t))
    np.testing.assert_allclose(np.asarray(params["w"]), w, rtol=1e-5)


def test_manifold_leaves_get_gcd_not_adam():
    cfg = opt.OptimizerConfig(
        lr=0.1, rotation=rotations.RotationConfig(learner="gcd",
                                                  method="greedy", lr=0.05))
    params = {"R": jnp.eye(8), "w": jnp.zeros((8,))}
    state = opt.init(params, cfg)
    G = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
    grads = {"R": G, "w": jnp.ones((8,))}
    new_params, _ = opt.update(grads, state, params, cfg, jax.random.PRNGKey(1))
    # R stays exactly orthogonal (GCD), w moved by adam
    assert float(givens.orthogonality_error(new_params["R"])) < 1e-5
    assert not np.allclose(np.asarray(new_params["R"]), np.eye(8))
    assert not np.allclose(np.asarray(new_params["w"]), 0.0)


def test_frozen_method_keeps_rotation():
    cfg = opt.OptimizerConfig(rotation=rotations.RotationConfig(learner="frozen"))
    params = {"R": jnp.eye(6)}
    state = opt.init(params, cfg)
    grads = {"R": jax.random.normal(jax.random.PRNGKey(0), (6, 6))}
    new_params, _ = opt.update(grads, state, params, cfg, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(new_params["R"]), np.eye(6))


def test_adafactor_state_is_factored_and_converges():
    cfg = opt.OptimizerConfig(name="adafactor", lr=0.3, grad_clip=0.0,
                              warmup_steps=0, schedule="constant")
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 16))}
    state = opt.init(params, cfg)
    assert state.mu["w"].shape == (8,)
    assert state.nu["w"].shape == (16,)
    target = jnp.ones((8, 16))
    for t in range(60):
        g = 2 * (params["w"] - target)
        params, state = opt.update({"w": g}, state, params, cfg,
                                   jax.random.PRNGKey(t))
    assert float(jnp.abs(params["w"] - target).mean()) < 0.15


def test_accum_steps_equivalent_loss_and_grads():
    cfg = _tiny_cfg()
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, lab = synthetic.lm_batch(jax.random.PRNGKey(1), 8, 16, 97)
    outs = {}
    for A in (1, 2, 4):
        ocfg = opt.OptimizerConfig(
            accum_steps=A, lr=0.0, grad_clip=0.0,
            rotation=rotations.RotationConfig(learner="frozen"))
        step = jax.jit(ts.make_train_step(
            lambda pp, t, l: tfm.forward_train(pp, t, l, cfg), ocfg))
        st = ts.init_state(jax.random.PRNGKey(2), p, ocfg)
        _, m = step(st, tok, lab)
        outs[A] = (float(m["loss"]), float(m["grad_norm"]))
    for A in (2, 4):
        assert np.isclose(outs[A][0], outs[1][0], rtol=1e-5)
        assert np.isclose(outs[A][1], outs[1][1], rtol=1e-4)


def test_checkpoint_atomicity_and_keep_n():
    with tempfile.TemporaryDirectory() as d:
        tree = {"a": np.arange(10), "b": {"c": np.ones((3, 3))}}
        for s in (1, 2, 3, 4):
            ckpt.save(d, s, tree, keep_n=2)
        assert ckpt.latest_step(d) == 4
        dirs = sorted(os.listdir(d))
        assert len(dirs) == 2  # keep_n respected
        # a partial (manifest-less) dir must be ignored
        os.makedirs(os.path.join(d, "step_0000000099"))
        assert ckpt.latest_step(d) == 4
        restored, man = ckpt.restore_latest(d, tree)
        np.testing.assert_array_equal(restored["b"]["c"], tree["b"]["c"])
        assert man["step"] == 4


def test_train_launcher_resume_exact():
    """Kill/restart mid-run resumes bit-exact (fault-tolerance contract)."""
    from repro.launch import train as train_mod
    with tempfile.TemporaryDirectory() as d:
        # run 6 steps straight
        state_a, hist_a = train_mod.train(
            "two-tower-retrieval", steps=6, batch=8, ckpt_dir=None,
            seed=3, log_every=100)
        # same 6-step job, crash after 3, then resume
        train_mod.train("two-tower-retrieval", steps=6, batch=8, ckpt_dir=d,
                        seed=3, ckpt_every=100, log_every=100, stop_after=3)
        state_b, hist_b = train_mod.train(
            "two-tower-retrieval", steps=6, batch=8, ckpt_dir=d, seed=3,
            ckpt_every=100, log_every=100)
        assert np.isclose(hist_a[-1], hist_b[-1], rtol=1e-4), (hist_a, hist_b)


def test_ef_compression_unbiased_over_time():
    rng = np.random.RandomState(0)
    g_true = jnp.asarray(rng.randn(256).astype(np.float32))
    err = jnp.zeros_like(g_true)
    acc_q = np.zeros(256)
    acc_t = np.zeros(256)
    for i in range(100):
        q, scale, err = gc.ef_quantize(g_true, err, axis_size=2)
        acc_q += np.asarray(q, np.float32) * float(scale) * 2
        acc_t += np.asarray(g_true)
    # error feedback: the long-run average matches full precision
    np.testing.assert_allclose(acc_q / 100, acc_t / 100, atol=1e-2)


# --- PR 10 satellites: prefetch determinism + delta emission ----------------


def test_pipeline_prefetch_bit_identical_and_resume():
    """The double-buffered prefetcher must not change the batch stream:
    prefetch on == off bitwise, and a pause/``state()``/``restore()``
    mid-stream (with a batch in flight) reproduces the uninterrupted
    stream exactly — the cursor is the whole checkpoint, never the
    buffer contents."""
    from repro.data import pipeline as pipe_lib

    def make(key):
        return jax.random.normal(key, (4, 8))

    sync = pipe_lib.Pipeline(make, seed=5, prefetch=False)
    want = [np.asarray(next(sync)) for _ in range(8)]

    pre = pipe_lib.Pipeline(make, seed=5, prefetch=True)
    got = [np.asarray(next(pre)) for _ in range(8)]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert pre.prefetch_hits >= 6   # steady state: only the first can miss
    pre.close()
    sync.close()

    for prefetch in (False, True):
        p1 = pipe_lib.Pipeline(make, seed=5, prefetch=prefetch)
        for _ in range(3):
            next(p1)
        cursor = p1.state()
        p1.close()                   # in-flight batch 3 is dropped here
        p2 = pipe_lib.Pipeline(make, seed=0, prefetch=prefetch)
        p2.restore(cursor)
        rest = [np.asarray(next(p2)) for _ in range(5)]
        for w, g in zip(want[3:], rest):
            np.testing.assert_array_equal(w, g)
        p2.close()


def test_train_launcher_prefetch_resume_exact():
    """Launcher-level fault tolerance with prefetch on: crash-after-3 +
    resume matches the uninterrupted prefetch-OFF run — the pipeline
    cursor in the checkpoint is prefetch-agnostic."""
    from repro.launch import train as train_mod
    with tempfile.TemporaryDirectory() as d:
        _, hist_a = train_mod.train(
            "two-tower-retrieval", steps=6, batch=8, ckpt_dir=None,
            seed=3, log_every=100)
        train_mod.train("two-tower-retrieval", steps=6, batch=8, ckpt_dir=d,
                        seed=3, ckpt_every=100, log_every=100, stop_after=3,
                        prefetch=True)
        _, hist_b = train_mod.train(
            "two-tower-retrieval", steps=6, batch=8, ckpt_dir=d, seed=3,
            ckpt_every=100, log_every=100, prefetch=True)
        assert np.isclose(hist_a[-1], hist_b[-1], rtol=1e-4), (hist_a, hist_b)


def _host_spans(log_dir: str, prefixes: tuple) -> dict:
    """host thread line -> [(start, end, name, args)] of a profiler trace's
    events whose names start with ``prefixes`` (lines are keyed by their
    position: threads may share a name)."""
    import glob

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith(prefixes)]
            if evs:
                out[(plane.name, i)] = evs
    return out


def test_train_spans_reach_a_profiler_trace(tmp_path):
    """With a profiler session running, each step's batch wait, dispatch
    and loss read land on the trainer's thread, and the prefetch worker's
    batch (with its seed read inside) on the worker's — no switch, no
    registry."""
    from repro.launch import train as train_mod
    train_mod.train("two-tower-retrieval", steps=2, batch=8, ckpt_dir=None,
                    seed=3, log_every=100, prefetch=True)   # compile first
    with jax.profiler.trace(str(tmp_path)):
        train_mod.train("two-tower-retrieval", steps=3, batch=8,
                        ckpt_dir=None, seed=3, log_every=100, prefetch=True)
    threads = _host_spans(str(tmp_path), ("train.", "pipeline."))
    main = next(t for t, evs in threads.items()
                if any(n == "train.dispatch" for _, _, n, _ in evs))
    names = [n for _, _, n, _ in threads[main]]
    for stage in ("train.next_batch", "train.dispatch", "train.loss_read"):
        assert names.count(stage) == 3, stage
    assert [a["step"] for _, _, n, a in threads[main]
            if n == "train.dispatch"] == [0, 1, 2]
    workers = [t for t in threads if t != main]
    assert workers                      # steps after the first prefetched
    for t in workers:
        produce = [(s, e) for s, e, n, _ in threads[t]
                   if n == "pipeline.produce"]
        seeds = [(s, e) for s, e, n, _ in threads[t] if n == "pipeline.seed"]
        assert produce and len(seeds) == len(produce)
        assert all(any(ps <= s and e <= pe for ps, pe in produce)
                   for s, e in seeds)


def test_update_with_deltas_matches_update():
    """``update_with_deltas`` is the same optimizer step plus the manifold
    deltas (the trainer→live-index sync contract): params bitwise equal to
    ``update``, and the emitted delta applied to the old R reproduces the
    new R."""
    cfg = opt.OptimizerConfig(
        lr=0.1, rotation=rotations.RotationConfig(learner="gcd",
                                                  method="greedy", lr=0.05))
    params = {"R": jnp.eye(8), "w": jnp.zeros((8,))}
    state = opt.init(params, cfg)
    grads = {"R": jax.random.normal(jax.random.PRNGKey(0), (8, 8)),
             "w": jnp.ones((8,))}
    p1, s1 = opt.update(grads, state, params, cfg, jax.random.PRNGKey(1))
    p2, s2, deltas = opt.update_with_deltas(grads, state, params, cfg,
                                            jax.random.PRNGKey(1))
    assert bool(jnp.array_equal(p1["R"], p2["R"]))
    assert bool(jnp.array_equal(p1["w"], p2["w"]))
    assert set(deltas) == {"R"}
    np.testing.assert_allclose(np.asarray(deltas["R"].apply(params["R"])),
                               np.asarray(p2["R"]), atol=1e-6)


def test_train_step_emit_deltas_metric():
    """``make_train_step(emit_deltas=True)`` surfaces the per-step manifold
    delta under ``metrics["rotation_deltas"]`` and changes nothing else."""
    cfg = _tiny_cfg()
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, lab = synthetic.lm_batch(jax.random.PRNGKey(1), 8, 16, 97)
    ocfg = opt.OptimizerConfig(
        lr=1e-3, rotation=rotations.RotationConfig(learner="gcd",
                                                   method="greedy"))
    loss = lambda pp, t, l: tfm.forward_train(pp, t, l, cfg)  # noqa: E731
    st0 = ts.init_state(jax.random.PRNGKey(2), p, ocfg)
    _, m_plain = jax.jit(ts.make_train_step(loss, ocfg))(st0, tok, lab)
    st0 = ts.init_state(jax.random.PRNGKey(2), p, ocfg)
    st1, m_del = jax.jit(ts.make_train_step(loss, ocfg,
                                            emit_deltas=True))(st0, tok, lab)
    assert "rotation_deltas" not in m_plain
    assert np.isclose(float(m_plain["loss"]), float(m_del["loss"]))
    for key, delta in m_del["rotation_deltas"].items():
        assert isinstance(delta, rotations.GivensDelta), key

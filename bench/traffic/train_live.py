"""Traffic ``train_live``: the trainer's own loop with its live index.

Set-up, from the seed: the initial parameters (the trainer's own
``init_model``), every item's tower output under them, an IVF-PQ index over
those (``search.make("ivf")``, fused refresh) and a ``search.Engine`` on it,
driven by ``pipeline.LiveIndexLoop`` from ``launch.train.train`` — the
trainer's entry point with its ``ClickLog`` batches, prefetch and GCD-G
rotation learner. The benchmark's hook is the trainer's ``live_loop``: it
forwards each step to the LiveIndexLoop, and the trainer's first
``check_steps`` steps (loss, Adam state, parameters, batches) are read off
the trainer's frame for the comparison with the reference. The window
opens at the hook after ``warm_steps`` steps (every program compiled and
the first refreshes done) and closes at the first hook after ``--seconds``.

``train_step_ms`` is the window divided by the steps completed in it; a
step ends in the trainer's host read of the loss, so it covers the device
work, and the refreshes ride between steps as in training.

Checked after the window: the first steps against ``reference/twotower.py``
(losses, the first gradient as Adam received it, the change of every leaf
after the last check step); the live rotation the index serves against the
trainer's R; the index's live matrices against the reference's own,
composed in float64 from the build rotation and every Givens delta the
trainer handed the LiveIndexLoop (``ivfpq.live_transform``); and the
Engine's answers over the refreshed state against the plain float32 search
of that reference state (``reference/ivfpq.py``).
"""
from __future__ import annotations

import functools
import statistics
import sys
import time

import numpy as np

from bench import harness, system, work
from bench.reference import ivfpq, twotower


class StopWindow(Exception):
    """Raised from the hook to end the trainer's loop at the window's end."""


def _leaf_name(path) -> str:
    return "_".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def _named(tree) -> dict:
    import jax

    return {_leaf_name(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@functools.lru_cache(maxsize=None)
def _fns(model, chunk: int):
    """Jitted helpers for one configuration (built once per process)."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train as train_lib
    from repro.models import recsys

    def unit(x):
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                               1e-6)

    @jax.jit
    def init(key):
        return train_lib.init_model(key, model, "recsys")

    @jax.jit
    def embed(params):
        V, n = model.item_vocab, model.out_dim
        steps, tail = divmod(V, chunk)
        out = jnp.zeros((V, n), jnp.float32)

        def rows(start, size):
            ids = start + jnp.arange(size, dtype=jnp.int32)
            return unit(recsys.item_tower(params, ids, model)[0])

        def body(i, out):
            return jax.lax.dynamic_update_slice(
                out, rows(i * chunk, chunk), (i * chunk, 0))

        if steps:
            out = jax.lax.fori_loop(0, steps, body, out)
        if tail:
            out = jax.lax.dynamic_update_slice(
                out, rows(steps * chunk, tail), (steps * chunk, 0))
        return out

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                for k, v in _named(tree).items()}

    @jax.jit
    def change(params, key):
        p0 = _named(train_lib.init_model(key, model, "recsys"))
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - p0[k])))
                for k, v in _named(params).items()}

    @jax.jit
    def queries(key, corpus):
        """64 items' own tower outputs: queries that land among the items,
        as a user vector near what the user clicks does."""
        pick = jax.random.choice(key, corpus.shape[0], (64,), replace=False)
        return corpus[pick]

    return init, embed, norms, change, queries


class Hook:
    """The trainer's ``live_loop`` (see module docstring)."""

    def __init__(self, run: harness.Run, loop, engine, fns, key, wl: dict):
        self.run, self.loop, self.engine = run, loop, engine
        self.norms, self.change = fns[2], fns[3]
        self.key, self.wl = key, wl
        self.steps = 0
        self.window_steps = 0
        self.losses, self.batches = [], []
        self.grad_norms = self.change_norms = None
        self.final_R = None
        self.deltas = []     # the trainer's Givens deltas, on the device
        self.pipe = None
        self._step_span = None
        self._t_step = None
        self.step_s: list[float] = []

    def _end_step_span(self):
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None

    def on_step(self, metrics: dict) -> None:
        import jax

        now = time.perf_counter()
        self._end_step_span()
        if self._t_step is not None:
            self.step_s.append(now - self._t_step)
        self.steps += 1
        delta = metrics.get("rotation_deltas", {}).get("index/R")
        if delta is not None:
            self.deltas.append((delta.pi, delta.pj, delta.theta))
        with self.run.span("bench.live_refresh"):
            self.loop.on_step(metrics)
        # the trainer hands its hook only the step's metrics; its state,
        # batch and pipeline are read from the calling frame (train())
        frame = sys._getframe(1)
        try:
            if self.steps <= self.wl["check_steps"]:
                self._capture(frame.f_locals, metrics)
            if self.steps == self.wl["warm_steps"]:
                self.run.open_window()
            elif self.run.t_open is not None:
                self.window_steps += 1
                self.run.tick(self.window_steps)
                if time.perf_counter() - self.run.t_open >= self.run.seconds:
                    jax.block_until_ready(self.engine.state)
                    self.run.close_window()
                    st = frame.f_locals["state"]
                    self.final_R = np.asarray(st.params["index"].R)
                    raise StopWindow
        finally:
            del frame
        if self.run.trace and self.run._window_span is not None:
            self._step_span = jax.profiler.TraceAnnotation("bench.train_step")
            self._step_span.__enter__()
        self._t_step = time.perf_counter()

    def _capture(self, f_locals: dict, metrics: dict) -> None:
        state = f_locals["state"]
        self.pipe = f_locals["pipe"]
        self.losses.append(float(metrics["loss"]))
        self.batches.append(tuple(np.asarray(x)
                                  for x in f_locals["batch_data"]))
        if self.steps == 1:
            # Adam's first moment after one step is (1 − β1)·g
            b1 = self.wl["optimizer"]["beta1"]
            self.grad_norms = {k: float(v) / (1.0 - b1) for k, v in
                               self.norms(state.opt_state.mu).items()}
        if self.steps == self.wl["check_steps"]:
            self.change_norms = {k: float(v) for k, v in
                                 self.change(state.params, self.key).items()}

    def drain(self) -> None:
        self.loop.drain()


def _worst_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """Widest gap of per-leaf norms, each against the larger of the
    reference leaf's norm and the median leaf's; leaves the reference
    leaves unmoved to rounding (under 1e-3 of the median) are left out."""
    med = statistics.median(ref.values())
    worst, at = 0.0, ""
    for k, r in ref.items():
        if k in skip or r < 1e-3 * med:
            continue
        gap = abs(prog[k] - r) / max(r, med)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers the first steps are judged by: the widest
    relative gap of the per-step losses, of the first gradient's per-leaf
    norms (Adam's leaves; R has no Adam state), and of each leaf's change
    after the check steps."""
    grad_gap, grad_at = _worst_gap(prog["grad_norms"], ref["grad_norms"],
                                   skip=("index_R",))
    change_gap, change_at = _worst_gap(prog["change_norms"],
                                       ref["change_norms"])
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                            zip(prog["losses"], ref["losses"])),
            "grad_gap": grad_gap, "grad_gap_leaf": grad_at,
            "change_gap": change_gap, "change_gap_leaf": change_at}


def run(run: harness.Run) -> harness.Result:
    import jax

    from repro import search
    from repro.launch import train as train_lib
    from repro.pipeline import LiveIndexLoop

    wl, cfg = run.workload, run.config
    model = system.twotower_config(cfg)
    arch_id = system.register_arch(model)
    fns = _fns(model, wl["embed_chunk"])
    init, embed, _, _, queries = fns
    key = jax.random.PRNGKey(run.seed)

    params = init(key)
    corpus = embed(params)
    R0 = params["index"].R
    del params
    searcher = search.make("ivf")
    state = searcher.build(jax.random.fold_in(key, 7), corpus, R0,
                           system.search_config(cfg, wl))
    Q = np.asarray(queries(jax.random.fold_in(key, 11), corpus))
    del corpus
    engine = search.Engine(searcher, state, k=wl["k"])
    loop = LiveIndexLoop(engine, refresh_every=wl["refresh_every"],
                         delta_key="index/R")
    hook = Hook(run, loop, engine, fns, key, wl)
    try:
        train_lib.train(arch_id, wl["schedule_steps"], wl["batch"], None,
                        full=True, seed=run.seed, rotation="gcd_greedy",
                        prefetch=True, live_loop=hook,
                        log_every=wl["schedule_steps"])
        raise RuntimeError("the trainer ended before the window closed")
    except StopWindow:
        pass
    if hook.pipe is not None:
        hook.pipe.close()
    mem = harness.peak_bytes(run.devices)
    reduced = run.reduce_trace() if run.trace else None

    # checks: the live index after its last refresh, then the first steps
    loop.flush_refresh()
    live = {k: np.asarray(getattr(engine.state, k))
            for k in ("rot", "wacc", "qdelta")}
    rot = live["rot"]
    deltas = [tuple(np.asarray(x) for x in d) for d in hook.deltas]
    R0 = np.asarray(engine.state.index.R)
    run.values.update(deltas=deltas, R0=R0)
    ref_live = ivfpq.live_transform(R0, deltas,
                                    cfg["index"]["dim"]
                                    // cfg["index"]["num_subspaces"])
    res = engine.search(Q)
    limits = wl["limits"]
    checks = system.served_check(engine.state, Q, res.scores, res.ids,
                                 nprobe=wl["nprobe"], k=wl["k"],
                                 limits=limits, qdelta=ref_live["qdelta"])
    checks["rot_sync"] = harness.check(
        float(np.max(np.abs(rot - hook.final_R))), limits["rot_sync"])
    checks["refresh_gap"] = harness.check(
        ivfpq.refresh_gap(live, ref_live), limits["refresh_gap"])
    del engine, state, loop, res
    ref = twotower.first_steps(run.seed, system.model_dict(cfg),
                               wl["optimizer"], hook.batches)
    got = gaps({"losses": hook.losses, "grad_norms": hook.grad_norms,
                "change_norms": hook.change_norms}, ref)
    # a number without a limit in the workload is printed, not compared
    for name in ("loss_gap", "grad_gap", "change_gap"):
        if name in limits:
            checks[name] = harness.check(got[name], limits[name])

    steps, window = hook.window_steps, run.window_s
    flops, bytes_ = work.train_step(system.model_dict(cfg), wl["batch"])
    run.values.update(steps=steps, window_s=window, step_flops=flops,
                      step_bytes=bytes_)
    finite = np.all(np.isfinite(hook.losses + ref["losses"]))
    info = {"steps": steps, "window_s": window,
            "compiles_in_window": run.compile_in_window,
            "losses": hook.losses, "ref_losses": ref["losses"],
            "loss_gap": got["loss_gap"],
            "grad_gap_leaf": got["grad_gap_leaf"],
            "change_gap_leaf": got["change_gap_leaf"],
            "rot_drift": float(np.max(np.abs(
                hook.final_R - np.eye(len(rot))))),
            "orthogonality": float(np.max(np.abs(
                hook.final_R.T @ hook.final_R - np.eye(len(rot))))),
            "deltas": len(deltas),
            "delta_scale": float(np.max(np.abs(
                ref_live["delta"] - np.eye(len(rot))))),
            "step_ms_median": 1e3 * statistics.median(hook.step_s[-steps:]),
            "step_ms_max": 1e3 * max(hook.step_s[-steps:]),
            "setup_s": run.setup_s}
    return harness.Result(
        correct=bool(finite) and steps > 0,
        attempted=steps, failed=0,
        end_to_end={"train_step_ms": 1e3 * window / steps},
        checks=checks, memory_peak_bytes=mem, reduced=reduced, info=info)

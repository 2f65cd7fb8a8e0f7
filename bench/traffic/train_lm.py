"""Traffic ``train_lm``: the trainer's own loop on an LM configuration.

Set-up, from the seed: ``launch.train.train`` on the configuration (its
``init_model`` parameters, ``lm_batch`` Zipf(1.1) token ids over the
configuration's vocabulary at its ``assumed.seq_len``, prefetch, AdamW and
the GCD-G rotation learner on every layer's latent rotation). The
benchmark's hook is the trainer's ``live_loop``: the trainer's first
``check_steps`` steps (loss, Adam's first moment, parameters, batches) are
read off the trainer's frame for the comparison with the reference. The
window opens at the hook after ``warm_steps`` steps and closes at the first
hook after ``--seconds``.

``train_step_ms`` is the window divided by the steps completed in it; a
step ends in the trainer's host read of the loss.

Checked after the window, against ``reference/deepseek_v2.py`` run on the
same batches from the same seed: ``grad_gap`` (per Adam leaf,
‖g − g_ref‖ / ‖g_ref‖ of the first clipped gradient, read from Adam's
first moment), ``change_gap`` (per leaf, the relative gap of ‖p − p0‖
after the check steps, the rotations included); and on the program alone
``rot_orth`` (max |RᵀR − I| of every latent rotation at the window's end)
and ``dropped`` (the most routed assignments to held experts that any
step's expert layers left outside the rows their grouped matmuls cover).
``dropped`` cannot see rows inside the groups that the grouped matmuls
compute wrongly; ``grad_gap`` and ``change_gap`` do. ``loss_gap`` (the
first step's loss) is printed, and compared only if the workload gives it
a limit.
"""
from __future__ import annotations

import functools
import statistics
import sys
import time

import numpy as np

from bench import harness, lm, work_lm
from bench.reference import deepseek_v2


class StopWindow(Exception):
    """Raised from the hook to end the trainer's loop at the window's end."""


def _named(tree) -> dict:
    import jax

    return {"/".join(str(getattr(p, "key", p)) for p in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@functools.lru_cache(maxsize=None)
def _change_fn(model):
    import jax
    import jax.numpy as jnp

    from repro.launch import train as train_lib

    @jax.jit
    def change(params, key):
        p0 = _named(train_lib.init_model(key, model, "lm"))
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32) - p0[k].astype(jnp.float32))))
            for k, v in _named(params).items()}

    return change


#: the expert-layer counters read from every step's metrics
COUNTERS = ("moe.local_tokens", "moe.max_expert_load", "moe.dropped")


class Hook:
    """The trainer's ``live_loop`` (see module docstring)."""

    def __init__(self, run: harness.Run, model, key, wl: dict):
        self.run, self.model, self.key, self.wl = run, model, key, wl
        self.steps = 0
        self.window_steps = 0
        self.losses, self.batches = [], []
        self.grads = self.change_norms = None
        self.final_rot = None
        self.counters = {k: [] for k in COUNTERS}
        self.pipe = None
        self._step_span = None

    def _end_step_span(self):
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None

    def on_step(self, metrics: dict) -> None:
        import jax

        self._end_step_span()
        self.steps += 1
        for k, v in jax.device_get({k: metrics[k] for k in COUNTERS}).items():
            self.counters[k].append(float(v))
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
                    self.run.close_window()
                    st = frame.f_locals["state"]
                    self.final_rot = np.asarray(st.params["kvq"]["rot_c"])
                    raise StopWindow
        finally:
            del frame
        if self.run.trace and self.run._window_span is not None:
            self._step_span = jax.profiler.TraceAnnotation("bench.train_step")
            self._step_span.__enter__()

    def _capture(self, f_locals: dict, metrics: dict) -> None:
        import jax

        state = f_locals["state"]
        self.pipe = f_locals["pipe"]
        self.losses.append(float(metrics["loss"]))
        self.batches.append(tuple(np.asarray(x)
                                  for x in f_locals["batch_data"]))
        if self.steps == 1:
            # Adam's first moment after one step is (1 − β1)·g
            b1 = np.float32(1.0 - self.wl["optimizer"]["beta1"])
            mu = _named(state.opt_state.mu)
            self.grads = {k: np.asarray(jax.device_get(v), np.float32) / b1
                          for k, v in mu.items() if k != "kvq/rot_c"}
        if self.steps == self.wl["check_steps"]:
            change = _change_fn(self.model)(state.params, self.key)
            self.change_norms = {k: float(v) for k, v in change.items()}

    def drain(self) -> None:
        pass


def _rel(a: np.ndarray, b: np.ndarray) -> float:
    a64, b64 = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
    return float(np.linalg.norm(a64 - b64) / np.linalg.norm(b64))


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers the first steps are judged by (module docstring), with
    the leaf where each is widest."""
    grad = {k: _rel(prog["grads"][k], g) for k, g in ref["grads"].items()
            if np.any(g)}
    change = {k: abs(prog["change_norms"][k] - r) / r
              for k, r in ref["change_norms"].items() if r > 0}
    ga, ca = max(grad, key=grad.get), max(change, key=change.get)
    return {"loss_gap": abs(prog["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_gap": grad[ga], "grad_gap_leaf": ga,
            "change_gap": change[ca], "change_gap_leaf": ca}


def rot_orth(rot: np.ndarray) -> float:
    R = rot.astype(np.float64)
    eye = np.eye(R.shape[-1])
    return float(max(np.max(np.abs(r.T @ r - eye)) for r in R))


def run(run: harness.Run) -> harness.Result:
    import jax

    from repro.launch import train as train_lib

    wl, cfg = run.workload, run.config
    model = lm.lm_config(cfg)
    arch_id = lm.register_arch(model)
    key = jax.random.PRNGKey(run.seed)
    hook = Hook(run, model, key, wl)
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
    steps, window = hook.window_steps, run.window_s
    c = {k: v[-steps:] for k, v in hook.counters.items()}
    local = statistics.mean(c["moe.local_tokens"])
    flops = work_lm.train_step(cfg, wl["batch"], cfg["assumed"]["seq_len"],
                               local)
    eflops, ebytes = work_lm.expert_rows(cfg, local)
    run.values.update(steps=steps, window_s=window, step_flops=flops,
                      expert_flops=eflops, expert_bytes=ebytes)
    if run.trace:
        lm.step_program(run)     # the scope readers' program, while warm

    ref = deepseek_v2.first_steps(run.seed, cfg, wl["optimizer"],
                                  hook.batches)
    got = gaps({"losses": hook.losses, "grads": hook.grads,
                "change_norms": hook.change_norms}, ref)
    limits = wl["limits"]
    dropped = max(hook.counters["moe.dropped"])
    # a number without a limit in the workload is printed, not compared
    checks = {name: harness.check(got[name], limits[name])
              for name in ("loss_gap", "grad_gap", "change_gap")
              if name in limits}
    checks["rot_orth"] = harness.check(rot_orth(hook.final_rot),
                                       limits["rot_orth"])
    checks["dropped"] = harness.check(dropped, limits["dropped"])
    finite = np.all(np.isfinite(hook.losses + ref["losses"]))
    info = {"steps": steps, "window_s": window,
            "compiles_in_window": run.compile_in_window,
            "losses": hook.losses, "ref_losses": ref["losses"],
            "loss_gap": got["loss_gap"],
            "grad_gap_leaf": got["grad_gap_leaf"],
            "change_gap_leaf": got["change_gap_leaf"],
            "local_tokens": local,
            "max_expert_load": max(c["moe.max_expert_load"]),
            # the runtime's own counters, beside memory_peak_bytes
            "memory_stats": dict(run.devices[0].memory_stats() or {}),
            "setup_s": run.setup_s}
    return harness.Result(
        correct=bool(finite) and steps > 0,
        attempted=steps, failed=0,
        end_to_end={"train_step_ms": 1e3 * window / steps},
        checks=checks, memory_peak_bytes=mem, reduced=reduced, info=info)

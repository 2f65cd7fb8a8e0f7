"""gcd.update_ms: device milliseconds per traced training step of the GCD
rotation update (scoring, preconditioning, pair selection and the apply),
from the profiler trace.

The program marks the update with ``jax.named_scope("gcd")``
(``rotations/gcd.py``); the trace names ops by HLO instruction only, so
the instructions under the scope are read from the trainer's own step
program, compiled again from abstract shapes exactly as the trainer builds
it (``launch/train.py build_step``, with the arguments
``bench/traffic/train_live.py`` passes; the persistent compile cache
serves it). Only the outermost of them are summed (``bench/scopes.py``),
so GCD-G's nested ``while`` loops count once. Nothing is read when the
program has no such scope or no ``build_step``, or when the rebuilt
program's instructions do not cover the ops the trace timed under its
name.
"""

SCOPE = "gcd"
#: the trainer's step program as the trace names it (``train_step``, jitted)
MODULE = "jit_train_step"


def _step_program(run):
    """(parsed HLO of the trainer's step, or None)."""
    import jax

    from bench import scopes, system
    from repro.launch import train as train_lib
    from repro.training import train_state as ts

    build = getattr(train_lib, "build_step", None)
    if build is None:
        return None
    wl = run.workload
    model = system.twotower_config(run.config)
    step_fn, ocfg = build(model, "recsys", wl["schedule_steps"],
                          "gcd_greedy", emit_deltas=True)

    def init(key):
        params = train_lib.init_model(key, model, "recsys")
        return ts.init_state(jax.random.fold_in(key, 1), params, ocfg)

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = wl["batch"]
    hist = jax.ShapeDtypeStruct((batch, model.hist_len), "int32")
    pos = jax.ShapeDtypeStruct((batch,), "int32")
    text = step_fn.lower(state, hist, pos).compile().as_text()
    return scopes.parse(text)


def read(run, reduced):
    from bench import scopes

    steps = run.values.get("traced_units", run.values.get("steps"))
    if reduced is None or not steps or not any(
            n.startswith(MODULE + "/") for n in reduced.op_seconds):
        return None
    program = _step_program(run)
    if (program is None or program.module != MODULE
            or not scopes.ran_here(program, reduced.op_seconds)):
        return None
    s = scopes.device_seconds(program, scopes.outermost(program, SCOPE),
                              reduced.op_seconds)
    return None if s is None else 1e3 * s / steps

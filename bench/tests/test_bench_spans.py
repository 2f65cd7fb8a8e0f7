"""The program's spans in a trace (``bench/spans.py``), the map from a named
scope to the compiled program's instructions (``bench/scopes.py``) and the
``gcd.update_ms`` reader built on it, on hand-built profiles, on programs
compiled for the CPU, and on a profiler trace of one Engine batch."""
from __future__ import annotations

from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, scopes, spans, trace
from bench.tests import tiny


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile():
    """Window 0–100 ns on the trainer's thread, which is in
    bench.train_step throughout: train.next_batch 10–40, train.dispatch
    40–50, train.loss_read 50–90. The prefetch worker is in
    pipeline.produce 0–60 and, inside it, pipeline.seed 0–50. The device
    runs one op, 45–85; its idle gaps are 0–45 and 85–100."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_train_step(1)", 0, 100)]),
        NS(name="XLA Ops", events=[_ev("%while.2 = f32[] while()", 45, 40)]),
    ])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[
            _ev("bench.window", 0, 100), _ev("bench.train_step", 0, 100),
            _ev("train.next_batch", 10, 30), _ev("train.dispatch", 40, 10),
            _ev("train.loss_read", 50, 40), _ev("PjitFunction", 41, 5)]),
        NS(name="pipeline-prefetch_0", events=[
            _ev("pipeline.produce", 0, 60), _ev("pipeline.seed", 0, 50)]),
    ])
    return NS(planes=[device, host])


def test_gaps_are_labelled_on_the_window_thread():
    r = spans.reduce(_profile())
    assert r.window_thread == "python3"
    # the 0–45 gap's middle (22.5) is in train.next_batch on the trainer's
    # thread, and in pipeline.seed (a shorter span) on the worker's
    assert r.gaps == [("train.next_batch", pytest.approx(45e-9)),
                      ("bench.train_step", pytest.approx(15e-9))]
    # the benchmark's own reduction of the same profile is unchanged
    assert trace.reduce(_profile()).gaps == [
        ("bench.train_step", pytest.approx(45e-9)),
        ("bench.train_step", pytest.approx(15e-9))]


def test_self_times_add_up_per_thread():
    r = spans.reduce(_profile())
    main, worker = r.threads["python3"], r.threads["pipeline-prefetch_0"]
    assert set(main) == {"bench.train_step", "train.next_batch",
                         "train.dispatch", "train.loss_read"}
    assert main["bench.train_step"] == {"total_s": pytest.approx(100e-9),
                                        "count": 1,
                                        "self_s": pytest.approx(20e-9)}
    assert main["train.dispatch"]["self_s"] == pytest.approx(10e-9)
    # a thread's self times cover its outermost spans exactly once
    assert sum(v["self_s"] for v in main.values()) == pytest.approx(100e-9)
    assert worker["pipeline.produce"]["self_s"] == pytest.approx(10e-9)
    assert worker["pipeline.seed"] == {"total_s": pytest.approx(50e-9),
                                       "count": 1,
                                       "self_s": pytest.approx(50e-9)}


def test_spans_outside_the_window_are_left_out():
    p = _profile()
    p.planes[1].lines[0].events.append(_ev("train.next_batch", 120, 30))
    p.planes[1].lines[0].events.append(_ev("train.next_batch", 90, 20))
    st = spans.reduce(p).threads["python3"]["train.next_batch"]
    assert st["count"] == 2
    assert st["total_s"] == pytest.approx(40e-9)     # 30 + the 10 inside


def _nested(x, n):
    """Under scope "outer": a while loop whose body holds another while;
    outside it, one more op."""
    with jax.named_scope("outer"):
        def body(i, acc):
            return jax.lax.fori_loop(0, n, lambda j, a: a * 1.5 + j, acc)
        y = jax.lax.fori_loop(0, n, body, x)
    return jnp.sin(y) * 2.0


@pytest.fixture(scope="module")
def nested_program():
    text = jax.jit(_nested).lower(jnp.ones(8), 3).compile().as_text()
    return scopes.parse(text)


def test_scope_map_of_a_cpu_program(nested_program):
    p = nested_program
    assert p.module == "jit__nested"
    under = {n for n, op in p.op_names.items()
             if scopes.in_scope(op, "outer")}
    assert len([n for n in under if n.startswith("while")]) >= 2
    top = scopes.outermost(p, "outer")
    # the inner loop and every op of a loop body are enclosed by the outer
    # loop, so only it (and any op of the scope at the top) remains
    assert len([n for n in top if n.startswith("while")]) == 1
    assert all(not p.parent.get(n) or p.parent[n] not in under
               for n in top)
    assert not any(scopes.in_scope(p.op_names[n], "sin") for n in top)


def test_nested_scope_ops_count_once(nested_program):
    p = nested_program
    outer = next(n for n in scopes.outermost(p, "outer")
                 if n.startswith("while"))
    inner = next(n for n, op in p.op_names.items()
                 if n.startswith("while") and n != outer
                 and scopes.in_scope(op, "outer"))
    other = next(n for n, op in p.op_names.items()
                 if not scopes.in_scope(op, "outer") and op)
    op_s = {f"{p.module}/{outer}": 1.0, f"{p.module}/{inner}": 0.9,
            f"{p.module}/{other}": 5.0, "jit_other/while.1": 7.0}
    assert scopes.ran_here(p, op_s)
    assert scopes.device_seconds(p, scopes.outermost(p, "outer"),
                                 op_s) == pytest.approx(1.0)
    assert scopes.device_seconds(p, {"no_such_op"}, op_s) is None
    # a trace of another program of the same name is not this one
    assert not scopes.ran_here(p, {f"{p.module}/fusion.999": 1.0})
    assert not scopes.ran_here(p, {})


@pytest.mark.parametrize("op_name, hit", [
    ("jit(f)/gcd/while/body/add", True), ("jit(f)/jvp(gcd)/mul", True),
    ("jit(f)/gcd", True), ("jit(f)/gcd_x/mul", False),
    ("jit(f)/ivf.select/gather", True), ("jit(f)/ivfXselect/gather", False),
])
def test_in_scope(op_name, hit):
    scope = "ivf.select" if "ivf" in op_name else "gcd"
    assert scopes.in_scope(op_name, scope) is hit


def test_engine_spans_in_a_profiler_trace(tmp_path):
    """One Engine batch under ``jax.profiler.trace``: its spans land on the
    host plane, nested on the caller's thread, with batch and bucket."""
    from repro import search

    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (2000, 16))
    s = search.make("ivf")
    state = s.build(key, X, jnp.eye(16), search.SearchConfig(
        subspaces=4, codewords=16, num_lists=8, nprobe=4, block_size=8,
        train_size=2000))
    engine = search.Engine(s, state, k=5)
    Q = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (5, 16)))
    engine.collect(engine.submit(Q))                   # compile outside
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            engine.collect(engine.submit(Q + 1.0))
    path = trace.find_xplane(str(tmp_path))
    r = spans.reduce(path)
    got = r.threads[r.window_thread]
    for name in ("engine.submit", "engine.rotate", "engine.luts",
                 "engine.dispatch", "engine.collect"):
        assert got[name]["count"] == 1, name
    sub = got["engine.submit"]
    assert sub["self_s"] < sub["total_s"]              # its stages inside
    args = {ev.name: dict(ev.stats)
            for plane in trace.load(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine.")}
    assert args["engine.submit"] == {"batch": 5, "bucket": 8}
    assert args["engine.collect"] == {"batch": 5, "bucket": 8}


@pytest.fixture(scope="module")
def train_program():
    """The tiny training cell's step program, as the reader rebuilds it."""
    _, wl, cfg = tiny.cell("paper-train-live")
    run = NS(workload=wl, config=cfg)
    return harness.layer_reader("gcd.update_ms")._step_program(run), run


def test_gcd_reader_sums_the_outermost_gcd_ops(train_program):
    p, run = train_program
    assert p.module == "jit_train_step"
    top = scopes.outermost(p, "gcd")
    assert any(n.startswith("while") for n in top)       # the matching
    nested = {n for n, op in p.op_names.items()
              if scopes.in_scope(op, "gcd")} - top
    assert nested
    op_s = {f"{p.module}/{n}": 0.010 for n in top}
    op_s.update({f"{p.module}/{n}": 0.5 for n in nested})
    other = next(n for n, op in p.op_names.items()
                 if op and not scopes.in_scope(op, "gcd"))
    op_s[f"{p.module}/{other}"] = 3.0
    reader = harness.layer_reader("gcd.update_ms")
    run.values = {"traced_units": 4, "steps": 40}
    got = reader.read(run, NS(op_seconds=op_s))
    assert got == pytest.approx(1e3 * 0.010 * len(top) / 4)


def test_gcd_reader_reads_nothing_it_cannot_tie_to_the_program(
        train_program, monkeypatch):
    p, run = train_program
    reader = harness.layer_reader("gcd.update_ms")
    run.values = {"steps": 40}
    assert reader.read(run, None) is None
    # a trace without the trainer's program, or with ops of another
    # program of its name: not the step that ran
    assert reader.read(run, NS(op_seconds={"jit_fn/while.1": 1.0})) is None
    assert reader.read(run, NS(op_seconds={
        "jit_train_step/fusion.99999": 1.0})) is None
    # a program that predates ``build_step``
    from repro.launch import train as train_lib
    monkeypatch.delattr(train_lib, "build_step")
    assert reader.read(run, NS(op_seconds={
        f"{p.module}/{n}": 1.0 for n in p.op_names})) is None

"""The trace reducer: busy and idle time, per-kernel device time and the
labelling of idle gaps, on a small trace recorded on a TPU v5e (two
64-query batches through ``search.Engine``, the Pallas ``ivf_adc`` scan in
each) and on a hand-built profile whose answer is known."""
from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(DATA)


def test_recorded_window_and_busy(recorded):
    assert recorded.chips == 1
    assert recorded.window_s == pytest.approx(0.364190538)
    assert 0 < recorded.busy_s <= recorded.window_s
    # every op inside the window is busy time: their sum bounds the union
    assert recorded.busy_s <= sum(recorded.op_seconds.values()) + 1e-12


def test_recorded_kernel_time(recorded):
    # the two scans, 143.26 ms each on the device clock; the ops that only
    # take the kernel's output (a reduce over it) are not counted with it
    assert recorded.seconds_matching("ivf_adc") == pytest.approx(
        2 * 0.1432597, rel=1e-5)
    top = recorded.top_ops(1)[0]
    assert top[0] == "jit_fn/ivf_adc.1"
    assert recorded.seconds_matching("no_such_kernel") is None


def test_recorded_gaps_are_labelled_by_host_spans(recorded):
    labels = {label for label, _ in recorded.gaps}
    assert labels <= {"bench.submit", "bench.collect",
                      "host: outside any bench span"}
    assert "bench.collect" in labels
    idle = recorded.window_s - recorded.busy_s
    assert sum(s for _, s in recorded.gaps) <= idle + 1e-9
    assert set(recorded.spans) == {"bench.submit", "bench.collect"}


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def test_hand_built_profile():
    """Window 0–100 ns; ops at 10–30, 20–40 (overlapping) and 60–70; one
    program spans 0–50, another 55–80; the host is in bench.a from 0 to 50
    and in bench.b from 50 to 100. Busy = 30 + 10 = 40 ns; the idle gaps
    are 70–100 and 40–60 (middles in bench.b) and 0–10 (in bench.a)."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_a(1)", 0, 50),
                                       _ev("jit_b(2)", 55, 25)]),
        NS(name="XLA Ops", events=[_ev("%k.1 = f32[] custom-call()", 10, 20),
                                   _ev("%fusion.3 = f32[] fusion()", 20, 20),
                                   _ev("%k.1 = f32[] custom-call()", 60, 10)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 100), _ev("bench.a", 0, 50),
        _ev("bench.b", 50, 50), _ev("other", 0, 100)])])
    r = trace.reduce(NS(planes=[device, host]))
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.op_seconds == pytest.approx({"jit_a/k.1": 20e-9,
                                          "jit_a/fusion.3": 20e-9,
                                          "jit_b/k.1": 10e-9})
    assert r.seconds_matching("k.") == pytest.approx(30e-9)
    assert r.gaps == [("bench.b", pytest.approx(30e-9)),
                      ("bench.b", pytest.approx(20e-9)),
                      ("bench.a", pytest.approx(10e-9))]

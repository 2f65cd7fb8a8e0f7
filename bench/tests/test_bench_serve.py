"""The serving drivers end to end at a CPU size: a sound run comes out
correct, and each fault a serving cell can have, planted in the timed path,
comes out not correct."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.tests import tiny
from bench.tests.test_bench_train import answer_altered


def half_batch(mp):
    """Only the first half of each batch is searched; the other half is
    answered with the first half's results."""
    from repro.search import ivf

    orig = ivf.IVF.search_prepared

    def broken(self, state, QR, lut, **kw):
        res = orig(self, state, QR, lut, **kw)
        b = res.ids.shape[0]
        h = max(b // 2, 1)
        rows = jnp.arange(b) % h
        return res._replace(ids=res.ids[rows], scores=res.scores[rows])
    mp.setattr(ivf.IVF, "search_prepared", broken)


@pytest.mark.parametrize("cell", ["paper-serve-bulk", "paper-serve-open"])
def test_sound_run_is_correct(cell):
    run, res = tiny.run(cell)
    assert tiny.correct(res), res.checks
    assert res.info["compiles_in_window"] == 0
    assert res.failed == 0 and res.attempted > 0
    assert all(v > 0 for v in res.end_to_end.values())


@pytest.mark.parametrize("cell", ["paper-serve-bulk", "paper-serve-open"])
@pytest.mark.parametrize("fault", [half_batch, answer_altered],
                         ids=["half_batch", "answer_altered"])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    _, res = tiny.run(cell)
    assert not tiny.correct(res), res.checks


def test_open_loop_offers_the_same_load_for_every_seed():
    from bench.traffic import serve_open

    a = serve_open.arrival_times(500.0, 4.0, 1)
    b = serve_open.arrival_times(500.0, 4.0, 2**31 + 7)
    assert len(a) == len(b) == 2000
    assert a[0] == b[0] == 0.0
    assert sorted(a.round(9)) != list(b.round(9))
    gaps_a = sorted(a[1:] - a[:-1])
    gaps_b = sorted(b[1:] - b[:-1])
    assert a[-1] == pytest.approx(4.0, rel=0.01)
    assert sum(gaps_a) == pytest.approx(sum(gaps_b), rel=0.01)

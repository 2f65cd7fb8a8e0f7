"""The ``train_live`` driver end to end at a CPU size: a sound run comes
out correct, and each fault the training cell can have, planted in the
timed path, comes out not correct."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.tests import tiny

CELL = "paper-train-live"


def test_sound_run_is_correct():
    run, res = tiny.run(CELL)
    assert tiny.correct(res), res.checks
    assert res.info["compiles_in_window"] == 0
    assert res.attempted > 0 and res.end_to_end["train_step_ms"] > 0
    assert res.info["losses"] == pytest.approx(res.info["ref_losses"],
                                               rel=1e-5)
    assert res.info["deltas"] > res.info["steps"] > 0
    assert res.info["delta_scale"] > 0


def _state_unchanged(mp):
    from repro.training import train_state as ts

    orig = ts.make_train_step

    def broken(loss_fn, opt_cfg, **kw):
        step = orig(loss_fn, opt_cfg, **kw)

        def same(state, *batch):
            new, metrics = step(state, *batch)
            return new._replace(params=state.params), metrics
        return same
    mp.setattr(ts, "make_train_step", broken)


def _half_batch(mp):
    from repro.launch import train as train_lib

    orig = train_lib.make_loss_fn

    def broken(cfg, family):
        f = orig(cfg, family)
        return lambda p, h, pos: f(p, h[:h.shape[0] // 2],
                                   pos[:pos.shape[0] // 2])
    mp.setattr(train_lib, "make_loss_fn", broken)


def _refresh_skipped(mp):
    from repro.search import engine

    mp.setattr(engine.Engine, "refresh", lambda self, delta: None)


def _refresh_unmasked(mp):
    """The fused refresh's within-subspace mask dropped: the codebook-side
    product W stays the identity, so the query transform is Δ, not Δ·Wᵀ."""
    from repro.kernels import ops as kops
    from repro.search import flat

    def broken(R0, rot, wacc, pi, pj, theta, sub):
        rot = kops.apply_pair_rotations(rot, pi, pj, theta, use_kernel=False)
        return rot, wacc, R0.T @ rot @ wacc.T
    mp.setattr(flat, "_fused_refresh_mats", broken)


def answer_altered(mp):
    """The first answer of every query replaced by the next item id."""
    from repro.search import ivf

    orig = ivf.IVF.search_prepared

    def broken(self, state, QR, lut, **kw):
        res = orig(self, state, QR, lut, **kw)
        return res._replace(ids=res.ids.at[:, 0].set(
            jnp.where(res.ids[:, 0] >= 0, res.ids[:, 0] + 1, 0)))
    mp.setattr(ivf.IVF, "search_prepared", broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _refresh_skipped, _refresh_unmasked,
                                   answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "refresh_skipped", "refresh_unmasked",
                              "answer_altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    _, res = tiny.run(CELL)
    assert not tiny.correct(res), res.checks

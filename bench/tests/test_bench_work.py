"""Work counts against hand counts: what a step needs, whatever the
implementation schedules."""
from __future__ import annotations

import numpy as np
import pytest

from bench import work


def test_scan_counts_a_shared_list_once():
    # lists 0..3 hold 5, 0, 7 and 2 live rows; query 0 probes lists 0 and 2,
    # query 1 probes lists 2 and 3: list 2 is read once for the batch
    live = np.array([5, 0, 7, 2])
    lists = np.array([[0, 2], [2, 3]])
    flops, bytes_ = work.scan(lists, live, code_width=8, codewords=16, k=3)
    assert flops == (5 + 7 + 7 + 2) * 8
    assert bytes_ == (5 + 7 + 2) * (8 + 4) + 2 * 8 * 16 * 4 + 2 * 3 * 8


def test_live_rows_ignore_padding_and_holes():
    ids = np.array([4, 7, -1, -1, 2, -1, -1, -1, 9, -1, -1, -1])
    offsets = np.array([0, 4, 8, 8])
    assert list(work.live_rows_per_list(ids, offsets)) == [2, 1, 0]


def test_search_step_adds_rotate_probe_and_lut_to_the_scan():
    live = np.array([5, 0, 7, 2])
    lists = np.array([[0, 2], [2, 3]])
    f_scan, b_scan = work.scan(lists, live, code_width=8, codewords=16, k=3)
    f, b = work.search_batch(lists, live, dim=32, num_lists=4, code_width=8,
                             codewords=16, k=3)
    assert f == pytest.approx(f_scan + 2 * 2 * 32 * 32 * 2 + 2 * 2 * 32 * 16
                              + 2 * 2 * 32 * 4 + 2 * 4 * 1)
    assert b == b_scan + 2 * 32 * 4 + 2 * 32 * 32 * 4 + 4 * 32 * 4 \
        + 16 * 32 * 4


def test_train_step_adam_bytes():
    cfg = {"item_vocab": 1000, "embed_dim": 8, "tower_dims": [8, 4],
           "hist_len": 2, "index": {"dim": 4, "num_subspaces": 2,
                                    "num_codewords": 16}}
    P = work.twotower_params(cfg)
    # table, two towers (8·8+8 + 8·4+4 each), codebooks 16·4, R 4·4
    assert P == 1000 * 8 + 2 * (72 + 36) + 64 + 16
    flops, bytes_ = work.train_step(cfg, batch=4)
    adam = 7 * 4 * (P - 16)
    assert adam < bytes_ < adam * 1.1
    assert flops > 12 * (P - 16)


def test_traced_train_rate_is_read_over_the_traced_part():
    """Stopping the profiler stalls the host inside the window, so a run
    traced for part of its window reads its step rate over that part."""
    from types import SimpleNamespace as NS

    from bench import harness, peaks

    reader = harness.layer_reader("train.mfu")
    values = {"steps": 40, "window_s": 40.0, "step_flops": 0.0,
              "step_bytes": peaks.TABLE["TPU v5 lite"].hbm_bytes_per_s}
    run = NS(values=values, peaks=peaks.TABLE["TPU v5 lite"])
    assert reader.read(run, None) == pytest.approx(100.0)
    values.update(traced_units=30, traced_s=10.0)
    assert reader.read(run, None) == pytest.approx(300.0)

"""BENCHMARK.json against the rules it is held to, discovery of cells,
configurations and per-layer metrics by name, and the refusal to measure
anywhere but on a TPU the peaks table knows."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from bench import harness, peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.benchmark()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]]
             + list(CELLS) + list(E2E)
             + [m["name"] for m in BENCH["per_layer"]]
             + [w["config"] for w in CELLS.values()]
             + [w["traffic"] for w in CELLS.values()]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in BENCH[group]]
        assert len(seen) == len(set(seen)), group


def test_keys_bounds_and_sources():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in CELLS.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert E2E["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # every cell that reports it reports the metric it moves
        moved = E2E[m["moves"]]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS)


def test_every_cell_reports_enough():
    for cell in CELLS:
        e2e = [m for m in BENCH["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        layer = [m for m in BENCH["per_layer"]
                 if cell in m.get("workloads", CELLS)]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer, cell


def test_every_name_has_its_file():
    root = harness.ROOT
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    for c in BENCH["configs"]:
        assert os.path.isfile(root / c["file"])
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert harness.config(c["name"])["name"] == c["name"]
    for w in CELLS.values():
        wl = harness.workload(w["name"])
        assert isinstance(wl["limits"], dict) and wl["limits"]
        assert os.path.isfile(harness.BENCH / "traffic"
                              / f"{w['traffic']}.py")
    for m in BENCH["per_layer"]:
        assert callable(harness.layer_reader(m["name"]).read)


def test_a_cell_and_a_metric_are_added_by_adding_files(tmp_path):
    """A new configuration, cell and per-layer metric are found by name in
    a copy of the benchmark that only gained files."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = harness.config("paper-twotower")
    cfg.update(name="paper-twotower-x")
    (bench / "configs" / "paper-twotower-x.json").write_text(json.dumps(cfg))
    wl = harness.workload("paper-serve-open")
    wl["rate"] = 123.0
    (bench / "workloads" / "paper-serve-open-x.json").write_text(
        json.dumps(wl))
    (bench / "layers" / "new.metric.py").write_text(
        "def read(run, reduced):\n    return run.values.get('x')\n")
    assert harness.config("paper-twotower-x", bench)["name"] == \
        "paper-twotower-x"
    assert harness.workload("paper-serve-open-x", bench)["rate"] == 123.0
    assert hasattr(harness.driver("serve_open", bench), "run")
    spec = {"per_layer": BENCH["per_layer"] + [
        {"name": "new.metric", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "front end",
         "moves": "serve_p99_ms", "workloads": ["paper-serve-open-x"]}]}
    run = NS(name="paper-serve-open-x", values={"x": 2.5})
    assert harness.per_layer(run, spec, None, bench) == {
        "new.metric": {"value": 2.5, "unit": "ms"}}


def test_peaks_table_refuses_unknown_devices():
    assert peaks.lookup("tpu", "TPU v5 lite").hbm_bytes_per_s == 819e9
    for platform, kind in (("cpu", "cpu"), ("tpu", "TPU v9 imaginary"),
                           ("gpu", "TPU v5 lite")):
        with pytest.raises(peaks.UnknownDevice):
            peaks.lookup(platform, kind)


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "paper-serve-open", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=120,
        cwd=harness.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr

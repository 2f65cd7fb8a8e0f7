"""``chip_smoke.py``'s phases run end to end on the CPU at smoke size.

The script itself refuses to run without a TPU; these tests drive its
phase functions with the smoke config (``full=False``) and the jnp
references, so a change to the trainer, the searchers, the Engine or the
live loop that would break the chip run fails here first.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


def test_one_chip_phases_at_smoke_size(capsys):
    from repro import obs

    cs = _chip_smoke()
    with obs.override(True):
        cs.run_one_chip(cs.CompileClock(), seed=0, full=False, on_chip=False)
    out = capsys.readouterr().out
    # every phase reported, and refresh recompiled nothing
    for phase in ("[B]", "[C]", "[D]"):
        assert phase in out
    assert "kernel_vs_reference_top10_agree=1.0" in out


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke as cs\n"
            "cs.run_four_chips(cs.CompileClock(), seed=0, n_items=20000, "
            "dim=64, nq=64, full=False)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ids_identical=True scores_identical=True" in out.stdout

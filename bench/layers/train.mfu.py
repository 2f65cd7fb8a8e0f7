"""train.mfu: the whole train step's share of the chip's roofline, in %.

The larger of (step FLOPs / peak FLOP/s) and (step bytes / peak bytes/s),
counted from shapes by ``bench/work.py`` (both towers, the index layer and
the loss forward and backward, the dense Adam update, the GCD step), over
the measured time per step of the traced part of the window. At these
sizes the Adam update's HBM bytes bound it.
"""


def read(run, reduced):
    v = run.values
    steps = v.get("traced_units", v.get("steps"))
    if not steps:
        return None
    pk = run.peaks
    least = max(v["step_flops"] / pk.bf16_flops,
                v["step_bytes"] / pk.hbm_bytes_per_s)
    return 100.0 * least * steps / v.get("traced_s", v["window_s"])

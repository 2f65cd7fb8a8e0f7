"""lut.ms: device milliseconds of the fused LUT-build kernel per batch,
from the trace (kernels/lut_build.py ``fused_lut``, which shows in the trace under
that name)."""

NAMES = ("fused_lut",)


def read(run, reduced):
    v = run.values
    if reduced is None or not v.get("batches"):
        return None
    s = reduced.seconds_matching(*NAMES)
    return None if s is None else 1e3 * s / v["batches"]

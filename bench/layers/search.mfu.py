"""search.mfu: the whole search step's share of the chip's roofline, in %.

Per batch served in the traced window, the larger of FLOPs / peak FLOP/s
and bytes / peak bytes/s of rotate, query transform, LUT build, coarse probe,
scan and top-k (``bench/work.py search_batch``, from the lists the benchmark
probes itself), summed and divided by the window.
"""


def read(run, reduced):
    v = run.values
    if not v.get("step_roofline_s"):
        return None
    return 100.0 * v["step_roofline_s"] / v["window_s"]

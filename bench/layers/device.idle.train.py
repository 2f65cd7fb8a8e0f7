"""device.idle.train: share of the traced window in which no operation ran
on the device, in %, from the profiler trace (``bench/trace.py``)."""


def read(run, reduced):
    if reduced is None or reduced.chips == 0:
        return None
    return 100.0 * (1.0 - reduced.busy_s / reduced.window_s)

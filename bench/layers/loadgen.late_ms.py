"""loadgen.late_ms: 99th percentile of how late the load generator
submitted requests after their scheduled time, in ms (a starved generator
must not read as a fast server)."""

import numpy as np


def read(run, reduced):
    late = run.values.get("late_ms")
    if late is None or len(late) == 0:
        return None
    return float(np.percentile(late, 99))

"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What a TPU trace holds, as ``jax.profiler.ProfileData`` shows it: one plane
per chip (``/device:TPU:<i>``) whose ``XLA Ops`` line has one event per
device operation (HLO ops and Pallas kernels, named after their kernel
function) and whose ``XLA Modules`` line has one per compiled program run;
and host planes (``/host:CPU``) whose thread lines carry the
benchmark's ``TraceAnnotation`` spans. Times are nanoseconds on one clock.

The window is the span of the benchmark's ``bench.window`` annotation.
Busy time is the union of the device-op intervals inside it, per chip,
averaged over the chips used; an idle gap is a stretch of the window in
which no operation runs, labelled by the innermost ``bench.*`` span the
host was in at the gap's middle.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import NamedTuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Op(NamedTuple):
    name: str        # "<program>/<HLO instruction>"
    start: float     # ns
    end: float       # ns


class Reduced(NamedTuple):
    window_s: float
    busy_s: float                     # mean over the chips with ops
    chips: int
    op_seconds: dict                  # op name -> summed seconds (all chips)
    gaps: list                        # [(label, seconds)], longest first
    spans: dict                       # bench.* span name -> summed seconds

    def seconds_matching(self, *needles: str) -> float | None:
        """Summed device seconds of the ops whose instruction name contains
        any of ``needles``; None when no op matches."""
        hit = [s for n, s in self.op_seconds.items()
               if any(x in n.rsplit("/", 1)[-1] for x in needles)]
        return sum(hit) if hit else None

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(plane):
    for line in plane.lines:
        for ev in line.events:
            yield line.name, ev


def _short(name: str) -> str:
    """An op's HLO instruction name ("%ivf_adc.1 = f32[...] custom-call(...)"
    -> "ivf_adc.1"); Pallas kernels carry their kernel's name here."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module(modules, t: float) -> str:
    """"<program>/" of the compiled program running at time ``t`` (HLO
    instruction names repeat across programs), or "" when none does."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2] + "/"
    return ""


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def reduce(profile, *, max_gaps: int = 10) -> Reduced:
    """Reduce one profile (a ``ProfileData`` or a path) to window, busy
    time, per-op device seconds, labelled idle gaps and host span totals."""
    if isinstance(profile, str):
        profile = load(profile)
    spans = []                       # (start, end, name) of bench.* spans
    devices: dict[str, list[Op]] = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            events = list(_events(plane))
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name.split("(", 1)[0])
                             for line, ev in events if line == MODULES_LINE)
            ops = [Op(_module(modules, ev.start_ns) + _short(ev.name),
                      ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line, ev in events if line == OPS_LINE]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for _, ev in _events(plane)
                         if ev.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w0, w1 = max(windows, key=lambda se: se[1] - se[0])
    window_ns = w1 - w0

    op_seconds: dict[str, float] = {}
    busy, gaps = [], []
    for ops in devices.values():
        inside = [(max(o.start, w0), min(o.end, w1), o) for o in ops
                  if o.end > w0 and o.start < w1]
        for s, e, o in inside:
            op_seconds[o.name] = op_seconds.get(o.name, 0.0) + (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    labelled = sorted(((_label(inner, (s + e) / 2), (e - s) * 1e-9)
                       for s, e in gaps), key=lambda g: -g[1])
    span_totals: dict[str, float] = {}
    for s, e, n in inner:
        lo, hi = max(s, w0), min(e, w1)
        if hi > lo:
            span_totals[n] = span_totals.get(n, 0.0) + (hi - lo) * 1e-9
    n_chips = max(len(devices), 1)
    return Reduced(window_s=window_ns * 1e-9,
                   busy_s=sum(busy) / n_chips * 1e-9,
                   chips=len(devices), op_seconds=op_seconds,
                   gaps=labelled[:max_gaps],
                   spans=span_totals)


def _label(spans, t: float) -> str:
    """Innermost bench.* span covering time ``t`` (the shortest one)."""
    cover = [(e - s, n) for s, e, n in spans if s <= t < e]
    return min(cover)[1] if cover else "host: outside any bench span"

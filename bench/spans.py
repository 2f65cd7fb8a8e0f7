"""The program's own spans in a profiler trace, beside the benchmark's.

The program marks its host work with ``repro.obs.annotate`` (and registry
spans, which forward to it): ``train.*`` and ``pipeline.*`` in the
trainer and its prefetch worker, ``engine.*`` in ``search.Engine``,
``frontend.*`` in ``serve.Frontend`` (``obs.SPAN_PREFIXES``). They land on
the host plane's thread lines, on the device clock, whenever a profiler
session runs. ``reduce`` reads them with the benchmark's ``bench.*`` spans
and gives, inside the ``bench.window`` span:

- for each thread, each span name's total seconds, count and self seconds
  (its duration less the part its child spans on the same thread cover);
- the device's idle gaps (as ``bench/trace.py`` finds them), each labelled
  by the innermost span of either kind on the thread that holds
  ``bench.window``, so a gap inside the trainer's loop names the stage the
  trainer's thread was in, not a span of another thread.

``bench/trace.py`` reads only ``bench.*`` spans; a run reads these with
``reduce(trace.find_xplane(dir))`` on the same trace.
"""
from __future__ import annotations

from typing import NamedTuple

from bench import trace
from repro import obs

OUTSIDE = "host: outside any span"


class Spans(NamedTuple):
    window_thread: str
    threads: dict      # thread -> span name -> {"total_s", "count", "self_s"}
    gaps: list         # [(label, seconds)] of the device's idle gaps


def is_span(name: str) -> bool:
    """A span of the program or of the benchmark (not one of JAX's own
    host events)."""
    return name.startswith(obs.SPAN_PREFIXES + (trace.SPAN_PREFIX,))


def _host_threads(profile) -> dict:
    """thread line name -> [(start, end, name)] of its spans. Threads may
    share a name (a prefetch worker's line is named like the main
    thread's); a repeated name gets a ' appended per repeat."""
    out: dict[str, list] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for ev in line.events if is_span(ev.name)]
            if spans:
                key = line.name
                while key in out:
                    key += "'"
                out[key] = spans
    return out


def _idle(profile, w0: float, w1: float) -> list:
    """(start, end) of each stretch of the window in which no device op ran
    on a chip (per chip, as ``trace.reduce`` counts them)."""
    gaps = []
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        ops = [(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
               for line, ev in trace._events(plane)
               if line == trace.OPS_LINE
               and ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1]
        if not ops:
            continue
        edges = [w0] + [x for se in trace._union(ops) for x in se] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    return gaps


def self_times(spans, w0: float, w1: float) -> dict:
    """name -> {"total_s", "count", "self_s"} of one thread's spans, clipped
    to the window [w0, w1). Spans on one thread nest; a span's self time
    is its clipped duration less its children's."""
    out: dict[str, dict] = {}
    stack: list = []          # [(end, name, clipped duration, child time)]

    def close(entry):
        _, name, dur, child = entry
        acc = out.setdefault(name, {"total_s": 0.0, "count": 0,
                                    "self_s": 0.0})
        acc["total_s"] += dur * 1e-9
        acc["count"] += 1
        acc["self_s"] += (dur - child) * 1e-9
        if stack:
            stack[-1][3] += dur

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        lo, hi = max(s, w0), min(e, w1)
        if hi > lo and n != trace.WINDOW_SPAN:
            stack.append([e, n, hi - lo, 0.0])
    while stack:
        close(stack.pop())
    return out


def _label(spans, t: float) -> str:
    cover = [(e - s, n) for s, e, n in spans
             if s <= t < e and n != trace.WINDOW_SPAN]
    return min(cover)[1] if cover else OUTSIDE


def reduce(profile, *, max_gaps: int = 10) -> Spans:
    """Span totals, counts and self times per thread inside the window,
    and the longest idle gaps labelled on the window's thread."""
    if isinstance(profile, str):
        profile = trace.load(profile)
    threads = _host_threads(profile)
    windows = [(e - s, s, e, t) for t, spans in threads.items()
               for s, e, n in spans if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {trace.WINDOW_SPAN!r} span")
    _, w0, w1, wt = max(windows)
    gaps = sorted(((_label(threads[wt], (s + e) / 2), (e - s) * 1e-9)
                   for s, e in _idle(profile, w0, w1)),
                  key=lambda g: -g[1])
    return Spans(window_thread=wt,
                 threads={t: self_times(sp, w0, w1)
                          for t, sp in threads.items()},
                 gaps=gaps[:max_gaps])

"""Process-local metrics registry: counters, gauges, distributions, spans.

The serving/training stack needs to SEE itself run — latency percentiles,
scan work, rotation health — without paying for it when nobody is looking.
Three design rules govern everything here:

  * **near-free when disabled** — a disabled registry hands out shared
    null singletons: no metric objects are created, no events buffered, no
    host syncs happen. Instrumented hot paths cost one attribute lookup +
    one no-op call.
  * **host-side only** — metrics never enter a ``jax.jit`` trace. Spans
    that time device work declare the arrays to wait on via ``sync`` and
    the span blocks (``jax.block_until_ready``) before stopping the clock;
    values that turn out to be tracers (the span accidentally ran under a
    trace) are skipped rather than crashed on. For *inside-jit* visibility
    use ``jax.named_scope`` at the call site (trace-time, zero runtime
    cost, shows up in XLA profiles) — the sharded searcher does exactly
    that for its scan/merge stages.
  * **windows vs lifetimes** — counters and gauges are lifetime values;
    distributions keep lifetime count/sum/min/max plus a bounded sample
    window that the streaming percentiles (p50/p95/p99) are computed over.
    Every consumer that mixes the two (``Engine.stats()``) documents which
    is which.

Spans nest: the recorded name is the dotted path of enclosing spans
(``engine.search`` inside ``serve`` records ``serve.engine.search``), the
stack is per-thread, and an exception inside the span still records the
timing (with ``error=True``) and propagates. Every span also enters
``annotate(name)``, the one way the program marks a stretch of host work
for the profiler: a ``jax.profiler.TraceAnnotation``, which records
nothing unless a profiler session is running, so a span reaches the trace
(on the device clock, beside the device ops) exactly when someone traces.
Hot paths that need no registry timing call ``annotate`` directly.

Registries are process-local. Metric CREATION (the get-or-create in
``counter``/``gauge``/``distribution``/``event``) is guarded by a lock, so
threads racing to instrument the same name always share one object — the
background-compaction worker relies on this. Concurrent WRITERS to the
same metric remain single-writer by convention (same assumption as
``search.Engine``): writers on the poll thread, workers return values; the
span stack is per-thread so concurrent readers/writers of different
metrics are fine in practice.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Iterator

import jax

MetricKey = tuple[str, tuple[tuple[str, Any], ...]]

#: name prefixes of the program's own spans and annotations, which tell
#: them apart from JAX's own host events in a profiler trace
SPAN_PREFIXES = ("train.", "pipeline.", "engine.", "frontend.", "churn.")


def annotate(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span for the profiler's trace, with ``args`` as its metadata:
    a context manager that reads no clock, writes to no registry and makes
    no host sync; without a running profiler session it records nothing."""
    return jax.profiler.TraceAnnotation(name, **args)


def _key(name: str, labels: dict[str, Any]) -> MetricKey:
    return (name, tuple(sorted(labels.items())))


def _label_str(name: str, labels: tuple[tuple[str, Any], ...]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labels)
    return f"{name}{{{inner}}}"


class Counter:
    """Monotonic lifetime count (requests served, compiles, cache hits)."""

    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: tuple[tuple[str, Any], ...] = ()):
        self.name = name
        self.labels = labels
        self.value = 0

    def inc(self, n: int | float = 1) -> None:
        self.value += n


class Gauge:
    """Last-written value (live recall, shard imbalance, orthogonality)."""

    __slots__ = ("name", "labels", "value", "updates")

    def __init__(self, name: str, labels: tuple[tuple[str, Any], ...] = ()):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self.updates = 0

    def set(self, v: float) -> None:
        self.value = float(v)
        self.updates += 1


class Distribution:
    """Lifetime count/sum/min/max + a bounded sample window for streaming
    percentiles. ``summary()`` labels which aggregates are which."""

    __slots__ = ("name", "labels", "count", "total", "min", "max", "_window")

    def __init__(self, name: str, labels: tuple[tuple[str, Any], ...] = (),
                 window: int = 1024):
        self.name = name
        self.labels = labels
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._window: collections.deque[float] = collections.deque(
            maxlen=max(1, window))

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        self._window.append(v)

    def window_values(self) -> list[float]:
        return list(self._window)

    def percentile(self, q: float) -> float:
        """Linear-interpolated percentile over the retained window."""
        w = sorted(self._window)
        if not w:
            return 0.0
        pos = (q / 100.0) * (len(w) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(w) - 1)
        frac = pos - lo
        return w[lo] * (1.0 - frac) + w[hi] * frac

    def summary(self) -> dict:
        w = list(self._window)
        return dict(
            count=self.count,                       # lifetime
            total=self.total,                       # lifetime
            min=self.min if self.count else 0.0,    # lifetime
            max=self.max if self.count else 0.0,    # lifetime
            window=len(w),
            mean=(sum(w) / len(w)) if w else 0.0,   # window-scoped ↓
            p50=self.percentile(50.0),
            p95=self.percentile(95.0),
            p99=self.percentile(99.0),
        )


class _NullMetric:
    """Shared no-op stand-in handed out by a disabled registry."""

    __slots__ = ()
    value = 0
    updates = 0
    count = 0
    total = 0.0

    def inc(self, n: int | float = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    def percentile(self, q: float) -> float:
        return 0.0

    def window_values(self) -> list[float]:
        return []

    def summary(self) -> dict:
        return {}


_NULL_METRIC = _NullMetric()


class _NullSpan:
    """No-op span (stateless, so one shared instance nests safely)."""

    __slots__ = ()
    elapsed_ms = 0.0
    path = ""

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def sync(self, value):
        return value


_NULL_SPAN = _NullSpan()


def _block_concrete(value) -> None:
    """block_until_ready on everything in ``value`` that is concrete —
    tracers (a span that ran under a jit trace) are skipped, not crashed
    on."""
    leaves = [x for x in jax.tree_util.tree_leaves(value)
              if not isinstance(x, jax.core.Tracer)]
    if leaves:
        jax.block_until_ready(leaves)


class Span:
    """Timing span: records a ``span.<path>.ms`` distribution + one event.

    ``sync(value)`` registers device values the span must wait on before
    stopping the clock, so async-dispatched work is charged to the span
    that launched it. Exception-safe: the timing records either way, with
    ``error=True`` on the failure path, and the exception propagates.
    """

    __slots__ = ("_registry", "name", "path", "_t0", "_pending",
                 "elapsed_ms", "_annotation")

    def __init__(self, registry: "Registry", name: str):
        self._registry = registry
        self.name = name
        self.path = name
        self._t0 = 0.0
        self._pending: list = []
        self.elapsed_ms = 0.0
        self._annotation = None

    def sync(self, value):
        """Register ``value`` (array/pytree) to block on at span exit.
        Returns it unchanged so call sites stay one-liners."""
        self._pending.append(value)
        return value

    def __enter__(self) -> "Span":
        stack = self._registry._span_stack()
        self.path = ".".join([*stack, self.name]) if stack else self.name
        stack.append(self.name)
        self._annotation = annotate(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._pending:
                _block_concrete(self._pending)
        finally:
            self.elapsed_ms = (time.perf_counter() - self._t0) * 1e3
            self._annotation.__exit__(exc_type, exc, tb)
            stack = self._registry._span_stack()
            if stack and stack[-1] == self.name:
                stack.pop()
            self._registry.distribution(
                f"span.{self.path}.ms").observe(self.elapsed_ms)
            self._registry.event(
                "span", name=self.path, ms=self.elapsed_ms,
                error=exc_type is not None)
        return False


class Registry:
    """One process-local metrics namespace (see module docstring).

    ``window`` bounds both distribution sample windows and per-kind event
    windows.
    """

    def __init__(self, *, enabled: bool = True, window: int = 1024):
        self.enabled = enabled
        self.window = max(1, window)
        self._metrics: dict[MetricKey, Any] = {}
        self._events: dict[str, collections.deque] = {}
        self._sinks: list = []
        self._local = threading.local()
        self._create_lock = threading.Lock()

    # -- metric accessors (get-or-create) ----------------------------------
    def _get(self, cls, name: str, labels: dict, **kw):
        if not self.enabled:
            return _NULL_METRIC
        key = _key(name, labels)
        m = self._metrics.get(key)
        if m is None:
            # creation is locked so racing threads share ONE metric object
            # (two Counter instances under one key would tear increments)
            with self._create_lock:
                m = self._metrics.get(key)
                if m is None:
                    m = cls(name, key[1], **kw)
                    self._metrics[key] = m
        return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def distribution(self, name: str, **labels) -> Distribution:
        return self._get(Distribution, name, labels, window=self.window)

    # -- spans --------------------------------------------------------------
    def _span_stack(self) -> list[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        return stack

    def span(self, name: str) -> Span | _NullSpan:
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name)

    # -- events -------------------------------------------------------------
    def event(self, kind: str, **fields) -> None:
        """Append one structured event (bounded per-kind window) and fan it
        out to the attached sinks (JSONL)."""
        if not self.enabled:
            return
        rec = {"kind": kind, "t": time.time(), **fields}
        win = self._events.get(kind)
        if win is None:
            with self._create_lock:
                win = self._events.get(kind)
                if win is None:
                    win = collections.deque(maxlen=self.window)
                    self._events[kind] = win
        win.append(rec)
        for sink in self._sinks:
            sink.write(rec)

    def events(self, kind: str | None = None) -> list[dict]:
        if kind is not None:
            return list(self._events.get(kind, ()))
        return [r for win in self._events.values() for r in win]

    def add_sink(self, sink) -> None:
        self._sinks.append(sink)

    # -- inspection ---------------------------------------------------------
    def metrics(self) -> Iterator[Any]:
        return iter(self._metrics.values())

    def snapshot(self) -> dict:
        """Nested plain-dict view: counters/gauges as values, distributions
        as ``summary()`` dicts — the JSON-ready export surface."""
        out: dict[str, dict] = {"counters": {}, "gauges": {},
                                "distributions": {}}
        for m in self._metrics.values():
            label = _label_str(m.name, m.labels)
            if isinstance(m, Counter):
                out["counters"][label] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][label] = m.value
            else:
                out["distributions"][label] = m.summary()
        return out

    def reset(self) -> None:
        """Drop every metric, event window, and sink (tests; start-of-run)."""
        self._metrics.clear()
        self._events.clear()
        for sink in self._sinks:
            close = getattr(sink, "close", None)
            if close:
                close()
        self._sinks.clear()


# ---------------------------------------------------------------------------
# The global default registry: disabled until someone asks to watch.
# ---------------------------------------------------------------------------

_default = Registry(enabled=False)


def default_registry() -> Registry:
    return _default


def enabled() -> bool:
    return _default.enabled


def enable(*, jsonl: str | None = None) -> Registry:
    """Turn the global registry on (optionally attaching a JSONL event
    log)."""
    _default.enabled = True
    if jsonl is not None:
        from repro.obs.export import JsonlSink

        _default.add_sink(JsonlSink(jsonl))
    return _default


def disable() -> None:
    _default.enabled = False


@contextlib.contextmanager
def override(enabled_: bool = True):
    """Temporarily flip the global registry's enabled flag (tests)."""
    prev = _default.enabled
    _default.enabled = enabled_
    try:
        yield _default
    finally:
        _default.enabled = prev


# Module-level conveniences over the default registry — instrumented library
# code calls these so a single ``obs.enable()`` lights everything up.
def counter(name: str, **labels) -> Counter:
    return _default.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return _default.gauge(name, **labels)


def distribution(name: str, **labels) -> Distribution:
    return _default.distribution(name, **labels)


def span(name: str) -> Span | _NullSpan:
    return _default.span(name)


def event(kind: str, **fields) -> None:
    _default.event(kind, **fields)

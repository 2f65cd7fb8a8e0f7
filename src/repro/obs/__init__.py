"""repro.obs — unified observability: metrics, spans, exporters, probes.

One lightweight subsystem watches every layer of the stack:

  * **registry** (``Registry``/``counter``/``gauge``/``distribution``) —
    process-local metrics with streaming window percentiles; the global
    default registry is DISABLED until ``obs.enable()`` and disabled
    instrumentation is near-free (shared null objects, no host syncs).
  * **spans** (``span``) — nestable, exception-safe timing blocks that can
    ``sync`` on device values (block_until_ready-aware). Each also enters
    ``annotate(name)``, a ``jax.profiler.TraceAnnotation``: it records
    nothing unless a profiler session runs, and then lands in the trace on
    the device clock. Hot paths call ``annotate`` alone (no clock, no
    registry, no sync); ``SPAN_PREFIXES`` lists the program's span names'
    prefixes for whoever reduces a trace. Use ``jax.named_scope`` for
    inside-jit stages (``gcd`` in the GCD update, ``ivf.select`` in the
    IVF search's candidate gather and top-k).
  * **exporters** — JSONL event log (``enable(jsonl=...)``), text
    snapshot (``report``), and the ``BENCH_*.json`` trajectory writer +
    validator (``write_bench``/``validate_bench``) that the benchmark
    harness emits through.
  * **probes** (``RecallProbe``) — pinned-query recall@k replayed through
    the serving path, so a bad rotation refresh shows up as a quality
    regression, not just a latency blip.

Who emits what: ``search.Engine`` (request latency p50/p99, bucket/pad
waste, LUT hit rate, compile counts, scheduled and real scan tile rows —
via its always-on private registry behind ``stats()``; ``engine.*``
annotations around submit, its rotate/LUT/dispatch stages, and collect),
``serve.Frontend`` (``frontend.poll``/``frontend.serve`` annotations),
``launch.train`` and ``data.pipeline`` (``train.*`` and ``pipeline.*``
annotations around each step's batch wait, dispatch and loss read, and the
prefetch worker's batch), ``search.sharded`` (per-shard rows, shard-imbalance
gauge, named-scope scan/merge spans), ``index.maintain`` (refresh spans,
delta norm, orthogonality drift), ``launch.train --obs-log`` (step time,
loss, rotation health), ``quant.kmeans`` (per-iteration distortion trace), and
``benchmarks/*`` (the BENCH trajectory).
"""
from repro.obs.bench import (
    SCHEMA as BENCH_SCHEMA,
    bench_path,
    load_bench,
    validate_bench,
    write_bench,
)
from repro.obs.export import JsonlSink, jsonable, read_jsonl, text_report
from repro.obs.probe import RecallProbe
from repro.obs.registry import (
    Counter,
    Distribution,
    Gauge,
    Registry,
    SPAN_PREFIXES,
    Span,
    annotate,
    counter,
    default_registry,
    disable,
    distribution,
    enable,
    enabled,
    event,
    gauge,
    override,
    span,
)

__all__ = [
    "BENCH_SCHEMA",
    "Counter",
    "Distribution",
    "Gauge",
    "JsonlSink",
    "RecallProbe",
    "Registry",
    "SPAN_PREFIXES",
    "Span",
    "annotate",
    "bench_path",
    "counter",
    "default_registry",
    "disable",
    "distribution",
    "enable",
    "enabled",
    "event",
    "gauge",
    "jsonable",
    "load_bench",
    "override",
    "read_jsonl",
    "span",
    "text_report",
    "validate_bench",
    "write_bench",
]


def report(registry: Registry | None = None) -> str:
    """Text snapshot of ``registry`` (default: the global registry)."""
    return text_report(registry if registry is not None else default_registry())

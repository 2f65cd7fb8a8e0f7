"""Shared machinery of one benchmark run: the device check, the compile
cache, the set-up clock, the measured window and its trace, the per-layer
readers, and the result line.

A run is one process: ``run.py`` loads the cell's workload and
configuration files by name, hands a ``Run`` to the traffic driver named by
the workload, and prints what the driver returns. Drivers call
``run.open_window()`` once set-up is done and ``run.close_window()`` when
the measured seconds have passed; everything between is the window.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import time
from pathlib import Path
from typing import Any, NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: is not set: a fixed path inside the checkout (the path is part of the
#: cache key, so it must not move between runs)
CACHE_DIR = ROOT / ".bench_cache" / "jax"
OUT_DIR = ROOT / ".bench_cache" / "out"


class Result(NamedTuple):
    """What a traffic driver hands back after its window and its checks."""

    correct: bool           # the run's own verdict besides the checks
    attempted: int
    failed: int
    end_to_end: dict        # metric name -> value (host clock)
    checks: dict            # number name -> {"value", "limit"}
    memory_peak_bytes: int
    reduced: Any = None     # trace.Reduced of a traced run
    info: dict = {}         # plain readings printed beside the result


def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Every cell, configuration, traffic driver and per-layer metric is found
# by its name in BENCHMARK.json: adding one is adding its file.

def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "workloads" / f"{name}.json")


def config(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "configs" / f"{name}.json")


def driver(traffic: str, bench: Path = BENCH):
    return load_module(bench / "traffic" / f"{traffic}.py",
                       f"bench_traffic_{traffic}")


def layer_reader(metric: str, bench: Path = BENCH):
    return load_module(bench / "layers" / f"{metric}.py",
                       "bench_layer_" + metric.replace(".", "_"))


def seed31(seed: int) -> int:
    """The run's seed folded into 31 bits (JAX and numpy seeds); distinct
    driver seeds stay distinct for any seed below 2**31 - 1."""
    return int(seed) % (2**31 - 1)


def check_device(chips: int):
    """(device, peaks) of the chip this run measures on; exits non-zero
    with a message when JAX finds no TPU in the peaks table or too few."""
    import jax

    from bench import peaks

    dev = jax.devices()[0]
    try:
        pk = peaks.lookup(dev.platform, dev.device_kind)
    except peaks.UnknownDevice as e:
        sys.exit(f"bench: refusing to measure: {e}")
    if len(jax.devices()) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{len(jax.devices())}")
    return dev, pk


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: JAX_COMPILATION_CACHE_DIR when
    set, else a fixed directory inside the checkout. Every program is
    written to it, however short its compile, so a second run compiles
    nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events (copied from chip_smoke.py)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _usage() -> dict:
    """This process's CPU seconds, page faults that read the disk, and
    context switches so far (``getrusage``)."""
    u = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_s": u.ru_utime + u.ru_stime, "major_faults": u.ru_majflt,
            "preempted": u.ru_nivcsw, "waits": u.ru_nvcsw}


class Run:
    """One run of one cell (see module docstring)."""

    def __init__(self, *, name: str, workload: dict, config: dict,
                 seed: int, seconds: float, trace: bool, t_start: float,
                 devices=None, peaks=None):
        self.name = name
        self.workload = workload
        self.config = config
        self.seed = seed31(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.devices = devices
        self.peaks = peaks
        # what per-layer readers (and bench/control.py) read
        self.values: dict[str, Any] = {}
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.setup_s: float | None = None
        self.compile_in_window = 0
        self.compile_clock: CompileClock | None = None
        self.host: dict = {}     # process usage over the window (_usage)
        self._usage_open: dict = {}
        self._window_span = None
        self._trace_dir: str | None = None

    # -- window ------------------------------------------------------------
    def watch_compiles(self) -> None:
        self.compile_clock = CompileClock()

    def open_window(self) -> None:
        # what set-up made (compiled programs, traced graphs: some 10^5–10^6
        # objects) leaves the garbage collector's reach, as a latency-bound
        # Python service does after warm-up; a full collection over them
        # otherwise stalls the host ~0.1–0.5 s at random points of a window
        gc.collect()
        gc.freeze()
        if self.trace:
            import jax

            self._trace_dir = str(OUT_DIR / f"trace-{self.name}-{os.getpid()}")
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
        clock = self.compile_clock
        self._compiles_at_open = clock.compiles if clock else 0
        self._usage_open = _usage()
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_start

    def close_window(self) -> None:
        self.t_close = time.perf_counter()
        self.host = {k: v - self._usage_open[k]
                     for k, v in _usage().items()}
        gc.unfreeze()
        if self.compile_clock is not None:
            self.compile_in_window = (self.compile_clock.compiles
                                      - self._compiles_at_open)
        self._stop_trace()

    def tick(self, done: int) -> None:
        """Called by a driver after each unit of work, with the units done
        in the window so far: ends the trace once the workload's
        ``trace_seconds`` of the window are traced (a long window of small
        device ops overruns the profiler's device buffer, which then drops
        events). Stopping the profiler holds the host for tens of seconds
        inside the window, so a traced run's rates are read over the
        traced part: ``traced_units`` done in ``traced_s``."""
        limit = self.workload.get("trace_seconds")
        now = time.perf_counter()
        if (self._window_span is not None and limit is not None
                and now - self.t_open >= limit):
            self.values.update(traced_units=done, traced_s=now - self.t_open)
            self._stop_trace()

    def _stop_trace(self) -> None:
        if self._window_span is not None:
            import jax

            self._window_span.__exit__(None, None, None)
            self._window_span = None
            jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: a ``TraceAnnotation`` in traced runs (so the trace
        can label idle gaps), and its host seconds added to
        ``values["span_s"][name]`` either way."""
        t0 = time.perf_counter()
        if self.trace:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        acc = self.values.setdefault("span_s", {})
        acc[name] = acc.get(name, 0.0) + time.perf_counter() - t0

    def reduce_trace(self):
        from bench import trace as trace_lib

        red = trace_lib.reduce(trace_lib.find_xplane(self._trace_dir))
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        return red


def device_info(run: Run) -> dict:
    d = run.devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(run.devices)}


def per_layer(run: Run, bench: dict, reduced, root: Path = BENCH) -> dict:
    """Every per-layer metric of BENCHMARK.json that applies to this cell,
    read by its reader; a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and run.name not in m["workloads"]:
            continue
        value = layer_reader(m["name"], root).read(run, reduced)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(reduced) -> dict:
    return {"device_ops": [[n, s] for n, s in reduced.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in reduced.gaps[:10]]}


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)


def checks_pass(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

"""Checkpointable, sharding-aware input pipeline with async host prefetch.

Every generator in data/synthetic.py is a pure function of (seed, step), so
pipeline state is just ``{"seed", "step"}`` — restarts and elastic re-meshes
resume exactly (the batch for step k is identical no matter the mesh). The
pipeline device_puts each batch with the step function's input shardings so
pjit never reshuffles input data.

``prefetch=True`` double-buffers the host side: while the trainer runs step
k, a worker thread generates batch k+1 and ``device_put``s it with the same
shardings, so the step loop never stalls on host batch synthesis or the
host→device copy. Because batches are pure functions of (seed, step), the
prefetched stream is bit-identical to the synchronous one, and
checkpoint/restore stays trivial: ``state()`` reports the step of the next
*unconsumed* batch and ``restore()`` simply discards any in-flight prefetch
(the batch is regenerated from (seed, step) — nothing is lost).
"""
from __future__ import annotations

import concurrent.futures
from typing import Any, Callable

import jax

from repro import obs


class Pipeline:
    """Wraps ``make_batch(key) -> pytree`` into a stateful, resumable iterator.

    ``prefetch`` enables the one-ahead background buffer (see module
    docstring). ``prefetch_hits`` / ``prefetch_misses`` count whether the
    batch for a step was already waiting when the trainer asked for it — a
    persistent miss stream means batch synthesis is slower than the train
    step and the prefetch depth (one) is the bottleneck. When a
    ``registry`` (``repro.obs.Registry``) is supplied the same counts land
    on ``pipeline.prefetch_hits`` / ``pipeline.prefetch_misses``.
    """

    def __init__(self, make_batch: Callable[[jax.Array], Any], seed: int = 0,
                 shardings: Any | None = None, prefetch: bool = False,
                 registry: Any | None = None):
        self._make = make_batch
        self._seed = seed
        self._step = 0
        self._shardings = shardings
        self._registry = registry
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self._pool = (concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pipeline-prefetch")
            if prefetch else None)
        self._inflight: tuple[int, concurrent.futures.Future] | None = None

    @property
    def prefetch(self) -> bool:
        return self._pool is not None

    def state(self) -> dict:
        """Step of the next unconsumed batch — an in-flight prefetch is NOT
        consumed, so a restore from this state replays it exactly."""
        return {"seed": self._seed, "step": self._step}

    def restore(self, state: dict) -> None:
        self._seed = int(state["seed"])
        self._step = int(state["step"])
        # drop any in-flight prefetch: it was generated for the old cursor;
        # the batch at the restored step regenerates from (seed, step)
        self._inflight = None

    def peek_key(self) -> jax.Array:
        return jax.random.fold_in(jax.random.PRNGKey(self._seed), self._step)

    def _produce(self, step: int):
        with obs.annotate("pipeline.produce", step=step):
            key = jax.random.fold_in(jax.random.PRNGKey(self._seed), step)
            batch = self._make(key)
            if self._shardings is not None:
                batch = jax.device_put(batch, self._shardings)
            return batch

    def _count(self, name: str) -> None:
        if self._registry is not None:
            self._registry.counter(f"pipeline.{name}").inc()

    def __iter__(self):
        return self

    def __next__(self):
        if self._pool is None:
            batch = self._produce(self._step)
            self._step += 1
            return batch
        if self._inflight is not None and self._inflight[0] == self._step:
            batch = self._inflight[1].result()
            self.prefetch_hits += 1
            self._count("prefetch_hits")
        else:
            # cold start, post-restore, or a stale in-flight slot: produce
            # synchronously (the miss is counted — steady state hits)
            batch = self._produce(self._step)
            self.prefetch_misses += 1
            self._count("prefetch_misses")
        self._step += 1
        self._inflight = (self._step,
                          self._pool.submit(self._produce, self._step))
        return batch

    def close(self) -> None:
        """Shut the prefetch worker down (idempotent; sync pipelines no-op)."""
        if self._pool is not None:
            self._inflight = None
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None

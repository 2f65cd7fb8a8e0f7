"""LiveIndexLoop: trainer → live index, through the zero-recompile path.

The glue between ``make_train_step(emit_deltas=True)`` and a serving
``search.Engine``. Each training step's manifold update already computes
the exact ``RotationDelta`` it applied to R; this loop buffers them and,
every ``refresh_every`` steps, replays them onto the live index via
``Engine.refresh`` — a shape-preserving state swap under the cached
executables, so keeping the index aligned with the trainer costs zero
recompiles and no rebuild.

Freshness accounting: the non-fused refresh drops cross-subspace angles
when absorbing a delta into product codebooks (``maintain.refresh_delta``),
so each applied delta leaves stored codes ~1% drifted from a fresh encode.
Every refresh round bumps the ``StalenessTracker`` epoch; the attached
``BackgroundCompactor`` re-encodes the stalest rows inside its next pass
(off-thread), so drift is repaid continuously instead of with stop-the-
world rebuilds.

Single-thread driver: call ``on_step(metrics)`` from the training loop
after each step. The only work on the training thread is the (cheap,
jit'd) refresh and a non-blocking compactor poll/submit.
"""
from __future__ import annotations

from repro import obs


class LiveIndexLoop:
    """Drive a live Engine from per-step rotation deltas (module docstring).

    ``delta_key`` names the manifold leaf in ``metrics["rotation_deltas"]``
    that rotates the index (the trainer may carry others, e.g. KV-cache
    rotations). ``compact_every`` counts refresh rounds between compaction
    submits (0 = never submit; the caller owns compaction cadence).
    """

    def __init__(self, engine, *, delta_key: str = "R",
                 refresh_every: int = 8, tracker=None, compactor=None,
                 compact_every: int = 4, registry=None):
        self.engine = engine
        self.delta_key = delta_key
        self.refresh_every = max(1, int(refresh_every))
        self.tracker = tracker
        self.compactor = compactor
        self.compact_every = int(compact_every)
        self.obs = (registry if registry is not None
                    else getattr(engine, "obs", None) or
                    obs.default_registry())
        self._buffer: list = []
        self._steps = 0
        self._rounds = 0

    def on_step(self, metrics: dict) -> None:
        """Consume one training step's metrics: buffer its delta, refresh
        on cadence, keep the background compactor moving."""
        self._steps += 1
        deltas = metrics.get("rotation_deltas")
        if deltas is not None:
            if self.delta_key not in deltas:
                # a key miss here would otherwise be a silent no-op for the
                # whole run — the trainer emits the same leaves every step
                raise KeyError(
                    f"LiveIndexLoop: delta_key {self.delta_key!r} not in "
                    f"emitted rotation deltas {sorted(deltas)} — pass "
                    f"delta_key= matching the trainer's manifold leaf")
            self._buffer.append(deltas[self.delta_key])
        if self.compactor is not None:
            self.compactor.poll()
        if self._steps % self.refresh_every == 0:
            self.flush_refresh()

    def flush_refresh(self) -> int:
        """Apply every buffered delta to the live index, in step order.
        Returns the number applied. Bumps the staleness epoch once per
        delta (each one drifts the stored codes a little further) and
        submits a background compaction every ``compact_every`` rounds."""
        applied = len(self._buffer)
        if applied:
            with obs.annotate("pipeline.refresh", deltas=applied):
                for delta in self._buffer:
                    self.engine.refresh(delta)
            self._buffer.clear()
            if self.tracker is not None:
                self.tracker.bump(applied)
            self.obs.counter("pipeline.refreshes").inc()
            self.obs.counter("pipeline.deltas_applied").inc(applied)
            self._rounds += 1
            if (self.compactor is not None and self.compact_every > 0
                    and self._rounds % self.compact_every == 0):
                self.compactor.submit()
        return applied

    def drain(self) -> None:
        """End of training: apply stragglers and land the last compaction
        pass (join → poll → swap)."""
        self.flush_refresh()
        if self.compactor is not None:
            self.compactor.join()
            self.compactor.poll()

    def stats(self) -> dict:
        return dict(
            steps=self._steps,
            refresh_rounds=self._rounds,
            buffered=len(self._buffer),
            refreshes=self.obs.counter("pipeline.refreshes").value,
            deltas_applied=self.obs.counter(
                "pipeline.deltas_applied").value,
            staleness_epoch=(self.tracker.epoch
                             if self.tracker is not None else 0),
            tracked_rows=(len(self.tracker)
                          if self.tracker is not None else 0),
        )

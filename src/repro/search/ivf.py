"""IVF searcher: probe top-``nprobe`` lists, scan only their tiles.

The production backend — wraps the ``index/search.py`` probe/scan pipeline
(coarse probe → per-query ADC LUT → fused Pallas selected-block scan →
masked top-k) behind the Searcher protocol. Scan work per query is
≈ ``nprobe/num_lists`` of the corpus; ``nprobe`` is the only serving knob
and can be overridden per call (the Engine keys its compile cache on it).

Shares ``ADCState`` with the ``flat_adc`` backend: ``attach`` the same
index to both and ``nprobe = num_lists`` reproduces the flat scan exactly
(the registry's internal consistency check). ``refresh`` absorbs a
disjoint GivensDelta via ``maintain.refresh_delta`` — centroids, codebooks
and R rotate in O(n²); codes and the CSR layout (hence ``max_blocks`` and
every compiled executable) are untouched.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax

from repro import rotations
from repro.churn import buffer as churn_buffer
from repro.index import ivf as index_ivf
from repro.index import search as index_search
from repro.index.ivf import IVFPQIndex
from repro.kernels.common import use_kernels
from repro.search import flat
from repro.search.base import SearchConfig, SearchResult
from repro.search.flat import ADCState, _adc_stats, _refresh


@dataclasses.dataclass(frozen=True)
class IVF:
    """Registry backend ``"ivf"`` (see module docstring)."""

    name: ClassVar[str] = "ivf"

    def build(self, key: jax.Array, corpus: jax.Array, R: jax.Array,
              cfg: SearchConfig) -> ADCState:
        index = index_ivf.build(key, corpus, R, cfg.ivf_config(),
                                train_size=cfg.train_size)
        return self.attach(index, nprobe=cfg.nprobe,
                           use_kernel=cfg.use_kernel,
                           lut_dtype=cfg.lut_dtype,
                           fused_refresh=cfg.fused_refresh)

    @staticmethod
    def attach(index: IVFPQIndex, *, nprobe: int = 8,
               use_kernel: bool | None = None, lut_dtype: str = "float32",
               fused_refresh: bool = False) -> ADCState:
        """State over an existing index (captures the static probe window)."""
        state = ADCState(index=index,
                         nprobe=min(nprobe, index.num_lists),
                         max_blocks=index.max_list_blocks(),
                         use_kernel=use_kernels(use_kernel),
                         lut_dtype=lut_dtype)
        return flat._fused_state(state) if fused_refresh else state

    def effective_nprobe(self, state: ADCState, nprobe: int | None) -> int:
        """The probe width actually served: the request's (or the state's
        default), capped at num_lists. Also an Engine capability — the
        compile cache keys on the clamped value so oversized requests
        share one executable."""
        return min(state.nprobe if nprobe is None else nprobe,
                   state.index.num_lists)

    @staticmethod
    def _max_blocks(state: ADCState) -> int:
        """The static probe window: baked by ``attach``, or derived from the
        index (one host sync) for a directly-constructed state."""
        if state.max_blocks >= 1:
            return state.max_blocks
        return state.index.max_list_blocks()

    def scheduled_rows(self, state: ADCState, nprobe: int | None) -> int:
        """Engine capability: rows the scan is scheduled to read per query —
        ``max_blocks`` whole tiles of each probed list, sentinel hole
        tiles of short lists included, plus the staging buffer's side
        pass. ``SearchResult.scanned`` counts the real ones among them."""
        rows = (self.effective_nprobe(state, nprobe)
                * self._max_blocks(state) * state.index.block_size)
        if state.staging is not None:
            rows += state.staging.ids.size
        return rows

    def prepare_state(self, state: ADCState) -> ADCState:
        """Bake derived statics into the state so it can be passed as a
        *traced* jit argument (the Engine does this once up front — the
        ``max_blocks`` fallback host-syncs on concrete offsets, which a
        tracer cannot satisfy)."""
        if state.max_blocks >= 1:
            return state
        return dataclasses.replace(
            state, max_blocks=state.index.max_list_blocks())

    def search(self, state: ADCState, Q: jax.Array, *, k: int = 10,
               nprobe: int | None = None) -> SearchResult:
        if state.qdelta is not None or state.staging is not None:
            # fused mode (LUT build must route through the accumulated
            # query-side transform) and live churn (staged rows merge after
            # the main scan) both go via the prepared path
            QR = flat._rotate_queries(state, Q)
            return self.search_prepared(state, QR, flat._luts(state, QR),
                                        k=k, nprobe=nprobe)
        return index_search.search_fixed(
            state.index, Q, nprobe=self.effective_nprobe(state, nprobe), k=k,
            max_blocks=self._max_blocks(state), use_kernel=state.use_kernel,
            lut_dtype=state.lut_dtype)

    # -- Engine LUT-cache capabilities -------------------------------------
    def rotate_queries(self, state: ADCState, Q: jax.Array) -> jax.Array:
        return flat._rotate_queries(state, Q)

    def luts(self, state: ADCState, QR: jax.Array):
        return flat._luts(state, QR)

    def luts_refresh_invariant(self, state: ADCState,
                               delta: rotations.RotationDelta) -> bool:
        return flat._luts_refresh_invariant(state, delta)

    def search_prepared(self, state: ADCState, QR: jax.Array,
                        lut, *, k: int = 10,
                        nprobe: int | None = None) -> SearchResult:
        res = index_search.search_prepared(
            state.index, QR, lut, nprobe=self.effective_nprobe(state, nprobe),
            k=k, max_blocks=self._max_blocks(state),
            use_kernel=state.use_kernel)
        if state.staging is not None:
            # live churn: staged rows ride a flat-ADC side pass over the
            # same LUT pack and merge into the probed top-k
            res = churn_buffer.merge_staged(
                res, state.staging, QR, lut, state.index.centroids, k,
                use_kernel=state.use_kernel)
        return res

    def refresh(self, state: ADCState,
                delta: rotations.RotationDelta) -> ADCState:
        return _refresh(state, delta)

    def stats(self, state: ADCState) -> dict:
        st = _adc_stats(self.name, state)
        mb = self._max_blocks(state)
        st["nprobe"] = state.nprobe
        st["max_blocks"] = mb
        st["scan_rows_per_query"] = min(
            state.nprobe * mb * state.index.block_size, st["capacity"])
        return st

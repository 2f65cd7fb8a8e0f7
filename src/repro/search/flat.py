"""Flat-ADC searcher: full PQ/RQ scan via the shared ADC kernels.

Scores every CSR row of an IVF-PQ/RQ index (coarse term + residual LUT
sum, ``kernels/adc_lookup``) — the quantized-but-unprobed middle point of
the registry: exact's quality ceiling is its score quantization, ``ivf``'s
additional loss on top is probing. Built with ``num_lists=1`` it is a pure
flat ADC scan; built with (or attached to, via ``attach``) a multi-list
index it scans the identical codes the ``ivf`` backend probes, which is
what makes "recall@10 vs flat" a pure measure of ``nprobe`` — the
backend-parity regression in tests/test_search.py pins ``ivf`` at
``nprobe = num_lists`` to this backend's exact output.

``ADCState`` is shared with the ``ivf`` backend: same index pytree, same
static serving knobs, so one build can serve both backends and ``refresh``
(``maintain.refresh_delta`` — disjoint GivensDelta only) behaves
identically.

Fused refresh (``SearchConfig.fused_refresh``): the index pytree — R,
centroids, codebooks, codes — is **frozen at build time** and rotation
deltas accumulate on the query side only. The state carries three extra
matrices: ``rot = R₀·Δ`` (the live rotation, for stats/health), ``wacc``
(the within-subspace part W of the accumulated delta) and
``qdelta = Δ·Wᵀ`` (the composed query-side LUT transform). LUTs are then
built as ``adc_lut(q·R₀·qdelta, C₀)`` — exactly equal to the eager path's
``adc_lut(q·R₀·Δ, C₀ rotated by W)`` because Wᵀ is block-diagonal per
subspace — via the rotation-fused kernel (kernels/lut_build.py). The
payoff: ``refresh(delta)`` is three (n, n) matmuls, no corpus-side buffer
moves, and for *purely within-subspace* deltas (exactly what
``rotations.subspace_gcd`` emits) ``qdelta`` is provably invariant — the
Engine keeps its whole LUT cache (``luts_refresh_invariant``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from repro import rotations
from repro.churn import buffer as churn_buffer
from repro.index import maintain
from repro.index import ivf as index_ivf
from repro.index import search as index_search
from repro.index.ivf import IVFPQIndex
from repro.kernels import ops as kops
from repro.kernels.common import use_kernels
from repro.search.base import SearchConfig, SearchResult, topk_padded


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ADCState:
    """Quantized-backend state: the servable index + static serving knobs.

    ``nprobe``/``max_blocks`` are read by the ``ivf`` backend only (the
    probe width default and the static probe-window size); ``flat_adc``
    scans everything. ``max_blocks = -1`` means "derive from the index at
    search time" — ``attach``/``build`` bake the concrete value so the
    serving hot path never host-syncs, but a directly-constructed
    ``ADCState(index=...)`` still searches exactly instead of silently
    truncating probed lists.

    ``lut_dtype`` selects the ADC-table precision streamed by the scan
    kernels. ``rot``/``wacc``/``qdelta`` are the fused-refresh matrices
    (module docstring); all three are None in eager mode — fused-ness is
    part of the pytree structure, so jit specializes per mode.
    """

    index: IVFPQIndex
    nprobe: int = dataclasses.field(default=8, metadata={"static": True})
    max_blocks: int = dataclasses.field(default=-1, metadata={"static": True})
    use_kernel: bool | None = dataclasses.field(
        default=None, metadata={"static": True})
    lut_dtype: str = dataclasses.field(
        default="float32", metadata={"static": True})
    rot: jax.Array | None = None     # (n, n) live rotation R₀·Δ (fused)
    wacc: jax.Array | None = None    # (n, n) within-subspace product W
    qdelta: jax.Array | None = None  # (n, n) query-side LUT transform Δ·Wᵀ
    # live-churn append buffer (repro.churn): staged rows are scanned by a
    # flat-ADC side pass and merged into every search below. None until
    # ``churn.with_staging`` installs one; like fused-ness, its presence is
    # pytree structure, so install it before the first search.
    staging: churn_buffer.StagingBuffer | None = None


def _fused_state(state: ADCState) -> ADCState:
    """Initialize the fused-refresh matrices at the build rotation
    (Δ = W = I: rot = R₀, qdelta = I)."""
    n = state.index.R.shape[0]
    eye = jnp.eye(n, dtype=state.index.R.dtype)
    return dataclasses.replace(state, rot=state.index.R, wacc=eye,
                               qdelta=eye)


def _adc_stats(name: str, state: ADCState) -> dict:
    index = state.index
    live = int(np.sum(np.asarray(index.ids) >= 0))
    code_bytes = int(index.codes.shape[1] * index.codes.dtype.itemsize)
    return dict(
        backend=name,
        rows=live,
        capacity=index.capacity,
        dim=index.dim,
        num_lists=index.num_lists,
        code_bytes_per_row=code_bytes,
        compression=float(index.dim * 4 / code_bytes),
        memory_bytes=int(index.codes.size * index.codes.dtype.itemsize),
        use_kernel=use_kernels(state.use_kernel),
        lut_dtype=state.lut_dtype,
        fused_refresh=state.rot is not None,
    )


@functools.partial(jax.jit, static_argnames=("sub",))
def _fused_refresh_mats(R0, rot, wacc, pi, pj, theta, sub: int):
    """Advance the fused matrices by one disjoint GivensDelta: the full
    delta composes into rot, its within-subspace part into wacc, and the
    query-side transform is recomputed as qdelta = R₀ᵀ·rot·waccᵀ
    (= Δ·Wᵀ — it cannot be updated incrementally from itself because the
    new within part must commute past the accumulated cross part)."""
    rot = kops.apply_pair_rotations(rot, pi, pj, theta, use_kernel=False)
    within = (pi // sub) == (pj // sub)
    theta_w = jnp.where(within, theta, 0.0)
    wacc = kops.apply_pair_rotations(wacc, pi, pj, theta_w, use_kernel=False)
    qdelta = R0.T @ rot @ wacc.T
    return rot, wacc, qdelta


def _refresh(state: ADCState, delta: rotations.RotationDelta) -> ADCState:
    if state.rot is None:
        return dataclasses.replace(
            state, index=maintain.refresh_delta(state.index, delta))
    # fused: index pytree frozen, only the query-side matrices move
    maintain.check_refreshable(delta)
    rot, wacc, qdelta = _fused_refresh_mats(
        state.index.R, state.rot, state.wacc,
        delta.pi, delta.pj, delta.theta, state.index.quantizer.sub)
    return dataclasses.replace(state, rot=rot, wacc=wacc, qdelta=qdelta)


def _rotate_queries(state: ADCState, Q: jax.Array) -> jax.Array:
    """Engine capability shared by both quantized backends: Q·R.

    In fused mode the index rotation is frozen at R₀ and the coarse term is
    exactly invariant (⟨q·R₀Δ, c·Δ⟩ = ⟨q·R₀, c⟩), so R₀ is the correct —
    and cache-stable — query rotation in both modes."""
    return Q @ state.index.R


def _luts(state: ADCState, QR: jax.Array):
    """Engine capability shared by both quantized backends: per-query ADC
    LUT pack over the residual quantizer. In fused mode the accumulated
    query-side transform is applied inside the LUT-build kernel; with an
    integer ``lut_dtype`` the tables are quantized to (qlut, scales)."""
    if state.qdelta is not None:
        cb_flat, colmap = state.index.quantizer.lut_operands()
        lut = kops.fused_lut(QR, state.qdelta, cb_flat, colmap,
                             use_kernel=use_kernels(state.use_kernel))
    else:
        lut = state.index.quantizer.adc_tables(QR)
    if state.lut_dtype != "float32":
        return kops.quantize_luts(lut, state.lut_dtype)
    return lut


def _luts_refresh_invariant(state: ADCState,
                            delta: rotations.RotationDelta) -> bool:
    """True iff cached LUT packs stay exactly valid across
    ``refresh(state, delta)``: fused mode and a purely within-subspace
    disjoint GivensDelta (then qdelta' = qdelta — module docstring).
    Host-side, conservative: any doubt returns False."""
    if state.rot is None:
        return False
    if not isinstance(delta, rotations.GivensDelta) or delta.overlapping:
        return False
    sub = state.index.quantizer.sub
    pi = np.asarray(delta.pi)
    pj = np.asarray(delta.pj)
    return bool(np.all((pi // sub) == (pj // sub)))


@functools.partial(jax.jit, static_argnames=("k",))
def _flat_search(state: ADCState, Q: jax.Array, k: int) -> SearchResult:
    QR = _rotate_queries(state, Q)
    lut = _luts(state, QR)
    return _flat_topk(state, QR, lut, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _flat_prepared(state: ADCState, QR: jax.Array, lut,
                   k: int) -> SearchResult:
    return _flat_topk(state, QR, lut, k)


def _flat_topk(state: ADCState, QR: jax.Array, lut,
               k: int) -> SearchResult:
    scores, cand_ids = index_search.flat_adc_prepared(
        state.index, QR, lut, use_kernel=state.use_kernel)
    top_scores, top_ids = topk_padded(scores, cand_ids, k)
    scanned = jnp.full((QR.shape[0],), state.index.capacity, jnp.int32)
    res = SearchResult(scores=top_scores, ids=top_ids, scanned=scanned)
    if state.staging is not None:
        res = churn_buffer.merge_staged(
            res, state.staging, QR, lut, state.index.centroids, k,
            use_kernel=state.use_kernel)
    return res


@dataclasses.dataclass(frozen=True)
class FlatADC:
    """Registry backend ``"flat_adc"`` (see module docstring)."""

    name: ClassVar[str] = "flat_adc"

    def build(self, key: jax.Array, corpus: jax.Array, R: jax.Array,
              cfg: SearchConfig) -> ADCState:
        index = index_ivf.build(key, corpus, R, cfg.ivf_config(),
                                train_size=cfg.train_size)
        return self.attach(index, use_kernel=cfg.use_kernel,
                           lut_dtype=cfg.lut_dtype,
                           fused_refresh=cfg.fused_refresh)

    @staticmethod
    def attach(index: IVFPQIndex, *, use_kernel: bool | None = None,
               lut_dtype: str = "float32",
               fused_refresh: bool = False) -> ADCState:
        """State over an existing index — flat-scan the very codes another
        backend probes (the parity-test and benchmark-sharing entry)."""
        state = ADCState(index=index, use_kernel=use_kernels(use_kernel),
                         max_blocks=index.max_list_blocks(),
                         lut_dtype=lut_dtype)
        return _fused_state(state) if fused_refresh else state

    @staticmethod
    def from_quantizer(R: jax.Array, quantizer, corpus: jax.Array, *,
                       block_size: int = 128,
                       use_kernel: bool | None = None) -> ADCState:
        """Serve a *pre-fit* quantizer (e.g. the PQ that OPQ's alternating
        minimization learned jointly with R) without refitting: the corpus
        is encoded as ``quantizer.encode(corpus @ R)`` under a single
        zero-centroid coarse list, so the served codes are exactly the
        quantizer's own."""
        from repro import quant
        XR = jnp.asarray(corpus) @ jnp.asarray(R).astype(corpus.dtype)
        coarse = quant.VQ(centroids=jnp.zeros((1, XR.shape[1]), XR.dtype))
        list_ids, codes = index_ivf.encode(XR, coarse, quantizer)
        ids = jnp.arange(XR.shape[0], dtype=jnp.int32)
        index = index_ivf.pack(R, coarse, quantizer, codes, list_ids, ids,
                               block_size=block_size)
        return FlatADC.attach(index, use_kernel=use_kernel)

    def search(self, state: ADCState, Q: jax.Array, *,
               k: int = 10) -> SearchResult:
        return _flat_search(state, Q, k)

    # -- Engine LUT-cache capabilities -------------------------------------
    def rotate_queries(self, state: ADCState, Q: jax.Array) -> jax.Array:
        return _rotate_queries(state, Q)

    def luts(self, state: ADCState, QR: jax.Array):
        return _luts(state, QR)

    def luts_refresh_invariant(self, state: ADCState,
                               delta: rotations.RotationDelta) -> bool:
        return _luts_refresh_invariant(state, delta)

    def search_prepared(self, state: ADCState, QR: jax.Array,
                        lut, *, k: int = 10) -> SearchResult:
        return _flat_prepared(state, QR, lut, k)

    def refresh(self, state: ADCState,
                delta: rotations.RotationDelta) -> ADCState:
        return _refresh(state, delta)

    def stats(self, state: ADCState) -> dict:
        st = _adc_stats(self.name, state)
        st["scan_rows_per_query"] = st["capacity"]
        return st

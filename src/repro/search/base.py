"""Searcher protocol + SearchConfig — the ``repro.search`` core contract.

Every retrieval backend in the repo — exact brute force, flat ADC over
PQ/RQ codes, and the IVF probe/scan pipeline — serves through one
optax/quant-style protocol (mirroring the searcher abstraction of the
ScaNN codebase, Guo et al. 2020):

    searcher = search.make("ivf")
    state    = searcher.build(key, corpus, R, cfg)     # offline
    result   = searcher.search(state, Q, k=10)         # hot path, jit'd
    state    = searcher.refresh(state, delta)          # live GCD step
    facts    = searcher.stats(state)                   # host-side dict

``build`` consumes the *learned rotation* R (the paper's serving transform
T(X) = φ(XR)Rᵀ — every backend rotates queries by the same R before
scoring) and a shared ``SearchConfig``, so the same (key, corpus, R, cfg)
triple is comparable across backends — the registry sweep in
``benchmarks/ivf_recall_qps.py`` runs all of them on one harness.

``refresh`` consumes a ``rotations.RotationDelta`` — the same pytree a
``RotationLearner.update`` step returns — so training and serving share one
refresh path: the trainer's delta is fed both to its own state and to the
live searcher, and the served rotation tracks the trained one without a
corpus re-encode (see ``index.maintain``). The ADC backends require a
disjoint ``GivensDelta`` (dense Cayley/Procrustes factors do not factor
into per-subspace codebook rotations); ``exact`` absorbs any delta.

Every backend returns the same ``SearchResult`` pytree with a well-defined
padding contract: when ``k`` exceeds the surviving candidate count, tail
slots carry ``id = −1`` and ``score = −inf``, and ``metrics.recall_at_k``
never counts padding as a hit.

States are jit-traceable pytrees whose serving knobs (tile/probe window
sizes, kernel toggles) are static aux fields, so ``jax.jit`` specializes
per layout and a state can be swapped under a compiled executable as long
as its shapes are unchanged — which is exactly what ``refresh`` guarantees,
and what lets ``search.Engine`` keep its compile cache warm across live
rotation refreshes.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Protocol, runtime_checkable

import jax

from repro import rotations
# SearchResult and the top-k/padding contract predate this package
# (repro.index.search, PR 1) and are re-exported as the one result type +
# padding behavior every backend shares.
from repro.index.search import (  # noqa: F401
    NEG_INF,
    SearchResult,
    topk_padded,
)


class SearchConfig(NamedTuple):
    """Backend-shared build parameters (each backend reads its slice).

    Quantized backends (``flat_adc``, ``ivf``) build an IVF-PQ/RQ index:
    ``subspaces``/``codewords``/``depth`` configure the residual quantizer,
    ``num_lists`` the coarse partition (``flat_adc`` defaults to scanning
    whatever partition it is given — 1 list makes it a pure flat scan),
    ``block_size`` the CSR/Pallas tile, ``train_size`` caps the k-means
    sample. ``nprobe`` is the ``ivf`` backend's default probe width (a
    per-call override exists). ``exact`` only reads ``tile_rows`` — the
    corpus tile of its streaming brute-force scan. ``use_kernel`` picks the
    Pallas kernels (True) or their jnp references (False); None — the
    default — resolves at build time to the kernels on a TPU backend and the
    references elsewhere (``kernels.common.use_kernels``).

    ``lut_dtype`` quantizes the ADC lookup tables the scan kernels stream
    ("float32" | "int8" | "uint8" — integer dtypes store per-subspace
    scales alongside and dequantize in VMEM, quartering LUT bytes moved).
    ``fused_refresh`` makes the ADC/exact backends absorb rotation deltas
    into the *query-side* transform only: corpus buffers are frozen at
    build time, ``refresh(delta)`` swaps one (n, n) matrix, and cached
    LUTs stay valid for within-subspace deltas (kernels/lut_build.py).
    """

    subspaces: int = 8
    codewords: int = 256
    depth: int = 1
    num_lists: int = 1
    nprobe: int = 8
    block_size: int = 128
    tile_rows: int = 4096
    train_size: int | None = None
    use_kernel: bool | None = None
    lut_dtype: str = "float32"
    fused_refresh: bool = False

    def ivf_config(self):
        """The ``IVFPQConfig`` slice consumed by the quantized backends."""
        from repro import quant
        from repro.index.ivf import IVFPQConfig
        return IVFPQConfig(
            num_lists=self.num_lists,
            pq=quant.PQConfig(self.subspaces, self.codewords),
            block_size=self.block_size,
            depth=self.depth,
            lut_dtype=self.lut_dtype,
        )


@runtime_checkable
class Searcher(Protocol):
    """The retrieval-backend protocol (see module docstring).

    Implementations are frozen dataclasses (hashable; safe to close over in
    jit) holding no per-corpus data — everything lives in the state pytree.
    Backends may expose extra capabilities the Engine sniffs for:
    ``rotate_queries``/``luts``/``search_prepared`` (ADC LUT caching) and
    per-call ``nprobe`` overrides (``ivf``).
    """

    def build(self, key: jax.Array, corpus: jax.Array, R: jax.Array,
              cfg: SearchConfig) -> Any:
        """Offline: index ``corpus`` under the learned rotation ``R``."""
        ...

    def search(self, state: Any, Q: jax.Array, *, k: int = 10) -> SearchResult:
        """Top-``k`` by inner product for a (b, n) query batch."""
        ...

    def refresh(self, state: Any, delta: rotations.RotationDelta) -> Any:
        """Absorb a rotation-learner step into the servable state."""
        ...

    def stats(self, state: Any) -> dict:
        """Host-side serving facts (rows, scan work, memory, knobs)."""
        ...



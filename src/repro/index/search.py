"""Batched IVF-PQ query engine.

Per query batch (b, n):

  1. rotate:       QR = Q·R  (the paper's serving transform)
  2. probe:        coarse scores QR·Cᵀ, keep the top-``nprobe`` lists
  3. LUT build:    ``index.quantizer.adc_tables(QR)`` — one
                   (code_width, K) table per query against the *residual*
                   quantizer, shared by all probes. Residual depth is
                   already flattened into code_width, so PQ and RQ feed
                   the same kernel.
  4. scan:         selected list blocks scored by the fused Pallas kernel
                   (kernels/ivf_adc.py) or its jnp oracle; the coarse term
                   ⟨q·R, c_l⟩ is added per block group outside the kernel
  5. top-k:        over nprobe·max_blocks·block_size masked candidates

Because every list is padded to whole ``block_size`` tiles (ivf.pack), the
probe window of each (query, list) pair is a fixed ``max_blocks`` tiles:
shorter lists redirect their out-of-range tiles to the index's all-hole
sentinel block, whose ids are −1 and therefore score −inf. So the scan
schedules nprobe·max_blocks·block_size rows per query, and scores only the
rows of the probed lists' own tiles (``SearchResult.scanned``): a step on
the sentinel block stores −inf and skips its tile work in the kernel. The
rows scored, against the corpus size for the flat scan, are the
recall/work trade-off, and it is entirely in ``nprobe``.

Device sharding: under an active mesh the candidate axis is annotated with
the ``ivf`` rule table (sharding/rules.py) so XLA splits list scanning over
the "model" axis while the query batch stays data-parallel. The row-sharded
deployment (``search/sharded.py``) instead runs ``_search_core`` as the
shard-local body of a shard_map — each device probes the shared centroids
but scans only its own CSR shard, and per-shard top-k runs merge
cross-device.

This module is the IVF *mechanism*; the serving front door is
``repro.search`` (Searcher registry + batching Engine), whose ``ivf`` and
``flat_adc`` backends dispatch here. The ``*_prepared`` variants take the
rotated queries and ADC LUTs as explicit operands so the Engine can cache
per-query LUTs across requests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.index.ivf import IVFPQIndex
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.common import use_kernels
from repro.sharding import rules as sh

NEG_INF = -jnp.inf


class SearchResult(NamedTuple):
    scores: jax.Array   # (b, k) approximate inner products, descending
    ids: jax.Array      # (b, k) item ids (−1 where fewer than k candidates)
    scanned: jax.Array  # (b,) CSR rows scanned per query (scan-work metric)


def topk_padded(scores: jax.Array, cand_ids: jax.Array,
                k: int) -> tuple[jax.Array, jax.Array]:
    """The one top-k + padding contract every retrieval path shares.

    ``cand_ids`` is (C,) or (b, C); masked candidates must already score
    −inf. Returns (b, k) scores/ids padded with (−inf, −1) when k > C or
    when fewer than k finite candidates survive. The core lives in
    ``kernels.ref.topk_merge_ref`` (also the cross-shard merge of the
    sharded searchers) so the contract has exactly one implementation.
    """
    b, C = scores.shape
    if cand_ids.ndim == 1:
        cand_ids = jnp.broadcast_to(cand_ids[None, :], (b, C))
    return kref.topk_merge_ref(scores, cand_ids, k)


def build_luts(quantizer, QR: jax.Array, lut_dtype: str = "float32"):
    """Build ADC tables for rotated queries, optionally quantized.

    Returns the **LUT pack** convention every scan path downstream accepts:
    a plain (b, Dp, K) float32 array for ``lut_dtype="float32"``, or a
    ``(qlut, scales)`` tuple from ``kernels.quantize_luts`` for
    int8/uint8 — a pytree, so packs flow through jit, shard_map, and the
    Engine's LUT cache unchanged.
    """
    lut = quantizer.adc_tables(QR)
    if lut_dtype == "float32":
        return lut
    return kops.quantize_luts(lut, lut_dtype)


def split_lut_pack(lut):
    """LUT pack -> (lut, scales | None) for the kernel call sites."""
    if isinstance(lut, tuple):
        qlut, scales = lut
        return qlut, scales
    return lut, None


def probe(index: IVFPQIndex, QR: jax.Array,
          nprobe: int) -> tuple[jax.Array, jax.Array]:
    """Top-``nprobe`` lists per rotated query: ((b, p) lists, (b, p) coarse
    scores ⟨q·R, c_l⟩ — the additive coarse term of the final score)."""
    coarse = QR @ index.centroids.T  # (b, L)
    cscores, lists = jax.lax.top_k(coarse, nprobe)
    return lists, cscores


def candidate_blocks(index: IVFPQIndex, lists: jax.Array,
                     max_blocks: int) -> tuple[jax.Array, jax.Array]:
    """Tile schedule for the probed lists.

    Returns (block_idx (b, p, B) int32 tile indices into the CSR codes
    array, valid (b, p, B) bool). Out-of-range tiles of short lists point at
    the sentinel hole block (still masked via ids, but ``valid`` lets the
    scan-work metric count only real tiles).
    """
    bs = index.block_size
    starts = index.list_offsets[lists] // bs                      # (b, p)
    nblocks = (index.list_offsets[lists + 1]
               - index.list_offsets[lists]) // bs                 # (b, p)
    k = jnp.arange(max_blocks, dtype=jnp.int32)
    blk = starts[..., None] + k
    valid = k < nblocks[..., None]
    return jnp.where(valid, blk, index.sentinel_block).astype(jnp.int32), valid


def _search_core(index: IVFPQIndex, QR: jax.Array, lut, *,
                 nprobe: int, k: int, max_blocks: int,
                 use_kernel: bool | None) -> SearchResult:
    """Probe + scan + top-k over already-rotated queries and built LUTs.
    ``lut`` is a LUT pack (plain f32 array or (qlut, scales)).
    ``use_kernel=None`` scans with the Pallas kernel on a TPU backend and
    the jnp reference elsewhere (``kernels.common.use_kernels``)."""
    b = QR.shape[0]
    bs = index.block_size
    QR = sh.constrain(QR, ("act_batch", None), sh.IVF_RULES)

    lists, cscores = probe(index, QR, nprobe)

    blk, valid = candidate_blocks(index, lists, max_blocks)    # (b, p, B)
    S = b * nprobe * max_blocks
    block_idx = blk.reshape(S)
    block_query = jnp.repeat(
        jnp.arange(b, dtype=jnp.int32), nprobe * max_blocks
    )

    lut, scales = split_lut_pack(lut)
    # holes/tombstones (id < 0) are masked to −inf inside the tile body, and
    # steps on the sentinel block score −inf without the tile work; adding
    # the finite coarse term afterwards cannot resurrect them
    res_scores = kops.ivf_adc(
        lut, index.codes, block_idx, block_query, scales, index.ids,
        block_size=bs, hole_block=index.sentinel_block,
        use_kernel=use_kernels(use_kernel),
    ).reshape(b, nprobe, max_blocks, bs)
    scores = res_scores + cscores[:, :, None, None]            # + coarse term

    # the candidate-id gather and the top-k over every scheduled row, named
    # in the compiled program's metadata so a profile can read the stage
    with jax.named_scope("ivf.select"):
        rows = blk[..., None] * bs + jnp.arange(bs)            # (b, p, B, bs)
        cand_ids = index.ids[rows]
        scores = sh.constrain(
            scores.reshape(b, -1), ("act_batch", "ivf_cand"), sh.IVF_RULES
        )

        # k can exceed the candidate pool (small nprobe, large k): the
        # shared contract clamps the top_k and pads back out to (b, k) with
        # (−inf, −1)
        top_scores, top_ids = topk_padded(scores, cand_ids.reshape(b, -1), k)
    scanned = jnp.sum(valid.reshape(b, -1), axis=1) * bs
    return SearchResult(scores=top_scores, ids=top_ids, scanned=scanned)


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "k", "max_blocks", "use_kernel", "lut_dtype"),
)
def search_fixed(index: IVFPQIndex, Q: jax.Array, *, nprobe: int, k: int = 10,
                 max_blocks: int, use_kernel: bool | None = None,
                 lut_dtype: str = "float32") -> SearchResult:
    """Jit-friendly core: ``max_blocks`` (the per-list probe window in tiles,
    ≥ index.max_list_blocks() for exactness) is passed statically."""
    # constrain before the LUT build so the (b, Dp, K) tables inherit the
    # act_batch annotation at their producer under an active mesh
    QR = sh.constrain(Q @ index.R, ("act_batch", None), sh.IVF_RULES)
    lut = build_luts(index.quantizer, QR, lut_dtype)           # (b, Dp, K)
    return _search_core(index, QR, lut, nprobe=nprobe, k=k,
                        max_blocks=max_blocks, use_kernel=use_kernel)


@functools.partial(
    jax.jit, static_argnames=("nprobe", "k", "max_blocks", "use_kernel")
)
def search_prepared(index: IVFPQIndex, QR: jax.Array, lut, *,
                    nprobe: int, k: int = 10, max_blocks: int,
                    use_kernel: bool | None = None) -> SearchResult:
    """``search_fixed`` with the rotate + LUT-build steps hoisted out: the
    caller supplies ``QR = Q·R`` and a LUT pack (``build_luts`` output).
    The ``search.Engine`` uses this to reuse cached per-query LUTs."""
    return _search_core(index, QR, lut, nprobe=nprobe, k=k,
                        max_blocks=max_blocks, use_kernel=use_kernel)


def search(index: IVFPQIndex, Q: jax.Array, *, nprobe: int, k: int = 10,
           use_kernel: bool | None = None,
           lut_dtype: str = "float32") -> SearchResult:
    """Batched ANN search: (b, n) queries -> top-k (scores, ids, scanned).

    Convenience wrapper that reads the probe-window size off the concrete
    index (one host sync) and dispatches to the jit'd ``search_fixed``.
    """
    nprobe = min(nprobe, index.num_lists)
    return search_fixed(
        index, Q, nprobe=nprobe, k=k,
        max_blocks=index.max_list_blocks(), use_kernel=use_kernel,
        lut_dtype=lut_dtype,
    )


def flat_adc_scores(index: IVFPQIndex, Q: jax.Array, *,
                    use_kernel: bool | None = None,
                    lut_dtype: str = "float32") -> tuple[jax.Array, jax.Array]:
    """Flat baseline over the same quantized representation: score every CSR
    row (coarse term + residual ADC). Returns ((b, cap) scores with holes at
    −inf, (cap,) ids) — the exactness oracle for nprobe = num_lists and the
    scan-work baseline for the recall/QPS benchmark."""
    QR = Q @ index.R
    lut = build_luts(index.quantizer, QR, lut_dtype)
    return flat_adc_prepared(index, QR, lut, use_kernel=use_kernel)


def flat_adc_prepared(index: IVFPQIndex, QR: jax.Array, lut, *,
                      use_kernel: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """``flat_adc_scores`` with rotate + LUT-build hoisted out (Engine LUT
    cache entry point, mirroring ``search_prepared``). ``lut`` is a LUT
    pack."""
    lut, scales = split_lut_pack(lut)
    # holes/tombstones (id < 0) are masked to −inf inside the tile body
    res = kops.adc_lookup(lut, index.codes, scales, index.ids,
                          use_kernel=use_kernels(use_kernel))  # (b, cap)
    # coarse term per row: row r belongs to list l iff offsets[l] ≤ r < offsets[l+1]
    row_list = jnp.searchsorted(
        index.list_offsets, jnp.arange(index.capacity), side="right"
    ) - 1
    row_list = jnp.clip(row_list, 0, index.num_lists - 1).astype(jnp.int32)
    coarse = QR @ index.centroids.T                                 # (b, L)
    scores = res + coarse[:, row_list]
    return scores, index.ids
